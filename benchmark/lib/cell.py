"""Resolve a cell by name. ``BENCHMARK.json`` lists cells, metrics and
configurations; everything that belongs to one of them is a file of its
own that is found by the name in that list, so a later PR adds a cell by
adding files and entries and edits nothing:

* ``benchmark/configs/<config>.json`` is named by the entry's ``file``;
* ``benchmark/archs/<arch>.py`` by the configuration file's ``arch``;
* ``benchmark/traffic/<traffic>.json`` is found beside it by name;
* ``benchmark/drivers/<kind>.py`` by the traffic file's ``kind``;
* ``benchmark/layer_metrics/<metric>.py`` by the metric's name.

An architecture file is the one place that knows a model's block: the
drivers, the harness and the tests reach the block through it and hand
it the configuration file, whatever keys its source gave it. It is what
``correct`` is decided against. Its interface:

* ``model_section(config) -> dict``: the source's keys to the program's
  ``model`` section, sizes only, raising :class:`BenchmarkError` for a
  shape the program cannot run;
* ``logits(params, tokens, config, last=None)`` and
  ``loss(params, tokens, config)``: the plain reference, float32 at
  ``highest`` matrix precision, importing nothing from the program;
* ``train_flops_per_token(config, seq_len)``,
  ``attention_train_flops_per_token(config, seq_len)`` and
  ``decode_bytes_per_step(config, contexts, weight_bytes=2, kv_bytes=2)``:
  the model's own counts, as ``lib/flops.py`` defines them
  (recomputation, padding, casts and copies never count).

An architecture that routes tokens to experts says so by four more
members; a file without ``routing_slack`` is compared free-running, as
above. A top-k is a discontinuity: at a near tie a bfloat16 program and
a float32 reference pick different experts, at every seed, so the
reference follows the program's choices and the choices are judged on
their own (``lib/compare.py`` has the limits):

* ``logits(..., routing=None)`` and ``loss(..., routing=None)`` take
  ``routing``, the program's choice, int32 ``[routed_layers, batch,
  seq, k]`` of expert ids (the file states the order of the layers and
  what ``k`` is). Given it, the reference sends each position to those
  experts and computes their gates from ITS OWN scores over that set;
* ``routing_slack(params, tokens, config, routing) -> float32
  [routed_layers, batch, seq]``: on that forced path, how far the worst
  forced expert's selection score (after any selection bias) lies below
  the reference's own k-th best, in units of the rms spread of that
  position's selection scores over the experts; 0 where the two sets
  are equal;
* ``routed_experts(config) -> int``: how many routed experts a layer
  has. The harness, not the file, refuses any (layer, position) whose k
  ids are not k different experts in ``[0, routed_experts)``: picking
  the best expert k times has slack 0, and a reference that follows
  repeated ids agrees with it.

The program hands its choices over through exports of its model record,
which the PR that adds the model adds to the program: ``apply(params,
tokens, train=False, return_aux=True) -> (logits, aux)`` with
``aux["routing"]`` (training cells); ``decode_prefill(params, tokens,
return_routing=True) -> (logits, ks, vs, routing)`` and
``decode_step(..., return_routing=True) -> (logits, k_cache, v_cache,
routing[routed_layers, slots, k])`` (serving cells). A routed
architecture whose record offers none of this, or whose export takes
the keyword and returns no routing, is a :class:`BenchmarkError`, never
a free-running compare. **The contract of the flag: it adds an output
and changes nothing else.** The timed path calls the exports without
it, so both checks run them both ways on the same inputs: the logits
(and the loss) that are compared are those without the flag, and those
with it have to equal them bit for bit (``routing_flag_diff``, limit
0). A program that routes through another kernel when nobody asks for
the choices is so not vouched for by the choices of the one that was
asked. (Exports that always return their routing would need no second
program, but the replica and the trainer unpack what the exports
return today, and a benchmark PR changes no file of the program.) The
serving check asks each call for its choices after the unasked one, over
the state that one has just written.

**A decode session** is the one line of the serving check
(``lib/serving.py::check_decode_against_reference``) that depends on how
a sequence's state is held. The harness keeps the seeded sequence, the
teacher forcing, logits and never tokens, the reference's call, the
limits and the verdict; the session is

* ``prefill(prompt[int32 n], return_routing=False) -> logits[vocab]`` of
  the prompt's last position, which leaves the prompt's state in the
  session's one sequence (slot 0 of whatever the step is batched over);
* ``step(token: int, position: int, return_routing=False) ->
  logits[vocab]``, which advances that sequence by the token at that
  position;
* asked for its routing, either returns ``(logits, routing)`` beside,
  ``routing`` int32 ``[routed_layers, n, k]`` from the prefill and
  ``[routed_layers, k]`` from a step: the flag adds an output and
  changes nothing else, the state included. The check repeats every
  call with the flag right after the unasked one, so a step asked again
  at the position it has just stepped leaves the state as the unasked
  step left it (rows a token get that for nothing: the same token at
  the same position writes the same rows; a state that is a sequence's
  and not a token's keeps what it stepped from);
* optionally ``said``, a dictionary the ``reference_check`` event
  carries under ``session`` (the default one's: ``attention_arm``,
  ``cache_arrays``, ``table_blocks``, ``step_compiled_bytes``).

The default session, ``lib/serving.py::PagedSession``, is what every
record without one of its own is driven through: the record's
``decode_prefill`` and ``decode_step`` over a scratch ``PagedKVCache``
built as ``DecodeReplica.__init__`` builds the replica's
(``kv_cache.cache_shapes`` of ``decode_cache_shape``, rows as wide as
``kv_cache.stored_head_dim`` answers, so a cache whose two arrays differ
in width needs no edit here), the step jitted and handed the narrowest
table width as the replica's loop does. A model whose state is not two
paged arrays (a row a sequence, a window, any rank and count of arrays)
brings ``decode_session(params, dcfg, cache_dtype) -> session`` on its
model record, added to the program by the PR that adds the model: **the
session is the code the replica itself admits and steps a sequence
through** (not a second forward written for the check), it takes the
replica's ``decode`` section and cache dtype, and it holds whatever
arrays the model needs. Where the record carries the export the check
drives it; no record does today. ``lib/decode_controls.py``'s faults in
the weights reach either kind of session; its fault in the step wraps
``decode_step`` and is the default session's alone.

**Adding a cell, a configuration or a metric** is appending: the new
entry goes at the END of its list in ``BENCHMARK.json`` (the driver
compares the accepted entries by position), and a metric that an
existing cell should report lists that cell in its own ``workloads``. No
test under ``tests/benchmark/`` depends on how many entries a list has
or on which is last (``test_bench_contract.py`` rehearses a seventh
cell and a new last metric on a copy).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmark"


class BenchmarkError(Exception):
    """The benchmark cannot run or cannot vouch for its result."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file, as it is run
    arch: types.ModuleType   # benchmark/archs/<config["arch"]>.py
    traffic_name: str
    traffic: dict         # the traffic mix's parameters
    end_to_end: tuple[dict, ...]   # metric entries this cell reports
    per_layer: tuple[dict, ...]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _metrics_for(entries: list[dict], cell: str) -> tuple[dict, ...]:
    """A metric belongs to every cell unless it lists its cells."""
    return tuple(m for m in entries
                 if "workloads" not in m or cell in m["workloads"])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {name!r} in BENCHMARK.json (known: "
            f"{[w['name'] for w in bench['workloads']]})")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchmarkError(f"workload {name!r} names configuration "
                             f"{entry['config']!r}, which is not listed")
    traffic_path = (root / "benchmark" / "traffic"
                    / f"{entry['traffic']}.json")
    config = load_json(root / cfg_entry["file"])
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"],
                config=config, arch=load_arch(config, root),
                traffic_name=entry["traffic"],
                traffic=load_json(traffic_path),
                end_to_end=_metrics_for(bench["end_to_end"], name),
                per_layer=_metrics_for(bench["per_layer"], name))


def _load_module(path: Path, label: str):
    if not path.exists():
        raise BenchmarkError(f"{label}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{label}_{path.stem}".replace("-", "_").replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_driver(kind: str, root: Path = ROOT):
    """The driver of one traffic kind: a module with
    ``run(cell, args, rt) -> dict``."""
    return _load_module(root / "benchmark" / "drivers" / f"{kind}.py",
                        "driver")


def load_reader(metric: str, root: Path = ROOT):
    """The reader of one per-layer metric: a module with
    ``read(ctx) -> float | None``."""
    return _load_module(root / "benchmark" / "layer_metrics"
                        / f"{metric}.py", "reader")


def load_arch(config: dict, root: Path = ROOT):
    """The architecture a configuration file names: a module with the
    interface in this file's docstring. There is no default: a file
    that does not say what its block is cannot be checked."""
    if "arch" not in config:
        raise BenchmarkError(
            "the configuration names no architecture: give it an "
            '"arch" key, the name of a file under benchmark/archs/')
    return _load_module(root / "benchmark" / "archs"
                        / f"{config['arch']}.py", "arch")
