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
