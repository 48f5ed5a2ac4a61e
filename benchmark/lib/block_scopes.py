"""Device time under the scopes that a block's mechanisms open beyond
``program_trace.SCOPES``: ``residual_mix`` and ``moe`` inside
``attention`` and ``ffn``, and ``mtp`` around the next-next-token
module's whole layer (``distributedmnist_tpu/obsv/spans.py``). The
readers ``latent_attention_ms_per_step``, ``moe_ms_per_step``,
``residual_mix_ms_per_step`` and ``mtp_ms_per_step`` are built on this
file, and the two routing counters on :func:`step_records`.

``program_trace.scope_path`` keeps the names it finds in that module's
``SCOPES``, a list this PR may not edit: :func:`table` reads the trace
with the three names added for the length of one call and puts the list
back. (A ``benchmark`` PR adds the names to the list and takes this
detour out: PERF.md §7.)"""

from __future__ import annotations

import contextlib
import json
import sys
from pathlib import Path

if __package__ in (None, ""):            # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmark.lib import program_trace  # noqa: E402

TRAIN_STEP = "jit_shard_fn"
BLOCK_SCOPES = ("residual_mix", "moe", "mtp")


@contextlib.contextmanager
def _also(names: tuple[str, ...]):
    was = program_trace.SCOPES
    program_trace.SCOPES = was + tuple(n for n in names if n not in was)
    try:
        yield
    finally:
        program_trace.SCOPES = was


def table(reduced: dict) -> dict | None:
    """The scope table of the train step in the run ``reduced`` came
    from, by paths that keep :data:`BLOCK_SCOPES`; None where the trace
    has no execution of the train step."""
    trace = program_trace.this_run(reduced)["trace"]
    if not program_trace.executions(trace, TRAIN_STEP)[0]:
        return None
    with _also(BLOCK_SCOPES):
        return program_trace.scope_table(trace, TRAIN_STEP)


def ms(reduced: dict, inside: str, outside: tuple[str, ...] = ()) -> float | None:
    """ms an execution, forward, recomputed and backward together, of
    the operations under scope ``inside`` and under none of
    ``outside``. None where the program opens no such scope (a program
    that predates it), so that the line leaves the metric out."""
    found = table(reduced)
    if found is None:
        return None
    total, seen = 0.0, False
    for (path, _), value in found["by_scope"].items():
        parts = path.split("/")
        if inside in parts and not any(o in parts for o in outside):
            total, seen = total + value, True
    return total if seen else None


def step_records(reduced: dict) -> list[dict]:
    """The trainer's step records of this run
    (``<workdir>/train/train_log.jsonl``) that carry ``expert_counts``:
    a row a routed layer of the pairs each held expert took."""
    path = (program_trace.this_run(reduced)["workdir"] / "train"
            / "train_log.jsonl")
    if not path.exists():
        return []
    with open(path, encoding="utf-8") as f:
        records = [json.loads(line) for line in f if line.strip()]
    return [r for r in records
            if r.get("event") == "step" and r.get("expert_counts")]


def experts_per_token(reduced: dict) -> int:
    """How many experts a token takes in the run's cell, by its
    configuration file."""
    from benchmark.lib import cell as cell_lib
    cell = cell_lib.load_cell(program_trace.this_run(reduced)["workdir"].name)
    return int(cell.config["num_experts_per_tok"])


#: the six readers built on this file, by metric name
READERS = ("latent_attention_ms_per_step", "moe_ms_per_step",
           "residual_mix_ms_per_step", "mtp_ms_per_step",
           "moe_pairs_held_share", "moe_expert_load_max_over_mean")


def describe(tokens_per_step: int) -> None:
    """Print, for the newest traced run under ``runtime.WORK_ROOT``, the
    train step's scope table with the block's scopes kept and the six
    readers' values (``BENCHMARK.json`` lists the six since PR 42; the
    scope table is how PERF.md §5's table of the cell is made):

        python3 benchmark/lib/block_scopes.py <tokens a step>

    after a ``--trace 1`` run of the cell, from the same checkout."""
    import glob
    import os

    from benchmark.lib import cell as cell_lib, trace_reduce
    from benchmark.lib.runtime import WORK_ROOT
    found = glob.glob(os.path.join(WORK_ROOT, "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    reduced = trace_reduce.reduce(trace_reduce.load(
        max(found, key=os.path.getmtime)))
    scopes = table(reduced)
    rows: dict[str, dict] = {}
    for (path, which), value in scopes["by_scope"].items():
        rows.setdefault(path, {})[which] = round(value, 2)
    print(f"{scopes['executions']} executions, "
          f"{scopes['total_ms']:.2f} ms each")
    for path, parts in sorted(rows.items(),
                              key=lambda kv: -sum(kv[1].values())):
        print(f"{sum(parts.values()):9.2f}  {path:36s} {parts}")
    print("kernels", {k: round(v, 2)
                      for k, v in scopes["by_kernel"].items()})
    for name in READERS:
        print(name, cell_lib.load_reader(name).read(
            reduced, {"tokens_per_step": tokens_per_step}))


if __name__ == "__main__":
    describe(int(sys.argv[1]))
