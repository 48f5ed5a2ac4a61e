"""What a routed, latent block adds to the decode step, read from a
serving run: device time under the scopes it opens inside
``jit_decode_step`` beyond ``program_trace.SCOPES`` (``latent_absorb``
inside ``attention``, ``moe`` inside ``ffn``:
``distributedmnist_tpu/obsv/spans.py``), and the two routing counters of
the replica's heartbeat (``expert_pairs_held``, ``experts_touched``: of
the step before the heartbeat, which is written when a request ends).
The readers ``decode_absorb_ms_per_step``, ``decode_moe_ms_per_step``,
``decode_experts_touched_p50`` and ``decode_pairs_per_touched_expert_p50``
are built on this file (the whole of ``attention`` is the accepted
``decode_attention_ms_per_step``'s, which lists a latent cell too). A program that opens no such scope or writes no
such field (the plain block's, any parent's) gives None, never an error.

``program_trace.scope_path`` keeps the names of that module's ``SCOPES``,
a list no PR but a ``benchmark`` one edits: :func:`table` reads the trace
with the two names added for one call, as ``lib/block_scopes.py`` does
for the train step (one detour; PERF.md §7 says what takes both out).

``BENCHMARK.json`` lists the four since PR 42 (a traced run's result
line carries them); this prints them with the step's scope table, which
no metric carries, for the newest traced run under
``runtime.WORK_ROOT``:

    python3 benchmark/lib/decode_scopes.py

after a ``--trace 1`` run of a serving cell, from the same checkout."""

from __future__ import annotations

import json
import sys
from pathlib import Path

if __package__ in (None, ""):            # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmark.lib import program_trace  # noqa: E402
from benchmark.lib.block_scopes import _also  # noqa: E402

DECODE_SCOPES = ("latent_absorb", "moe")
#: the four readers built on this file, by metric name
READERS = ("decode_absorb_ms_per_step", "decode_moe_ms_per_step",
           "decode_experts_touched_p50",
           "decode_pairs_per_touched_expert_p50")


def table(reduced: dict) -> dict | None:
    """The scope table of the decode step in the run ``reduced`` came
    from, by paths that keep :data:`DECODE_SCOPES`; None where the trace
    has no execution of the step."""
    trace = program_trace.this_run(reduced)["trace"]
    if not program_trace.executions(trace, program_trace.DECODE_STEP)[0]:
        return None
    with _also(DECODE_SCOPES):
        return program_trace.scope_table(trace, program_trace.DECODE_STEP)


def ms(reduced: dict, inside: str) -> float | None:
    """ms an execution of the operations under scope ``inside``. None
    where the step opens no such scope: the program is then not the one
    the metric is of."""
    found = table(reduced)
    if found is None:
        return None
    paths = [path.split("/") for path, _ in found["by_scope"]]
    if not any(inside in parts for parts in paths):
        return None
    return program_trace.scope_ms(found, inside)


def heartbeats(reduced: dict) -> list[dict]:
    """The replica's heartbeats of this run inside the load's window
    (``<workdir>/serve/train_log.jsonl``) that carry the routing
    counters."""
    run = program_trace.this_run(reduced)
    path = run["workdir"] / "serve" / "train_log.jsonl"
    if not path.exists():
        return []
    with open(run["workdir"] / "load.json", encoding="utf-8") as f:
        load_ = json.load(f)
    lo, hi = load_["window_start"], load_["window_end"]
    with open(path, encoding="utf-8") as f:
        records = [json.loads(line) for line in f if line.strip()]
    return [r for r in records
            if r.get("event") == "heartbeat" and "experts_touched" in r
            and lo <= r.get("time", lo) < hi]


def describe() -> None:
    import glob
    import os

    from benchmark.lib import cell as cell_lib, trace_reduce
    from benchmark.lib.runtime import WORK_ROOT
    found = glob.glob(os.path.join(WORK_ROOT, "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    reduced = trace_reduce.reduce(trace_reduce.load(
        max(found, key=os.path.getmtime)))
    scopes = table(reduced)
    if scopes is not None:
        rows: dict[str, float] = {}
        for (path, _), value in scopes["by_scope"].items():
            rows[path] = rows.get(path, 0.0) + value
        print(f"{scopes['executions']} executions of "
              f"{program_trace.DECODE_STEP}, {scopes['total_ms']:.2f} ms each")
        for path, value in sorted(rows.items(), key=lambda kv: -kv[1]):
            print(f"{value:9.3f}  {path}")
        print("kernels", {k: round(v, 2)
                          for k, v in scopes["by_kernel"].items()})
    for name in READERS:
        print(name, cell_lib.load_reader(name).read(reduced, {}))


if __name__ == "__main__":
    describe()
