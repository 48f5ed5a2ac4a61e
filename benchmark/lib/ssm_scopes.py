"""What a state-space layer adds to a serving run, read from its trace:
device time under the scopes ``ops/ssm.py`` opens beyond
``program_trace.SCOPES`` (``ssm`` around the mixer whole, and inside it
``ssm_conv``, ``ssm_scan`` in the prefill, ``state_update`` in the decode
step:
``distributedmnist_tpu/obsv/spans.py``). The readers
``decode_ssm_ms_per_step``, ``decode_state_update_ms_per_step``,
``decode_state_update_roofline`` and ``prefill_scan_ms_p50`` are built on
this file. A program that opens no such scope (every model without such
layers, any parent) gives None, never an error.

``program_trace.scope_path`` keeps the names of that module's ``SCOPES``,
a list no PR but a ``benchmark`` one edits: the trace is read with the
four names added for one call, as ``lib/decode_scopes.py`` does (PERF.md
§7 says what takes the detour out).

    python3 benchmark/lib/ssm_scopes.py

prints the four for the newest traced run under ``runtime.WORK_ROOT``,
with the decode step's scope table, from the same checkout."""

from __future__ import annotations

import sys
from bisect import bisect_left
from pathlib import Path

if __package__ in (None, ""):            # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmark.lib import program_trace, trace_reduce as tr  # noqa: E402
from benchmark.lib.block_scopes import _also  # noqa: E402
from benchmark.lib.stats import percentile  # noqa: E402

SSM_SCOPES = ("ssm", "ssm_conv", "ssm_scan", "state_update")
PREFILL = "jit_decode_prefill"
#: the four readers built on this file, by metric name
READERS = ("decode_ssm_ms_per_step", "decode_state_update_ms_per_step",
           "decode_state_update_roofline", "prefill_scan_ms_p50")


def step_ms(reduced: dict, inside: str) -> float | None:
    """ms an execution of the decode step under scope ``inside``. None
    where the trace has no execution of the step or the step opens no
    such scope: the program is then not the one the metric is of."""
    trace = program_trace.this_run(reduced)["trace"]
    if not program_trace.executions(trace, program_trace.DECODE_STEP)[0]:
        return None
    with _also(SSM_SCOPES):
        found = program_trace.scope_table(trace, program_trace.DECODE_STEP)
    if not any(inside in path.split("/") for path, _ in found["by_scope"]):
        return None
    return program_trace.scope_ms(found, inside)


def per_execution_ms(reduced: dict, program: str, inside: str) -> list[float]:
    """Device time under scope ``inside`` of each execution of
    ``program`` that lies wholly in the traced part (self times, so a
    loop counts once), in ms; empty where the program never ran there or
    opens no such scope."""
    trace = program_trace.this_run(reduced)["trace"]
    runs, fingerprints = program_trace.executions(trace, program)
    if not runs:
        return []
    ops = [e for e in tr._line(tr.device_planes(trace)[0], tr.OPS_LINE)
           if e[4].get("program_id") in fingerprints]
    with _also(SSM_SCOPES):
        mine = sorted(
            (ev[1], ns) for ev, ns in tr.self_times(ops)
            if inside in program_trace.scope_path(ev[4]["op_name"]))
    if not mine:
        return []
    starts = [start for start, _ in mine]
    return [sum(ns for _, ns in mine[bisect_left(starts, run[1]):
                                     bisect_left(starts, run[1] + run[2])])
            / 1e6 for run in runs]


def state_bytes_per_step(reduced: dict) -> float | None:
    """What the architecture file of the run's cell counts for the
    recurrent state's traffic in one step (``state_bytes_per_step(config,
    live_slots, tail_bytes=0)``: the state read and written, WITHOUT the
    convolution's tail, which the convolution reads under a scope of its
    own) at the median of the slots live at the traced iterations
    (``live`` on the dispatch span); None where the file has no such
    count or the trace no such span."""
    from benchmark.lib import cell as cell_lib
    run = program_trace.this_run(reduced)
    live = [e[4]["live"]
            for events in program_trace.spans_by_thread(
                run["trace"]).values()
            for e in events if e[0] == program_trace.SPAN_DISPATCH]
    try:
        cell = cell_lib.load_cell(run["workdir"].name)
    except cell_lib.BenchmarkError:
        return None
    count = getattr(cell.arch, "state_bytes_per_step", None)
    if count is None or not live:
        return None
    return float(count(cell.config, int(percentile(live, 0.5)),
                       tail_bytes=0))


def prefill_scan_ms_p50(reduced: dict) -> float | None:
    found = per_execution_ms(reduced, PREFILL, "ssm_scan")
    return percentile(found, 0.5) if found else None


def describe() -> None:
    import glob
    import os

    from benchmark.lib import cell as cell_lib
    from benchmark.lib.runtime import WORK_ROOT
    found = glob.glob(os.path.join(WORK_ROOT, "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    reduced = tr.reduce(tr.load(max(found, key=os.path.getmtime)))
    trace = program_trace.this_run(reduced)["trace"]
    for program in (program_trace.DECODE_STEP, PREFILL):
        if not program_trace.executions(trace, program)[0]:
            continue
        with _also(SSM_SCOPES):
            scopes = program_trace.scope_table(trace, program)
        rows: dict[str, float] = {}
        for (path, _), value in scopes["by_scope"].items():
            rows[path] = rows.get(path, 0.0) + value
        print(f"{scopes['executions']} executions of {program}, "
              f"{scopes['total_ms']:.2f} ms each")
        for path, value in sorted(rows.items(), key=lambda kv: -kv[1]):
            print(f"{value:9.3f}  {path}")
        print("kernels", {k: round(v, 2)
                          for k, v in scopes["by_kernel"].items()})
    for name in READERS:
        print(name, cell_lib.load_reader(name).read(reduced, {}))


if __name__ == "__main__":
    describe()
