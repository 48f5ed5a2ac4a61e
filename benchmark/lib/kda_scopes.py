"""What a delta-rule linear-attention layer adds to a serving run, read
from its trace: device time under the scopes ``ops/kda.py`` opens beyond
``program_trace.SCOPES`` (``kda`` around the mixer whole, and inside it
``kda_conv``, ``kda_gate``, ``kda_chunk`` in the prefill, ``kda_state`` in
the decode step: ``distributedmnist_tpu/obsv/spans.py``). The readers
``decode_kda_ms_per_step``, ``decode_kda_state_ms_per_step``,
``decode_kda_state_roofline`` and ``prefill_kda_chunk_ms_p50`` are built
on this file. A program that opens no such scope (every model without
such layers, any parent) gives None, never an error.

``program_trace.scope_path`` keeps the names of that module's ``SCOPES``,
a list no PR but a ``benchmark`` one edits: the trace is read with the
names added for one call, around ``lib/ssm_scopes.py``'s two functions
(which add a state-space layer's four: the additions nest; PERF.md §7
says what takes the detour out).

    python3 benchmark/lib/kda_scopes.py

prints the four for the newest traced run under ``runtime.WORK_ROOT``,
with both programs' scope tables (``moe`` and ``latent_absorb`` kept
too), from the same checkout."""

from __future__ import annotations

import sys
from pathlib import Path

if __package__ in (None, ""):            # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmark.lib import (program_trace, ssm_scopes,  # noqa: E402
                           trace_reduce as tr)
from benchmark.lib.block_scopes import _also  # noqa: E402
from benchmark.lib.stats import percentile  # noqa: E402

KDA_SCOPES = ("kda", "kda_conv", "kda_gate", "kda_chunk", "kda_state")
PREFILL = "jit_decode_prefill"
#: the four readers built on this file, by metric name
READERS = ("decode_kda_ms_per_step", "decode_kda_state_ms_per_step",
           "decode_kda_state_roofline", "prefill_kda_chunk_ms_p50")


def step_ms(reduced: dict, inside: str) -> float | None:
    """ms an execution of the decode step under scope ``inside``
    (``ssm_scopes.step_ms`` with this file's names kept too). None where
    the trace has no execution of the step or the step opens no such
    scope: the program is then not the one the metric is of."""
    with _also(KDA_SCOPES):
        return ssm_scopes.step_ms(reduced, inside)


def per_execution_ms(reduced: dict, program: str, inside: str) -> list[float]:
    """Device time under scope ``inside`` of each execution of
    ``program`` that lies wholly in the traced part, in ms
    (``ssm_scopes.per_execution_ms`` with this file's names kept too);
    empty where the program never ran there or opens no such scope."""
    with _also(KDA_SCOPES):
        return ssm_scopes.per_execution_ms(reduced, program, inside)


def state_bytes_per_step(reduced: dict) -> float | None:
    """What the architecture file of the run's cell counts for the
    matrix state's traffic in one step (``kda_state_bytes_per_step(config,
    live_slots)``: every live slot's state read once and written once,
    nothing else) at the median of the slots live at the traced
    iterations (``live`` on the dispatch span); None where the file has no
    such count or the trace no such span."""
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
    count = getattr(cell.arch, "kda_state_bytes_per_step", None)
    if count is None or not live:
        return None
    return float(count(cell.config, int(percentile(live, 0.5))))


def prefill_kda_chunk_ms_p50(reduced: dict) -> float | None:
    found = per_execution_ms(reduced, PREFILL, "kda_chunk")
    return percentile(found, 0.5) if found else None


def describe() -> None:
    import glob
    import os

    from benchmark.lib import cell as cell_lib
    from benchmark.lib.decode_scopes import DECODE_SCOPES
    from benchmark.lib.runtime import WORK_ROOT
    found = glob.glob(os.path.join(WORK_ROOT, "*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    reduced = tr.reduce(tr.load(max(found, key=os.path.getmtime)))
    trace = program_trace.this_run(reduced)["trace"]
    for program in (program_trace.DECODE_STEP, PREFILL):
        if not program_trace.executions(trace, program)[0]:
            continue
        with _also(KDA_SCOPES + DECODE_SCOPES):
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
        print(name, cell_lib.load_reader(name).read(
            reduced, {"peak_hbm_bytes_per_s": 819e9}))


if __name__ == "__main__":
    describe()
