"""Percent of the first chip's idle time in the traced window that no
``dml.*`` span covers: the instrumentation's own coverage. Near 0 the
span table explains the idle share; 100 says the program opens no span
(PR 23's parent), and the span metrics beside this one then read 0.
Layer: device. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import program_trace


def read(trace: dict, counters: dict) -> float | None:
    return program_trace.idle_share_unattributed(
        program_trace.this_run(trace)["trace"])
