"""Median time from the start of one decode-step program on the device
to the start of the next (the program with most device time in the
trace). Its difference to ``decode_step_device_ms`` is the host's share
of a token. Layer: decode_loop. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib.stats import percentile
from benchmark.lib.trace_reduce import TraceError, main_module


def read(trace: dict, counters: dict) -> float | None:
    _, module = main_module(trace)
    starts = sorted(module["starts_ms"])
    if len(starts) < 3:
        raise TraceError("fewer than three decode steps in the trace")
    return percentile([b - a for a, b in zip(starts, starts[1:])], 0.5)
