"""Median device time of one decode-step program. Layer: model_step.
Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib.stats import percentile
from benchmark.lib.trace_reduce import main_module


def read(trace: dict, counters: dict) -> float | None:
    _, module = main_module(trace)
    return percentile(module["durations_ms"], 0.5)
