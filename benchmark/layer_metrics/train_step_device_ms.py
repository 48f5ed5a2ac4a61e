"""Median device time of one execution of the train-step program (the
program with most device time in the trace), from the ``XLA Modules``
line. Layer: train_step. Moves ``train_tokens_per_s_per_chip``."""

from __future__ import annotations

from benchmark.lib.stats import percentile
from benchmark.lib.trace_reduce import main_module


def read(trace: dict, counters: dict) -> float | None:
    _, module = main_module(trace)
    return percentile(module["durations_ms"], 0.5)
