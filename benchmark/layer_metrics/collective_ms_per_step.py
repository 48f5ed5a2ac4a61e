"""Device time in collective operations per train step, averaged over
the chips. Layer: aggregate (the masked ``psum``). Moves
``train_tokens_per_s_per_chip``."""

from __future__ import annotations

from benchmark.lib.trace_reduce import main_module


def read(trace: dict, counters: dict) -> float | None:
    _, module = main_module(trace)
    return 1e3 * trace["collective_s"] / len(module["durations_ms"])
