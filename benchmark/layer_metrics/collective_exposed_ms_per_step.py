"""The part of the collective time per train step during which no
other operation runs on that chip: what overlap could still hide.
Layer: aggregate. Moves ``train_tokens_per_s_per_chip``."""

from __future__ import annotations

from benchmark.lib.trace_reduce import main_module


def read(trace: dict, counters: dict) -> float | None:
    _, module = main_module(trace)
    return (1e3 * trace["collective_exposed_s"]
            / len(module["durations_ms"]))
