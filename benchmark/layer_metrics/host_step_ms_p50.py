"""Median host time per train step, feed plus dispatch, from the
trainer's own ``collector.host_step_stats()``. It is host time per step,
not step time: the dispatch returns before the device finishes. Layer:
train_loop. Moves ``train_tokens_per_s_per_chip`` through the idle
share."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("host_step_ms_p50")
