"""Median depth of the device-prefetch queue at dequeue
(``collector.prefetch_depth_stats()``): pinned at 0 the producer is the
bottleneck, pinned at the configured depth the device is. Layer:
train_loop. Moves ``train_tokens_per_s_per_chip``."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("prefetch_depth_p50")
