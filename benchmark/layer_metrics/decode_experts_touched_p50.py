"""Median, over the replica's heartbeats in the window, of how many
(routed layer, held expert) took at least one of the last decode step's
tokens: ``experts_touched``. The step reads each such expert's weights
once, so its bytes follow this count (``decode_bytes_per_step`` of the
architecture file has the expectation under uniform routing). Layer:
model_step. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import decode_scopes
from benchmark.lib.stats import percentile


def read(trace: dict, counters: dict) -> float | None:
    beats = decode_scopes.heartbeats(trace)
    if not beats:
        return None
    return float(percentile([b["experts_touched"] for b in beats], 0.5))
