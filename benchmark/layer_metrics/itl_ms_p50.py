"""Median gap between consecutive tokens of a stream, client clock.
For reading beside the tail; decides nothing. Layer: service. Moves
``itl_ms_p90``."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("itl_ms_p50")
