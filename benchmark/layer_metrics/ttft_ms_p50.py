"""Median time from due to first token, client clock. For reading
beside the tail; decides nothing. Layer: service. Not bounded (see
``ttft_ms_p90.py``)."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("ttft_ms_p50")
