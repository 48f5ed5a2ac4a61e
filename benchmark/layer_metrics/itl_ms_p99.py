"""99th percentile of the gap between consecutive tokens of a stream,
client clock. Recorded, not bounded: in the chat cell it sits on the
edge between one and two prefills run between two tokens and flips with
the seed (PERF.md, Findings, PR 22). Layer: service. Moves
``itl_ms_p90``."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("itl_ms_p99")
