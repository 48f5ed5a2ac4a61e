"""90th percentile of the time from when a request was due to its first
token, client clock; a failed or refused request counts as missing. Not
bounded: at some fifty requests a window it spreads by more than a bound
may be wide (PERF.md, Findings, PR 22). Layer: service."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("ttft_ms_p90")
