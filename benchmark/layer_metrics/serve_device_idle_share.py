"""Percent of the traced window in which no operation ran on the
device. Layer: device. Moves ``itl_ms_p90``: the gap between
tokens is the step's device time plus what the host adds between steps."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
