"""Percent of the traced window in which no operation ran on the
device, averaged over the chips. Layer: device. Moves
``train_tokens_per_s_per_chip``."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
