"""Percent of the device's busy time spent in Mosaic custom calls, all
Pallas kernels together (in a train step: flash attention forward and
backward). The split by kernel needs ``name=`` on each ``pallas_call``,
which is the tracing issue's. Layer: attention_kernels. Moves
``train_tokens_per_s_per_chip``."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
