"""Percent of the device's busy time spent in Mosaic custom calls in a
serving cell: the flash kernel of the prefills (the decode step's dense
arm has none). Layer: attention_kernels. Moves ``itl_ms_p90``."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
