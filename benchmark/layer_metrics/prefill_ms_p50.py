"""Median duration of a prefill inside the window, from the replica's
journal (``prefill`` records; the field is named ``ttft_ms`` there and is
the prefill's own duration). Layer: prefill. Moves ``itl_ms_p90``
(a prefill runs inline between two decode steps, so its duration is the
stall every live stream sees) and the time to first token."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("prefill_ms_p50")
