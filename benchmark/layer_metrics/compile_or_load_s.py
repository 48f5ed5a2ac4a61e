"""Seconds of set-up inside jax's compile-or-load-from-cache path
(``backend_compile_duration`` summed from process start to the window):
a compile in a cold checkout, a read of the persistent cache in a warm
one. Layer: entry_bring_up. Moves ``setup_s``."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("setup_compile_s")
