"""The one percentile the benchmark uses."""

from __future__ import annotations


def percentile(values: list[float], q: float) -> float:
    """Nearest rank, as ``servesvc/loadgen.py::_percentile`` has it: no
    interpolation, so a reported tail is a reading that was taken."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    return s[min(len(s) - 1, max(0, round(q * (len(s) - 1))))]
