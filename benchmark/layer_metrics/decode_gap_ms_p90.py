"""90th percentile of the time the first chip runs nothing between two
decode steps, over the plain iterations of the traced part
(``decode_gap_ms_p50`` has the rule and the source): the host's tail,
which the bounded ``itl_ms_p90`` sits on once the step is short. Layer:
decode_loop. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import host_gaps
from benchmark.lib.stats import percentile


def read(trace: dict, counters: dict) -> float | None:
    gaps = host_gaps.device_gaps_ms(trace)
    return percentile(gaps, 0.9) if gaps else None
