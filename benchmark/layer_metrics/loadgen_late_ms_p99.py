"""How late the benchmark's own generator sent: 99th percentile of send
time less due time. Above a few milliseconds the serving numbers are the
generator's. Layer: benchmark_generator. Listed under
``itl_ms_p90`` for want of a metric it moves; it guards all of them."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("loadgen_late_ms_p99")
