"""How far this trace's device plane lies EARLY against its host plane,
at least: the largest, over the decode iterations of the traced part, of
``dml.serve.step.dispatch``'s start less the start of the execution it
launched, floored at 0 (``offset_lo`` of ``benchmark/lib/host_gaps.py``;
its printer has ``offset_hi`` too). A program cannot start before the
call that launches it, so every number that intersects the two planes as
recorded (``serve_idle_sample_share``, ``serve_idle_unattributed_share``,
``breakdown.idle_gaps``) is off by this much of each gap; the
``decode_gap_*`` metrics are differences within one clock and are not. A
program that opens no span reads 0; spans without the dispatch span or
the step read nothing. Layer: device. Moves ``itl_ms_p90``: it is the
error bar of the readers that account for it."""

from __future__ import annotations

from benchmark.lib import host_gaps


def read(trace: dict, counters: dict) -> float | None:
    return host_gaps.clock_offset_ms(trace)
