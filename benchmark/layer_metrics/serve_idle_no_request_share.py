"""Percent of the first chip's traced window in which it runs nothing
while the batcher thread is parked in ``dml.serve.idle`` (no slot live,
nobody waiting), the device plane moved later by ``offset_lo``
(``trace_clock_offset_ms``) before the two are intersected. The part of
``serve_device_idle_share`` that no change to the loop gives back: 0 in
a saturated cell, large in one under its knee. A program that opens no
span, or never parks in the traced part, reads 0; spans without the
dispatch span or the step read nothing. Layer: device. Moves
``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import host_gaps


def read(trace: dict, counters: dict) -> float | None:
    return host_gaps.no_request_share(trace)
