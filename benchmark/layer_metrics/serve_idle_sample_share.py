"""Percent of the traced window in which the first chip runs nothing
while the batcher thread is inside ``dml.serve.sample``: the part of
``serve_device_idle_share`` that batched on-device sampling would give
back. A program that opens no span at all (PR 23's parent) reads 0, as
``serve_idle_unattributed_share`` then reads 100; one that opens spans
and not this one reads nothing. Layer: device. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import program_trace


def read(trace: dict, counters: dict) -> float | None:
    t = program_trace.this_run(trace)["trace"]
    names = program_trace.span_names(t)
    if names and program_trace.SPAN_SAMPLE not in names:
        return None
    return program_trace.idle_share_inside(t, program_trace.SPAN_SAMPLE)
