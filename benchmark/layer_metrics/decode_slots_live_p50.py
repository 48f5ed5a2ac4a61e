"""Median, over the decode iterations of the traced part, of the slots
that held a live sequence: ``live`` on ``dml.serve.step.dispatch``, a
count taken where it is true. A program without that span (PR 23's
parent) has its occupancy read from the journal's ``prefill`` and
``decode_finish`` records over the whole window. Layer: decode_loop.
Moves ``itl_ms_p90``: the step's cost does not depend on it today (a
fixed slot shape), so tokens per second do."""

from __future__ import annotations

from benchmark.lib import program_trace
from benchmark.lib.stats import percentile


def read(trace: dict, counters: dict) -> float | None:
    run = program_trace.this_run(trace)
    live = [e[4]["live"]
            for events in program_trace.spans_by_thread(
                run["trace"]).values()
            for e in events if e[0] == program_trace.SPAN_DISPATCH]
    if live:
        return float(percentile(live, 0.5))
    if program_trace.instrumented(run["trace"]):
        return None        # spans, and not this one: not a zero
    return program_trace.slots_live_from_journal(run)
