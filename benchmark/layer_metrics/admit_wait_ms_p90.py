"""90th percentile over the window's prefills of the time a request waited
from its admission to the start of its prefill: the journal's
``prefill.queue_ms`` (``DecodeReplica._prefill``), about 48 samples in
40 s. In the chat cell it is the wait for the running decode step, most
of the distance between ``ttft_ms_p50`` and ``prefill_ms_p50``. A
journal without the field (PR 23's parent) gives the same wait from its
``admit`` and ``prefill`` records. Layer: decode_loop. Moves
``itl_ms_p90`` (what shortens the iteration shortens this wait)."""

from __future__ import annotations

from benchmark.lib import program_trace
from benchmark.lib.stats import percentile


def read(trace: dict, counters: dict) -> float | None:
    waits = program_trace.admit_waits_ms(program_trace.this_run(trace))
    return percentile(waits, 0.9) if waits else None
