"""Self time of the ``dml.serve.sample`` spans in the traced part over its
decode iterations (the count of ``dml.serve.step.dispatch``): a row's
upload, an eager argmax and a blocking fetch for every live slot, and the
same once per prefill (where it also waits for the prefill on the
device). Part of what ``decode_iter_ms_p50`` holds beyond
``decode_step_device_ms``. Layer: decode_loop. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import program_trace


def read(trace: dict, counters: dict) -> float | None:
    return program_trace.span_ms_per_iteration(
        program_trace.this_run(trace)["trace"], program_trace.SPAN_SAMPLE)
