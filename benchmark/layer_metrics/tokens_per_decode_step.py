"""Tokens the replica streamed while the trace ran over decode-step
executions in the trace: the batch the step really carries (prefill's
first tokens included). Layer: decode_loop. Moves
``serve_tokens_per_s``."""

from __future__ import annotations

from benchmark.lib.trace_reduce import main_module


def read(trace: dict, counters: dict) -> float | None:
    if counters.get("tokens_in_trace") is None:
        return None
    _, module = main_module(trace)
    return counters["tokens_in_trace"] / len(module["durations_ms"])
