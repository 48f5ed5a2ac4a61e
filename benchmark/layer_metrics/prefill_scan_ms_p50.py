"""Median device time under scope ``ssm_scan`` of the executions of
``jit_decode_prefill`` in the traced part, ms: the recurrence over a
prompt, every state-space layer, chunk by chunk and token by token
inside a chunk. None where no prefill ran in the traced part or the
program has no such layer. Layer: prefill. Moves ``itl_ms_p90`` (a
prefill runs inline between two decode steps: its duration is the stall
every live stream sees)."""

from __future__ import annotations

from benchmark.lib import ssm_scopes


def read(trace: dict, counters: dict) -> float | None:
    return ssm_scopes.prefill_scan_ms_p50(trace)
