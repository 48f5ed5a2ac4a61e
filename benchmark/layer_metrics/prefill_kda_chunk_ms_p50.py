"""Median device time under scope ``kda_chunk`` of the executions of
``jit_decode_prefill`` in the traced part, ms: the delta rule over a
prompt in its chunk form, every such layer: a chunk's decays between
every pair of its tokens, its triangular system and the state carried
from chunk to chunk. None where no prefill ran in the traced part or the
program has no such layer. Layer: prefill. Moves ``itl_ms_p90`` (a
prefill runs inline between two decode steps: its duration is the stall
every live stream sees)."""

from __future__ import annotations

from benchmark.lib import kda_scopes


def read(trace: dict, counters: dict) -> float | None:
    return kda_scopes.prefill_kda_chunk_ms_p50(trace)
