"""Device time under scope ``cache_write`` inside program
``jit_decode_step``, per whole execution in the traced part, every
fingerprint of the name: the scatter of the new token's keys and values
into the paged cache (in the plain block 48 ``while`` loops of one
iteration a slot). 0 where the step holds no such scope, and for a
program that opens no span (PR 23's parent); an instrumented one whose
step cannot be found reads nothing. Layer: model_step. Moves
``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import host_gaps, program_trace


def read(trace: dict, counters: dict) -> float | None:
    return host_gaps.of_the_step(
        trace, lambda table: program_trace.scope_ms(table, "cache_write"))
