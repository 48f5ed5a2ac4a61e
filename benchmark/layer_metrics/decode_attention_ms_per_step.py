"""Device time under scope ``attention`` inside program
``jit_decode_step``, per whole execution in the traced part: the
projections, the write into the cache, the gather of the dense view and
the scores, with the operations the compiler made between them (the
float32 views; ``program_trace._scoped``). The copies of the whole cache
before the first layer are not under it. A program without the named
step and without a span (PR 23's parent) reads 0; an instrumented one
whose step cannot be found reads nothing. Layer: model_step. Moves
``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import program_trace


def read(trace: dict, counters: dict) -> float | None:
    t = program_trace.this_run(trace)["trace"]
    if not program_trace.executions(t, program_trace.DECODE_STEP)[0]:
        return None if program_trace.instrumented(t) else 0.0
    return program_trace.scope_ms(
        program_trace.scope_table(t, program_trace.DECODE_STEP), "attention")
