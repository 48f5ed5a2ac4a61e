"""Device time of a decode step under scope ``moe``, ms an execution:
router, sort, the grouped product over the held experts' tiles, combine
and the shared expert, of every routed layer. Layer: model_step. Moves
``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import decode_scopes


def read(trace: dict, counters: dict) -> float | None:
    return decode_scopes.ms(trace, "moe")
