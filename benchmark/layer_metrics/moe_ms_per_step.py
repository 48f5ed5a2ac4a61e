"""Device time of a train step under scope ``moe``, ms an execution,
forward + recomputed + backward: router, sort, the grouped product over
the held experts' tiles, combine and the shared expert, of every routed
layer (the next-next-token module's too). Layer: model_step. Moves
``train_tokens_per_s_per_chip``."""

from __future__ import annotations

from benchmark.lib import block_scopes


def read(trace: dict, counters: dict) -> float | None:
    return block_scopes.ms(trace, "moe")
