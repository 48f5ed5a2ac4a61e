"""Device time of a train step under scope ``mtp``, ms an execution,
forward + recomputed + backward: the next-next-token module whole (its
projection, its layer with that layer's attention, routing and stream
mixing, its norm, head and loss). Layer: model_step. Moves
``train_tokens_per_s_per_chip``."""

from __future__ import annotations

from benchmark.lib import block_scopes


def read(trace: dict, counters: dict) -> float | None:
    return block_scopes.ms(trace, "mtp")
