"""Device time of a train step under scope ``residual_mix``, ms an
execution, forward + recomputed + backward: the products with ``phi``,
the Sinkhorn iteration, and every read and write of the residual
streams, both sublayers of every layer. Layer: model_step. Moves
``train_tokens_per_s_per_chip``."""

from __future__ import annotations

from benchmark.lib import block_scopes


def read(trace: dict, counters: dict) -> float | None:
    return block_scopes.ms(trace, "residual_mix")
