"""Device time of a train step under scope ``attention`` and not under
``residual_mix``, ms an execution, forward + recomputed + backward: the
latent projections, the rotation, the flash kernels and the output
projection of every layer, the next-next-token module's included (so it
overlaps ``mtp_ms_per_step``). Layer: model_step. Moves
``train_tokens_per_s_per_chip``."""

from __future__ import annotations

from benchmark.lib import block_scopes


def read(trace: dict, counters: dict) -> float | None:
    return block_scopes.ms(trace, "attention", outside=("residual_mix",))
