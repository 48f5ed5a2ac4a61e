"""Device time of a decode step under scope ``latent_absorb``, ms an
execution: the query through ``W_uk`` and the weighted latents through
``W_uv``, every layer: what the absorbed form pays for never expanding a
cached token's keys and values. Layer: model_step. Moves
``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import decode_scopes


def read(trace: dict, counters: dict) -> float | None:
    return decode_scopes.ms(trace, "latent_absorb")
