"""Seconds from the start of the publish to the replica serving the
weights: ``save_checkpoint``, ``wait_for_run_config`` and the replica's
digest-verified restore up to ``model_step`` set. Layer:
checkpoint_follow. Moves ``setup_s``."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    return counters.get("weights_ready_s")
