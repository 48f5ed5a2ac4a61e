"""Median, over the replica's heartbeats in the window, of the last
decode step's (token, expert) pairs on held experts over the experts
that took any: the rows a touched expert's weights are read for. A
deployment of 16 chips at 4 slots each sends this chip's 16 experts 2 a
step. Layer: model_step. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import decode_scopes
from benchmark.lib.stats import percentile


def read(trace: dict, counters: dict) -> float | None:
    beats = [b for b in decode_scopes.heartbeats(trace)
             if b["experts_touched"]]
    if not beats:
        return None
    return float(percentile(
        [b["expert_pairs_held"] / b["experts_touched"] for b in beats], 0.5))
