"""Percent of a step's (token, expert) pairs that land on an expert this
chip holds: the trainer's step records' ``expert_counts`` over tokens a
step times experts a token, a routed layer; the median over the run's
steps. Even routing gives held / routed experts (8 of 64: 12.5%). Layer:
model_step. Moves ``train_tokens_per_s_per_chip`` (the grouped
product's work follows it)."""

from __future__ import annotations

from benchmark.lib import block_scopes
from benchmark.lib.stats import percentile


def read(trace: dict, counters: dict) -> float | None:
    records = block_scopes.step_records(trace)
    if not records or not counters.get("tokens_per_step"):
        return None
    pairs = counters["tokens_per_step"] * block_scopes.experts_per_token(trace)
    return percentile(
        [100.0 * sum(map(sum, r["expert_counts"]))
         / (len(r["expert_counts"]) * pairs) for r in records], 0.5)
