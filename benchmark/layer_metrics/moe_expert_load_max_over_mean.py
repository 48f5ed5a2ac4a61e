"""The busiest held expert's pairs over the mean of the held experts',
a routed layer, the mean over a step's routed layers and the median over
the run's steps, from the trainer's step records' ``expert_counts``: 1 is
even. An expert's pairs are padded to whole tiles of the grouped
product, and the busiest decides how long a deployment's other chips
wait. Layer: model_step. Moves ``train_tokens_per_s_per_chip``."""

from __future__ import annotations

from benchmark.lib import block_scopes
from benchmark.lib.stats import percentile


def read(trace: dict, counters: dict) -> float | None:
    records = block_scopes.step_records(trace)
    if not records:
        return None
    def spread(row):
        return max(row) * len(row) / max(sum(row), 1)
    return percentile(
        [sum(map(spread, r["expert_counts"])) / len(r["expert_counts"])
         for r in records], 0.5)
