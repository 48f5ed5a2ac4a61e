"""Model FLOP/s utilisation in percent: ``flops.py``'s forward-and-
backward operations per token (recomputation never counted) times the
tokens per second of this run's window, over chips times the chip's
bf16 peak. An end-to-end utilisation, not a kernel's roofline share.
Layer: train_step. Moves ``train_tokens_per_s_per_chip``."""

from __future__ import annotations


def read(trace: dict, counters: dict) -> float | None:
    if counters.get("model_flops_per_token") is None:
        return None
    return (100.0 * counters["model_flops_per_token"]
            * counters["tokens_per_s"]
            / (counters["chips"] * counters["peak_bf16_flops_per_s"]))
