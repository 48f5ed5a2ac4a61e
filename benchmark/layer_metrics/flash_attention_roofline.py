"""The flash-attention kernels' share of their roofline in a train
step, in percent: the least time the chip could take for attention's
model operations (``flops.py``: causal QK^T and PV, forward plus
backward, nothing recomputed; at these shapes the kernel is bound by
operations, not bytes) over the time the step spends in Mosaic calls,
which in a train step are the flash kernels and nothing else. Work the
kernels repeat (the forward run again under ``model.remat``, the scores
recomputed inside the backward kernel) lowers the share, as it should.
Layer: attention_kernels. Moves ``train_tokens_per_s_per_chip``."""

from __future__ import annotations

from benchmark.lib.trace_reduce import TraceError, main_module


def read(trace: dict, counters: dict) -> float | None:
    if counters.get("attention_flops_per_step_per_chip") is None:
        return None
    if trace["pallas_s"] <= 0:
        raise TraceError("no Mosaic call in the traced train steps")
    _, module = main_module(trace)
    least_s = (counters["attention_flops_per_step_per_chip"]
               / counters["peak_bf16_flops_per_s"])
    return 100.0 * least_s / (trace["pallas_s"] / len(module["durations_ms"]))
