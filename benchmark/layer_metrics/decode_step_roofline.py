"""The decode step's share of its roofline, in percent. A decode step
is bound by bytes: it has to read every matrix weight once and the keys
and values of every live sequence (``flops.py::decode_bytes_per_step``,
for the contexts the load generator saw in flight in the middle of the
trace, 2 bytes a value). That over the chip's memory bandwidth is the
least time a step could take; the share is that over the step's median
device time. A whole program and not one kernel: the kernels inside it
have no names yet. Layer: model_step. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib.stats import percentile
from benchmark.lib.trace_reduce import main_module


def read(trace: dict, counters: dict) -> float | None:
    if counters.get("decode_bytes_per_step") is None:
        return None
    _, module = main_module(trace)
    least_ms = (1e3 * counters["decode_bytes_per_step"]
                / counters["peak_hbm_bytes_per_s"])
    return 100.0 * least_ms / percentile(module["durations_ms"], 0.5)
