"""Median time the first chip runs nothing between two decode steps:
from the end of one execution of the decode-step program to the start
of the next, over the plain iterations of the traced part (those with no
execution of ``jit_decode_prefill`` or ``jit_write_prompt_kv`` between
the two steps: a prefill's gap is the prefill's, ``prefill_ms_p50``).
Read from the reduced trace's ``modules`` alone (every module of
``main_module``'s name, one a table width, in start order:
``host_gaps.device_gaps_ms``), on the device's clock, so it needs no file
of the run and no span. It is ``decode_iter_ms_p50`` less
``decode_step_device_ms`` where those read the same rung, and
``decode_gap_beneath_ms`` + ``_emit_ms`` + ``_inputs_ms`` + ``_rest_ms``
say what the host did with it. Layer: decode_loop. Moves
``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import host_gaps
from benchmark.lib.stats import percentile


def read(trace: dict, counters: dict) -> float | None:
    gaps = host_gaps.device_gaps_ms(trace)
    return percentile(gaps, 0.5) if gaps else None
