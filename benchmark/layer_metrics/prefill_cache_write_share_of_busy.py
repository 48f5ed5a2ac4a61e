"""Percent of the device's busy time spent in program
``jit_write_prompt_kv``: the scatter of a prompt's K/V into the paged
cache, which copies both caches whole. A share, so a traced part in
which no request arrived reads 0 and not nothing (48 uniform arrivals
leave a 3 s trace empty once in 40 runs); so does a program whose
scatter has another name (PR 23's parent: ``jit__unknown``). Layer:
prefill. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib.program_trace import CACHE_WRITE, program_name


def read(trace: dict, counters: dict) -> float | None:
    ms = sum(sum(m["durations_ms"]) for name, m in trace["modules"].items()
             if program_name(name) == CACHE_WRITE)
    return 100.0 * ms / (trace["busy_s"] * 1e3)
