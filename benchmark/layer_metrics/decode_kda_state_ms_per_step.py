"""Device time of a decode step under scope ``kda_state``, ms an
execution: every delta-rule layer's matrix state of every slot decayed,
read against the token's key and query, updated by the delta rule and
written where it lay. None for a program with no such layer. Layer:
slot_state. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import kda_scopes


def read(trace: dict, counters: dict) -> float | None:
    return kda_scopes.step_ms(trace, "kda_state")
