"""Device time of a decode step under scope ``state_update``, ms an
execution: every state-space layer's recurrent state of every slot read,
advanced by one token and written where it lay, with the read-out. None
for a program with no such layer. Layer: slot_state. Moves
``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import ssm_scopes


def read(trace: dict, counters: dict) -> float | None:
    return ssm_scopes.step_ms(trace, "state_update")
