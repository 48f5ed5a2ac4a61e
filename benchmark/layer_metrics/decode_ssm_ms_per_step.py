"""Device time of a decode step under scope ``ssm``, ms an execution: the
state-space mixers whole, every such layer: the in and out projections,
the convolution's one new tap, the step, B and C, and the state's
update. None for a program with no such layer. Layer: model_step. Moves
``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import ssm_scopes


def read(trace: dict, counters: dict) -> float | None:
    return ssm_scopes.step_ms(trace, "ssm")
