"""Device time of a decode step under scope ``kda``, ms an execution: the
delta-rule mixers whole, every such layer: the projections, the three
convolutions' one new tap, the gate, the matrix state's update and
read-out, the head norm and the output gate. None for a program with no
such layer. Layer: model_step. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import kda_scopes


def read(trace: dict, counters: dict) -> float | None:
    return kda_scopes.step_ms(trace, "kda")
