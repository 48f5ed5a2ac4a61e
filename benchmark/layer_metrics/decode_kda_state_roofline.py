"""The matrix state's update as a share of its roofline, in percent. It
is bound by bytes: every live sequence's state, every delta-rule layer,
read once and written once a step and nothing else
(``kda_state_bytes_per_step(config, live_slots)`` of the cell's
architecture file, for the median of the slots that were live at the
traced iterations; idle slots, which the program's fixed shape steps too,
do not count, nor does a second read of the state, which an
implementation that makes one pays for in time: the count is the same
whatever implements the update). That over the chip's memory bandwidth is
the least time the update could take; the share is that over the device
time under scope ``kda_state`` an execution. None for a program with no
such layer, or an architecture without the count. Layer: slot_state.
Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import kda_scopes


def read(trace: dict, counters: dict) -> float | None:
    took_ms = kda_scopes.step_ms(trace, "kda_state")
    least = kda_scopes.state_bytes_per_step(trace)
    if not took_ms or least is None or not counters.get(
            "peak_hbm_bytes_per_s"):
        return None
    return 100.0 * (1e3 * least / counters["peak_hbm_bytes_per_s"]) / took_ms
