"""The state update's share of its roofline, in percent. It is bound by
bytes: every live sequence's recurrent state, every state-space layer,
read and written once a step (``state_bytes_per_step(config, live_slots,
tail_bytes=0)`` of the cell's architecture file, for the median of the
slots that were live at the traced iterations; idle slots, which the
program's fixed shape steps too, do not count, nor does the convolution's
tail, 9% more, which the convolution reads under scope ``ssm_conv``: what
is counted is moved under the scope that is timed). That over the chip's memory bandwidth is the least time the
update could take; the share is that over the device time under scope
``state_update`` an execution. None for a program with no such layer, or
an architecture without the count. Layer: slot_state. Moves
``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import ssm_scopes


def read(trace: dict, counters: dict) -> float | None:
    took_ms = ssm_scopes.step_ms(trace, "state_update")
    least = ssm_scopes.state_bytes_per_step(trace)
    if not took_ms or least is None or not counters.get(
            "peak_hbm_bytes_per_s"):
        return None
    return 100.0 * (1e3 * least / counters["peak_hbm_bytes_per_s"]) / took_ms
