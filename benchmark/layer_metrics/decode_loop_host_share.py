"""Percent of the batcher thread's time with a request in the replica
that it does NOT spend waiting for the decode step: ``100 * (1 - fetch /
(loop_wall - idle))``. From the replica's own always-on loop clock
(``loop_s`` and ``loop_wall_s`` of its heartbeats:
``obsv/timing.LoopClock``), between the first and the last heartbeat
inside the load's window, on the host's clock alone and over every
iteration of the window, prefills included. Where the heartbeats carry
no ``loop_s`` (a program before PR 40, which the driver also runs with
this reader; a recorded run without the file) the same quantity from the
traced part's spans: ``dml.serve.step.fetch`` against the thread's time
from its first span to its last, less ``dml.serve.idle``. A program that
opens no span reads 0; spans without the dispatch span read nothing.
What an operator reads from two heartbeats to tell a host-bound replica
from a device-bound one. Layer: decode_loop. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import host_gaps


def read(trace: dict, counters: dict) -> float | None:
    return host_gaps.loop_host_share(trace)
