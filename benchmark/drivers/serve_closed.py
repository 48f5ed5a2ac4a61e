"""Traffic kind ``serve_closed``: a fixed number of clients, each with
one request in flight and the next sent when the last one ended (batch
generation, evaluation harnesses). The replica is saturated by
construction, so the cell is judged on tokens per second and on the gap
between tokens; time to first token is queue wait here and is not
reported."""

from __future__ import annotations

from benchmark.lib.serving import run_serving


def run(cell, rt) -> dict:
    return run_serving(cell, rt, mode="closed")
