"""Traffic kind ``serve_open``: independent users. Requests are sent on
a schedule fixed by the traffic file and the seed, whether or not
earlier ones have finished, at a fixed rate below the knee found once by
a sweep on the chip; every latency runs from when the request was due."""

from __future__ import annotations

from benchmark.lib.serving import run_serving


def run(cell, rt) -> dict:
    return run_serving(cell, rt, mode="open")
