"""The part of the gap between two decode steps that the batcher spends
building the next step's inputs: the time from the end of the fetch to
the next dispatch that ``dml.serve.step.inputs`` covers (three numpy
vectors, their uploads, the block table's where it changed).

One of the four parts of the gap between two decode steps
(``benchmark/lib/host_gaps.py``: spans joined to the step's executions
by order, every number a difference within one clock); mean over the
plain iterations of the traced part, and the four add up to the mean
plain gap. A program that opens no span at all (no ``dml.*`` label among
the reduced trace's idle gaps: PR 23's parent) reads 0, nothing being
attributed; one that opens spans and lost ``dml.serve.step.dispatch``, or
whose step cannot be found, reads nothing, which fails the run. Layer:
decode_loop. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import host_gaps


def read(trace: dict, counters: dict) -> float | None:
    return host_gaps.part_ms(trace, "inputs")
