"""Device time of the Mosaic kernel ``paged_decode`` inside program
``jit_decode_step``, per whole execution in the traced part, every
fingerprint of the name (one call a layer:
``ops/pallas_paged_attention.py``). 0.0 where the step ran no call of
that name: the ``dense`` arm, a CPU, a program before the kernel, one
that opens no span. So a 0 beside ``attention_arm: paged`` in the
journal's ``decode_start`` is a lost name, not a free kernel. An
instrumented program whose step cannot be found reads nothing. Layer:
attention_kernels. Moves ``itl_ms_p90``."""

from __future__ import annotations

from benchmark.lib import host_gaps


def read(trace: dict, counters: dict) -> float | None:
    return host_gaps.of_the_step(
        trace, lambda table: table["by_kernel"].get("paged_decode", 0.0))
