"""The delta-rule state kernel alone on a chip, against the XLA step, at
a given state's shape (no part of graftcheck: it needs jax and a TPU;
no benchmark cell runs it).

    python -m distributedmnist_tpu.analysis.kda_state_bench [SLOTS,H,D] [HB,...]

``LAYERS`` layers' calls of ``ops/kda.py::state_step`` in one program,
each on a state of its own (``[SLOTS, H, D, D]`` float32, donated and
handed back, as ``decode_step_with_state`` holds them; default 128,32,128:
the cell ``ling-3.0-flash.serve_reason_long_closed``), one slot idle, for
every head block in the second list (default the module's): ms a call
(mean over ``RUNS`` runs of the program), GB/s on the counted bytes (the
live slots' state read once and written once: what
``benchmark/archs/bailing_hybrid.py::kda_state_bytes_per_step`` counts),
the seconds its trace-and-lower and its compile took, the largest
difference of outputs and state from ``ops/kda.py::step`` jitted and
donated the same way (relative to the oracle's largest value), and whether
the idle slot's state came back bit for bit. One JSON line a reading,
then all of them in one line. PERF.md (PR 47) has the readings that fixed
``HEAD_BLOCK``."""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kda

LAYERS, RUNS, IDLE = 11, 10, 3


def xla_arm(q, k, v, g, beta, s, live):
    """What ``mixer_step`` does off the kernel."""
    o, new = kda.step(q, k, v, g, beta, s)
    return o, jnp.where(live[:, None, None, None], new, s)


def timed(fn, vectors, states, live):
    """``fn`` over every layer's state in one program, compiled, run
    ``RUNS`` + 1 times on states it is handed back: (ms a call,
    trace-and-lower s, compile s, the first run's outputs and states)."""
    def layers(vectors, states, live):
        outs = [fn(*vectors, s, live) for s in states]
        return [o for o, _ in outs], [s for _, s in outs]

    t0 = time.perf_counter()
    lowered = jax.jit(layers, donate_argnums=1).lower(vectors, states, live)
    t1 = time.perf_counter()
    run = lowered.compile()
    t2 = time.perf_counter()
    outs, states = run(vectors, states, live)
    first = (np.asarray(outs[0]), np.asarray(states[0]))
    t = time.perf_counter()
    for _ in range(RUNS):
        outs, states = run(vectors, states, live)
    jax.block_until_ready(states)
    ms = (time.perf_counter() - t) / (RUNS * len(states)) * 1e3
    return ms, t1 - t0, t2 - t1, first


def bench(slots, heads, d, head_blocks, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    vectors = (kda._l2(jax.random.normal(ks[0], (slots, heads, d))),
               kda._l2(jax.random.normal(ks[1], (slots, heads, d))),
               jax.random.normal(ks[2], (slots, heads, d)),
               -5 * jax.nn.sigmoid(
                   jax.random.normal(ks[3], (slots, heads, d)) * 2 - 3),
               jax.nn.sigmoid(jax.random.normal(ks[4], (slots, heads))))
    live = jnp.arange(slots) != IDLE
    fresh = lambda: [jax.random.normal(  # noqa: E731
        jax.random.fold_in(ks[5], i), (slots, heads, d, d)) * 0.3
        for i in range(LAYERS)]
    given = np.asarray(fresh()[0])
    counted = 2 * int(live.sum()) * heads * d * d * 4

    def reading(name, fn):
        ms, lower_s, compile_s, first = timed(fn, vectors, fresh(), live)
        return first, {"arm": name, "ms": round(ms, 4),
                       "GB_s": round(counted / ms / 1e6, 1),
                       "trace_and_lower_s": round(lower_s, 2),
                       "compile_s": round(compile_s, 2)}

    (want_o, want_s), xla = reading("xla", xla_arm)
    said = {"state": [slots, heads, d, d], "layers": LAYERS,
            "counted_bytes": counted,
            "ms_at_819_GB_s": round(counted / 819e6, 4), "readings": [xla]}
    print(json.dumps(xla), flush=True)
    alive = np.asarray(live)
    for hb in head_blocks:
        (o, s), row = reading("kernel", functools.partial(
            kda.state_step, head_block=hb))
        row.update(
            head_block=hb,
            o_err=float(np.abs(o - want_o)[alive].max()
                        / np.abs(want_o).max()),
            s_err=float(np.abs(s - want_s).max() / np.abs(want_s).max()),
            idle_kept=bool((s[IDLE] == given[IDLE]).all()))
        said["readings"].append(row)
        print(json.dumps(row), flush=True)
    return said


def main(argv):
    ints = lambda text: [int(n) for n in text.split(",")]  # noqa: E731
    slots, heads, d = ints(argv[0]) if argv else (128, 32, 128)
    head_blocks = ints(argv[1]) if len(argv) > 1 else [kda.HEAD_BLOCK]
    said = bench(slots, heads, d, head_blocks)
    print(json.dumps({"device": jax.devices()[0].device_kind, **said}))
    sound = all(r["idle_kept"] and max(r["o_err"], r["s_err"]) < 1e-5
                for r in said["readings"][1:])
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
