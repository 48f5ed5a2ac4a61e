"""The latent paged kernel alone on a chip, against the gather arm, at
the two latent serving cells' shapes (no part of graftcheck: it needs
jax and a TPU).

    python -m distributedmnist_tpu.analysis.latent_bench [ROWS,...] [UNROLL,...]

A layer's call of ``ops/pallas_paged_attention.py::
paged_latent_attention_write`` over random pages (contexts 1.5-3.1k at
64 slots x 128 heads and pages of 16; 2.2-3.0k at 128 x 32 and pages of
128; one idle slot), for every ``TARGET_ROWS`` in the first list
(default the module's) and every ``PAGE_UNROLL`` in the second: ms a
call (mean of 20), the seconds its trace-and-lower and its compile
took, the largest difference from the gather arm over live slots,
whether the idle slot's output is zeros and whether both arrays hold
what the gather arm's scatters leave outside the null block. One JSON
line a reading, then all of them in one line. PERF.md (PR 46) has the
readings that fixed the two constants."""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import pallas_paged_attention as ppa

LATENT, ROPE, SCALE, LAYER = 512, 64, 0.07, 1
#: name: layers, blocks, block size, slots, heads, table width, contexts
CELLS = {
    "openpangu-ultra-moe-718b": (5, 16385, 16, 64, 128, 192, (1500, 3072)),
    "ling-3.0-flash": (2, 6145, 128, 128, 32, 24, (2200, 3000)),
}


def gather_arm(q_c, q_r, new_c, new_kr, c, kr, tables, lengths):
    """What ``_latent_decode_attention`` does off the kernel: two
    scatters, a layer's slice, the table-wide gather."""
    block = c.shape[2]
    at = lengths - 1
    blk = jnp.take_along_axis(
        tables, (jnp.maximum(at, 0) // block)[:, None], axis=1)[:, 0]
    c = c.at[LAYER, blk, at % block, :LATENT].set(new_c[:, :LATENT])
    kr = kr.at[LAYER, blk, at % block, :ROPE].set(new_kr[:, :ROPE])
    out = ppa.paged_latent_attention_dense(
        q_c, q_r, c[LAYER], kr[LAYER], tables, lengths, scale=SCALE)
    return out, c, kr


def timed(fn, args, caches, calls):
    """``fn`` compiled for ``args``, run ``calls`` + 1 times on caches
    it is handed back: (ms a call, trace-and-lower s, compile s, its
    last results)."""
    c, kr = caches
    args = (*args[:4], c, kr, *args[4:])
    t0 = time.perf_counter()
    lowered = jax.jit(fn, donate_argnums=(4, 5)).lower(*args)
    t1 = time.perf_counter()
    run = lowered.compile()
    t2 = time.perf_counter()
    out, c, kr = run(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(calls):
        out, c, kr = run(*args[:4], c, kr, *args[6:])
    jax.block_until_ready(out)
    return ((time.perf_counter() - t) / calls * 1e3, t1 - t0, t2 - t1,
            out, c, kr)


def cell(name, rows_list, unrolls, seed=0):
    layers, blocks, block, slots, heads, table, (lo, hi) = CELLS[name]
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)

    def normal(key, shape, pad=0):
        a = jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
        return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),))

    lengths = rng.integers(lo, hi, slots).astype(np.int32)
    lengths[3] = 0
    need = -(-lengths // block)
    order = rng.permutation(np.arange(1, blocks))
    tables = np.zeros((slots, table), np.int32)
    for s, start in enumerate(np.cumsum(need) - need):
        tables[s, :need[s]] = order[start:start + need[s]]
    args = (normal(keys[0], (slots, heads, LATENT)),
            normal(keys[1], (slots, heads, ROPE)),
            normal(keys[2], (slots, LATENT)),
            normal(keys[3], (slots, ROPE), pad=128 - ROPE),
            jnp.asarray(tables), jnp.asarray(lengths))

    def fresh():
        return (normal(keys[4], (layers, blocks, block, LATENT)),
                normal(keys[5], (layers, blocks, block, ROPE),
                       pad=128 - ROPE))

    ms, _, _, want, want_c, want_kr = timed(gather_arm, args, fresh(), 5)
    live = lengths > 0
    want = np.asarray(want, np.float32)
    said = {"cell": name, "gather_ms": round(ms, 3),
            "live_rows": int(lengths.sum()),
            "rows_ms_at_819_GB_s": round(
                float(lengths.sum()) * (LATENT + 128) * 2 / 819e6, 3),
            "o_peak": float(np.abs(want[live]).max()), "readings": []}
    print(json.dumps(said), flush=True)
    # (the gather arm's scatter sends the idle slot's row to block 0)
    same = jax.jit(lambda a, b: jnp.array_equal(a[:, 1:], b[:, 1:]))
    for rows in rows_list:
        for unroll in unrolls:
            ppa.PAGE_UNROLL = unroll
            jax.clear_caches()
            ms, lower_s, compile_s, got, c, kr = timed(
                functools.partial(ppa.paged_latent_attention_write,
                                  layer=LAYER, scale=SCALE, target_rows=rows),
                args, fresh(), 20)
            got = np.asarray(got, np.float32)
            said["readings"].append({
                "target_rows": rows, "page_unroll": unroll,
                "ms": round(ms, 3), "trace_and_lower_s": round(lower_s, 2),
                "compile_s": round(compile_s, 2),
                "max_err": float(np.abs(got[live] - want[live]).max()),
                "idle_zero": not got[~live].any(),
                "rows_equal": bool(same(c, want_c) and same(kr, want_kr))})
            del c, kr
            print(json.dumps({"cell": name, **said["readings"][-1]}),
                  flush=True)
    return said


def main(argv):
    ints = lambda text: [int(n) for n in text.split(",")]  # noqa: E731
    rows_list = ints(argv[0]) if argv else [ppa.TARGET_ROWS]
    unrolls = ints(argv[1]) if len(argv) > 1 else [ppa.PAGE_UNROLL]
    results = [cell(name, rows_list, unrolls) for name in CELLS]
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "results": results}))
    sound = all(r["rows_equal"] and r["idle_zero"]
                and r["max_err"] <= 0.02 * c["o_peak"]
                for c in results for r in c["readings"])
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
