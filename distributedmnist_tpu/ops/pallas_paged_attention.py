"""Single-query paged attention as a Pallas TPU kernel.

The decode twin of :mod:`ops.pallas_attention`. The decode service's
hot path reads each slot's K/V through a block table into the paged
cache (:mod:`servesvc.kv_cache`: ``[layers, num_blocks, block_size,
heads, head_dim]`` arrays). The dense path gathers EVERY table entry
into a ``[slots, max_context, heads, head_dim]`` view before attending,
so a 10-token sequence pays the same HBM traffic as a 1k-token one.

This kernel fuses the table walk into the K/V tile load: the grid is
``(slots, max_blocks_per_seq)`` and the K/V BlockSpec index map reads
the prefetched block table — ``(tables[s, j], 0, 0)`` — so each grid
step DMAs exactly one cache block. Two properties make per-token
traffic O(actual context) instead of O(max context):

* dead table entries all point at the reserved null block
  (:data:`servesvc.kv_cache.NULL_BLOCK` = 0), and Pallas skips the DMA
  when consecutive grid steps map to the same block — the dead tail of
  a short sequence's table costs one null-block fetch, not P fetches;
* the accumulation body is wrapped in ``pl.when(j*block_size < length)``
  so dead blocks do no compute at all.

Numeric semantics are pinned to the dense decode path in
``models/transformer.py decode_step`` (and its parity tests): scores
and softmax in f32, scale ``1/sqrt(head_dim)``, masked positions get
the finite ``-1e30`` (whose exp underflows to exactly 0.0 in f32), one
online-softmax accumulator per head in VMEM scratch. The ONE documented
divergence: an idle slot (``length == 0``) returns exact zeros here,
while the dense path softmaxes a fully-masked row into a uniform
average of cache garbage — both are unspecified-by-contract (the
decode loop never reads idle rows), and the parity tests compare live
slots only.

Layout notes: heads are a static in-kernel unroll (decode head counts
are small); K/V tiles ride with heads folded into the lane dim. For
compiled-TPU efficiency size ``block_size`` to a multiple of 8 and
``head_dim`` to a multiple of 128 — other shapes are padded per call
(correct everywhere, and free in interpret mode, but the cache pad is
a real copy on-chip). ``interpret=None`` auto-selects the pallas
interpreter off-TPU, same as the training kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # finite: matches decode_step's mask, exp -> exact 0.0

_LANE = 128
_SUBLANE = 8


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, num_heads: int,
                  block_size: int, hdp: int):
    """One (slot, table-entry) grid step.

    ``tables_ref``/``lengths_ref`` are the scalar-prefetch operands
    (SMEM); the K/V tile for THIS step was already selected by the
    index map reading ``tables_ref[s, j]``, so the kernel body never
    sees a block id — only its tile."""
    s = pl.program_id(0)
    j = pl.program_id(1)
    np_ = pl.num_programs(1)
    length = lengths_ref[s]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Dead blocks (entirely past the sequence) do no compute; their
    # table entries are all NULL_BLOCK so the DMA was skipped too.
    @pl.when(j * block_size < length)
    def _accumulate():
        k_tile = k_ref[0].astype(jnp.float32)   # [Bp, h*hdp]
        v_tile = v_ref[0].astype(jnp.float32)
        q_all = q_ref[0].astype(jnp.float32)    # [hp, hdp]
        bp = k_tile.shape[0]
        tile_pos = jax.lax.broadcasted_iota(jnp.int32, (1, bp), 1)
        live = ((tile_pos < block_size)
                & (j * block_size + tile_pos < length))  # [1, Bp]
        for hh in range(num_heads):
            qh = q_all[hh:hh + 1, :]                       # [1, hdp]
            kh = k_tile[:, hh * hdp:(hh + 1) * hdp]        # [Bp, hdp]
            vh = v_tile[:, hh * hdp:(hh + 1) * hdp]
            sc = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [1, Bp]
            sc = jnp.where(live, sc, _NEG_INF)
            m_prev = m_ref[hh:hh + 1, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)                          # [1, Bp]
            corr = jnp.exp(m_prev - m_new)                   # [1, 1]
            l_new = (l_ref[hh:hh + 1, :1] * corr
                     + jnp.sum(p, axis=1, keepdims=True))
            acc_ref[hh:hh + 1, :] = (acc_ref[hh:hh + 1, :] * corr
                                     + jnp.dot(
                                         p, vh,
                                         preferred_element_type=jnp.float32))
            m_ref[hh:hh + 1, :] = jnp.broadcast_to(m_new, (1, _LANE))
            l_ref[hh:hh + 1, :] = jnp.broadcast_to(l_new, (1, _LANE))

    @pl.when(j == np_ - 1)
    def _finalize():
        # idle slots (length 0) never accumulated: l == 0 -> output 0.
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _pad_axis(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    scale: float | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Single-query attention over a paged KV cache, one layer.

    ``q``: [slots, heads, head_dim] (the current token's query, AFTER
    its K/V were scattered into the cache — position ``length-1``
    attends to itself through the cache, exactly like the dense path).
    ``k_pages``/``v_pages``: [num_blocks, block_size, heads, head_dim]
    (one layer of :class:`servesvc.kv_cache.PagedKVCache`).
    ``block_tables``: [slots, max_blocks_per_seq] int32, dead entries
    ``NULL_BLOCK``. ``lengths``: [slots] int32 — position count
    INCLUDING the current token; 0 marks an idle slot (output zeros).

    Returns [slots, heads, head_dim] float32.
    """
    num_slots, num_heads, hd = q.shape
    num_blocks, block_size, h2, hd2 = k_pages.shape
    assert (h2, hd2) == (num_heads, hd), (q.shape, k_pages.shape)
    assert v_pages.shape == k_pages.shape
    assert block_tables.shape[0] == num_slots == lengths.shape[0]
    width = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # tile-align: lanes (head_dim -> 128) and sublanes (block rows -> 8,
    # head rows -> 8). No-ops for TPU-sized models; real copies for the
    # tiny CPU-test shapes, where only correctness matters.
    hdp = hd + ((-hd) % _LANE)
    hp = num_heads + ((-num_heads) % _SUBLANE)
    qp = _pad_axis(_pad_axis(q, 2, _LANE), 1, _SUBLANE)       # [S, hp, hdp]
    kp = _pad_axis(_pad_axis(k_pages, 3, _LANE), 1, _SUBLANE)
    vp = _pad_axis(_pad_axis(v_pages, 3, _LANE), 1, _SUBLANE)
    bp = kp.shape[1]
    # heads fold into the lane dim of the K/V tiles (contiguous ->
    # free reshape); per-head lane slices select them in-kernel
    kp = kp.reshape(num_blocks, bp, num_heads * hdp)
    vp = vp.reshape(num_blocks, bp, num_heads * hdp)

    kernel = functools.partial(
        _paged_kernel, scale=scale, num_heads=num_heads,
        block_size=block_size, hdp=hdp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_slots, width),
        in_specs=[
            pl.BlockSpec((1, hp, hdp), lambda s, j, t, l: (s, 0, 0)),
            # the fused gather: this tile load IS the table walk
            pl.BlockSpec((1, bp, num_heads * hdp),
                         lambda s, j, t, l: (t[s, j], 0, 0)),
            pl.BlockSpec((1, bp, num_heads * hdp),
                         lambda s, j, t, l: (t[s, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hp, hdp), lambda s, j, t, l: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hp, _LANE), jnp.float32),  # running max
            pltpu.VMEM((hp, _LANE), jnp.float32),  # running denom
            pltpu.VMEM((hp, hdp), jnp.float32),    # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_slots, hp, hdp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qp, kp, vp)
    return out[:, :num_heads, :hd]


def paged_attention_dense(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, block_tables: jax.Array,
                          lengths: jax.Array, *,
                          scale: float | None = None) -> jax.Array:
    """The dense-gather oracle: same signature/semantics as
    :func:`paged_attention`, implemented with the full-table gather the
    decode path used before the kernel (and still uses under
    ``decode.attention_kernel = dense``). Parity tests pin the kernel
    against this for live slots; idle rows differ by design (see module
    docstring)."""
    num_slots, num_heads, hd = q.shape
    block_size = k_pages.shape[1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    ctx = block_tables.shape[1] * block_size
    kd = k_pages[block_tables].reshape(num_slots, ctx, num_heads, hd)
    vd = v_pages[block_tables].reshape(num_slots, ctx, num_heads, hd)
    live = jnp.arange(ctx)[None, :] < lengths[:, None]
    scores = jnp.einsum("shd,skhd->shk", q.astype(jnp.float32),
                        kd.astype(jnp.float32)) * scale
    scores = jnp.where(live[:, None, :], scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("shk,skhd->shd", w, vd.astype(jnp.float32))
