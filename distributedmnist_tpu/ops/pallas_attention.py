"""Fused flash attention as a Pallas TPU kernel.

The hot op of the long-context model family. XLA's dense-attention
lowering materializes the [s, s] score matrix in HBM; this kernel
streams K/V blocks through VMEM with an online-softmax accumulator, so
HBM traffic stays O(s·d) and the two matmuls per block ride the MXU
back-to-back without leaving the chip.

The reference has no attention at all (SURVEY §5.7; fixed 28×28 inputs,
reference src/mnist.py:27-30) — this is framework capability, not
parity. Composes with the sequence-parallel strategies:

* single-device / data-parallel: :func:`flash_attention_bshd` is the
  model-layout entry — it reads the residual stream's natural
  [batch, seq, heads, head_dim] (one free reshape away from
  [b, s, d_model]) via a head grid axis, so NO transpose is ever
  materialized around the kernel. Measured on v5e at d=2048 H=16 S=1024
  this removes ~20 ms/step of pure layout copies (~14% of the step).
* Ulysses (ops/ulysses_attention): after the all-to-all each device
  holds full sequences for a head subset in [b, h, s, d] —
  :func:`flash_attention` serves that layout (free reshape to a
  folded batch·heads grid, still no transpose).
* ring (ops/ring_attention): keeps its own psum-free online-softmax
  accumulator across ppermute steps.

Internally both entries run ONE kernel set over [B', s, H', d]:
bhsd folds to [b·h, s, 1, d], bshd keeps [b, s, h, d]; grid =
(B', H', q blocks, k blocks), the k dimension "arbitrary"
(sequential) so the f32 accumulator/max/denominator live in VMEM
scratch across k steps and outputs are written once at the final k
block. Head dim and sequence are padded to lane/block multiples and
masked, so any (s, d) works.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # finite: keeps exp() algebra NaN-free on padded rows

_LANE = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                 scale: float, causal: bool, block_q: int, block_k: int,
                 seq_len: int, save_lse: bool):
    if save_lse:  # lse output only exists on the VJP-forward variant
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Blocks strictly above the causal diagonal contribute nothing.
    live = (ik * block_k <= iq * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _accumulate():
        # native-dtype operands: bf16 inputs ride the MXU's bf16 path
        # (4× f32 throughput) with f32 accumulation via
        # preferred_element_type
        q = q_ref[0]   # [bq, dp]
        k = k_ref[0]   # [bk, dp]
        v = v_ref[0]   # [bk, dp]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len  # padded keys never attend
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask &= qpos >= kpos
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                       # [bq, bk]
        corr = jnp.exp(m_prev - m_new)               # [bq, 1]
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        if save_lse:
            # log-sum-exp per query row, lane-broadcast (the backward
            # kernels re-normalize scores with it instead of
            # re-reducing). The 128-lane replication is the TPU-native
            # layout for a per-sublane-row scalar (the lane dim cannot
            # go below one 128 tile); upstream flash kernels store TWO
            # such arrays (l and m) — folding into lse halves that.
            lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# Measured (block_q, block_k) table for v5e ("TPU v5 lite", bf16,
# head_dim ≤ 128), keyed by the smallest table seq ≥ s. Swept on-chip
# with scan-chunk timing (one dispatch per 10-50 kernel chains) over
# the FULL train composition — custom-vjp forward + dq + dkv kernels
# with all three cotangents consumed (an
# earlier sweep whose chain used only dq let XLA dead-code the dkv
# kernel and mis-ranked (512,1024) at depth): (1024,1024) wins at
# every S ≥ 1024 — 4.66 ms vs 7.78 for the old fixed (512,512) at the
# S=1024 shape (d=2048 H=16), 11.0 vs 16.3 at S=8192. Blocks stay ≤1024:
# 2048-wide blocks exceed the 16 MB scoped-VMEM stack limit at depth
# (compile-time OOM in the dkv kernel). Callers can still override
# explicitly; other chips inherit the table as a heuristic.
_TUNED_BLOCKS = (
    (512, (512, 512)),
    (1 << 62, (1024, 1024)),
)


def _auto_blocks(s: int, d: int = _LANE) -> tuple[int, int]:
    """The table's blocks for ``s``; a head wider than one lane tile
    (padded to two) holds blocks twice as large in VMEM, and
    (1024, 1024) then passes the scoped limit in the dkv kernel by 1 MB
    at compile time: such a head takes at most 512. Chosen to fit, not
    swept."""
    for bound, blocks in _TUNED_BLOCKS:
        if s <= bound:
            return blocks if d <= _LANE else tuple(min(b, 512)
                                                   for b in blocks)
    raise AssertionError  # unreachable: table ends with a sentinel


def _block_sizes(s: int, block_q: int, block_k: int) -> tuple[int, int]:
    """Clamp blocks to the sequence and align to the 8-row sublane tile.

    Beyond clamping, blocks are *balanced*: keep the block count implied
    by the requested size, then shrink each block so the last one isn't
    mostly padding (s=600 with 512-blocks becomes 2×304 → 608 padded
    rows instead of 2×512 → 1024, saving ~2.9× of masked-out MXU work).
    Balancing is discarded if it blows up the lcm padding instead. The
    backward must derive the SAME values so residual shapes line up.
    """
    import math
    r8 = lambda n: -(-n // 8) * 8
    bq0 = r8(min(block_q, max(s, 1)))
    bk0 = r8(min(block_k, max(s, 1)))
    bq1 = r8(-(-s // max(1, -(-s // bq0))))
    bk1 = r8(-(-s // max(1, -(-s // bk0))))

    def padded(bq, bk):
        m = math.lcm(bq, bk)
        return -(-s // m) * m

    return min(((bq1, bk1), (bq0, bk0)),
               key=lambda p: (padded(*p), -(p[0] * p[1])))


def _prep(x: jax.Array, block_q: int, block_k: int) -> jax.Array:
    """[B', s, H', d] → [B', s_padded, H'·d_padded] (lcm so BOTH grids
    tile the padded sequence exactly). The head axis folds into the
    lane dim — Pallas TPU blocks must keep their last two dims
    (sublane, lane) tile-aligned, so a head GRID axis instead selects
    each head's 128-lane slice via the index map (no transpose, and for
    d=128 no copy at all: the reshape is free)."""
    import math
    bb, s, hh, d = x.shape
    x = _pad_to(x, 3, _LANE)
    x = _pad_to(x, 1, math.lcm(block_q, block_k))
    return x.reshape(bb, x.shape[1], hh * x.shape[3])


def _vma_sds(shape, dtype, *inputs):
    """ShapeDtypeStruct declaring the union of the inputs' varying mesh
    axes — required for pallas_call outputs under shard_map check_vma."""
    vma = frozenset()
    for x in inputs:
        vma |= getattr(jax.typeof(x), "vma", frozenset()) or frozenset()
    return (jax.ShapeDtypeStruct(shape, dtype, vma=vma) if vma
            else jax.ShapeDtypeStruct(shape, dtype))


def _forward(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
             scale: float, block_q: int, block_k: int, interpret: bool,
             save_lse: bool) -> tuple[jax.Array, jax.Array | None]:
    bb, s, hh, d = q.shape
    block_q, block_k = _block_sizes(s, block_q, block_k)
    qp = _prep(q, block_q, block_k)
    kp = _prep(k, block_q, block_k)
    vp = _prep(v, block_q, block_k)
    _, sp, hdp = qp.shape
    dp = hdp // hh
    nq, nk = sp // block_q, sp // block_k

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_len=s, save_lse=save_lse)
    out_shape = [_vma_sds((bb, sp, hh * dp), q.dtype, qp, kp, vp)]
    out_specs = [pl.BlockSpec((1, block_q, dp),
                              lambda ib, ih, iq, ik: (ib, iq, ih))]
    if save_lse:
        out_shape.append(_vma_sds((bb, sp, hh * _LANE), jnp.float32,
                                  qp, kp, vp))
        out_specs.append(pl.BlockSpec((1, block_q, _LANE),
                                      lambda ib, ih, iq, ik: (ib, iq, ih)))
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(bb, hh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dp),
                         lambda ib, ih, iq, ik: (ib, iq, ih)),
            pl.BlockSpec((1, block_k, dp),
                         lambda ib, ih, iq, ik: (ib, ik, ih)),
            pl.BlockSpec((1, block_k, dp),
                         lambda ib, ih, iq, ik: (ib, ik, ih)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, dp), jnp.float32),     # acc
            pltpu.VMEM((block_q, _LANE), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANE), jnp.float32),  # running denom
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(qp, kp, vp)
    out = res[0].reshape(bb, sp, hh, dp)[:, :s, :, :d]
    return out, (res[1] if save_lse else None)


# ---------------------------------------------------------------------------
# Backward: FlashAttention-2 style pallas kernels. The forward saves
# per-row log-sum-exp, so the backward re-derives p = exp(s - lse) in
# one pass — no second online softmax. Two kernels, both recomputing
# the score block on the MXU from VMEM-resident tiles:
#   * dq: grid (B', H', q, k) — k innermost, dq accumulates in scratch.
#   * dk/dv: grid (B', H', k, q) — q innermost, so each k/v tile stays
#     resident while q/do/lse/delta stream past; the transposed
#     contractions (pᵀ·do, dsᵀ·q) ride the MXU via dot_general instead
#     of materializing a transpose.
# Residuals stay O(s·d) + O(s) for lse; the [s, s] score matrix never
# touches HBM in either direction.
# ---------------------------------------------------------------------------

def _scores_block(q_ref, k_ref, lse_ref, iq, ik, *, scale, causal,
                  block_q, block_k, seq_len):
    """Recompute p = exp(q·kᵀ·scale − lse) for one [bq, bk] tile.

    Padded rows carry garbage lse (the forward never normalized them),
    so validity masking must zero p — selection, not arithmetic, keeps
    the inf/NaN out."""
    s = jax.lax.dot_general(q_ref[0], k_ref[0],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (qpos < seq_len) & (kpos < seq_len)
    if causal:
        mask &= qpos >= kpos
    p = jnp.where(mask, jnp.exp(s - lse_ref[0][:, :1]), 0.0)
    return p


def _delta_block(do_ref, o_ref):
    """δ_i = rowsum(do ⊙ out) for one q block — recomputed in-kernel
    from the out residual (a [bq, d] elementwise+reduce, negligible
    next to the matmuls) instead of materializing a lane-broadcast
    [s, 128] array in HBM."""
    return jnp.sum(do_ref[0].astype(jnp.float32)
                   * o_ref[0].astype(jnp.float32), axis=1, keepdims=True)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                   dq_ref, dq_acc, *, scale: float, causal: bool,
                   block_q: int, block_k: int, seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = (ik * block_k <= iq * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _accumulate():
        p = _scores_block(q_ref, k_ref, lse_ref, iq, ik, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          seq_len=seq_len)
        k = k_ref[0]
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _delta_block(do_ref, o_ref))
        dq_acc[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    causal: bool, block_q: int, block_k: int, seq_len: int):
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # Under the causal mask, q blocks strictly before this k block see
    # none of it.
    live = (iq * block_q + block_q - 1 >= ik * block_k) if causal else True

    @pl.when(live)
    def _accumulate():
        p = _scores_block(q_ref, k_ref, lse_ref, iq, ik, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          seq_len=seq_len)
        q = q_ref[0]
        do = do_ref[0]
        # contract over the q rows (dim 0 of both): pᵀ·do and dsᵀ·q
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _delta_block(do_ref, o_ref))
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32) * scale

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, *, scale: float, causal: bool,
                      block_q: int, block_k: int, seq_len: int):
    """Single-visit backward for the one-block-pair case (nq == nk == 1,
    i.e. the whole padded sequence fits one (block_q, block_k) tile —
    true for every s ≤ 1024 under the tuned table). The split dq / dkv
    kernels each recompute the score matrix; here p and do·vᵀ are
    computed ONCE and feed all three cotangents — 7 → 5 score-sized
    matmuls (−29% backward FLOPs), measured −2.5 ms/step on a v5e at
    d=2048 H=16 S=1024. Larger grids keep the two-kernel path: a fused kernel
    would have to revisit dq blocks across non-adjacent iterations,
    and the resulting spill/reload traffic exceeds the recompute."""
    # the always-true pl.when is load-bearing on the interpreter path:
    # cond discharge inserts the vma adjustments that let ref gets on
    # mesh-varying blocks pass shard_map's check_vma (the split
    # kernels get this for free from their real pl.when branches)
    @pl.when(pl.program_id(2) == 0)
    def _all():
        p = _scores_block(q_ref, k_ref, lse_ref, 0, 0, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          seq_len=seq_len)
        q = q_ref[0]
        do = do_ref[0]
        dv_ref[0] = jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _delta_block(do_ref, o_ref))
        dq_ref[0] = (jnp.dot(ds.astype(q.dtype), k_ref[0],
                             preferred_element_type=jnp.float32)
                     * scale).astype(dq_ref.dtype)
        dk_ref[0] = (jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                     * scale).astype(dk_ref.dtype)


def _backward(q, k, v, out, lse, dout, causal: bool, scale: float,
              block_q: int, block_k: int, interpret: bool):
    bb, s, hh, d = q.shape
    block_q, block_k = _block_sizes(s, block_q, block_k)
    qp = _prep(q, block_q, block_k)
    kp = _prep(k, block_q, block_k)
    vp = _prep(v, block_q, block_k)
    dop = _prep(dout, block_q, block_k)
    op = _prep(out, block_q, block_k)
    _, sp, hdp = qp.shape
    dp = hdp // hh
    nq, nk = sp // block_q, sp // block_k
    assert lse.shape == (bb, sp, hh * _LANE), (lse.shape,
                                               (bb, sp, hh * _LANE))

    def unpad(x, dtype):
        return x.reshape(bb, sp, hh, dp)[:, :s, :, :d].astype(dtype)

    if nq == 1 and nk == 1:
        # one block pair — fused single-pass kernel (docstring above).
        # The grid keeps the 4D (B', H', 1, 1) shape of the split
        # kernels so every block index stays a traced grid value (a
        # literal 0 index breaks the interpreter's vma check under
        # shard_map — the Ulysses composition tests pin this).
        fspec = pl.BlockSpec((1, block_q, dp),
                             lambda ib, ih, i, j: (ib, i, ih))
        flane = pl.BlockSpec((1, block_q, _LANE),
                             lambda ib, ih, i, j: (ib, i, ih))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, seq_len=s),
            out_shape=[_vma_sds((bb, sp, hdp), t.dtype, qp, kp, vp, dop)
                       for t in (q, k, v)],
            grid=(bb, hh, 1, 1),
            in_specs=[fspec, fspec, fspec, fspec, fspec, flane],
            out_specs=[fspec, fspec, fspec],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
            name="flash_bwd_fused",
        )(qp, kp, vp, dop, op, lse)
        return (unpad(dq, q.dtype), unpad(dk, k.dtype), unpad(dv, v.dtype))

    # Per grid: the q-tiled operands follow the q program index — dim 2
    # in the dq grid (B', H', nq, nk), dim 3 in the dkv grid
    # (B', H', nk, nq) — and the k-tiled operands follow the other.
    qspec = pl.BlockSpec((1, block_q, dp), lambda ib, ih, i, j: (ib, i, ih))
    lane_q = pl.BlockSpec((1, block_q, _LANE),
                          lambda ib, ih, i, j: (ib, i, ih))
    qspec_inner = pl.BlockSpec((1, block_q, dp),
                               lambda ib, ih, i, j: (ib, j, ih))
    lane_q_inner = pl.BlockSpec((1, block_q, _LANE),
                                lambda ib, ih, i, j: (ib, j, ih))
    kspec = pl.BlockSpec((1, block_k, dp), lambda ib, ih, i, j: (ib, i, ih))
    kspec_inner = pl.BlockSpec((1, block_k, dp),
                               lambda ib, ih, i, j: (ib, j, ih))

    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_len=s)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        out_shape=_vma_sds((bb, sp, hdp), q.dtype, qp, kp, vp, dop),
        grid=(bb, hh, nq, nk),
        in_specs=[qspec, kspec_inner, kspec_inner, qspec, qspec, lane_q],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, op, lse)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        out_shape=[_vma_sds((bb, sp, hdp), k.dtype, qp, kp, vp, dop),
                   _vma_sds((bb, sp, hdp), v.dtype, qp, kp, vp, dop)],
        grid=(bb, hh, nk, nq),
        in_specs=[qspec_inner, kspec, kspec, qspec_inner, qspec_inner,
                  lane_q_inner],
        out_specs=[kspec, kspec],
        scratch_shapes=[pltpu.VMEM((block_k, dp), jnp.float32),
                        pltpu.VMEM((block_k, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, op, lse)

    return unpad(dq, q.dtype), unpad(dk, k.dtype), unpad(dv, v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _forward(q, k, v, causal, scale, block_q, block_k, interpret,
                    save_lse=False)[0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _forward(q, k, v, causal, scale, block_q, block_k, interpret,
                        save_lse=True)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, dout):
    q, k, v, out, lse = res
    return _backward(q, k, v, out, lse, dout, causal, scale, block_q,
                     block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)



def _resolve(s: int, d: int, scale, block_q, block_k, interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    auto_q, auto_k = _auto_blocks(s, d)
    return scale, block_q or auto_q, block_k or auto_k, interpret


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Exact attention, flash-style. q/k/v: [batch, heads, seq, head_dim]
    (self-attention: one shared seq length). Returns q-shaped output.
    Differentiable (custom blockwise VJP).

    ``block_q``/``block_k`` default to the measured per-seq-length
    table (``_TUNED_BLOCKS``); pass explicit values to override.
    ``interpret=None`` auto-selects: compiled kernel on TPU, pallas
    interpreter elsewhere (the CPU test path).
    """
    b, h, s, d = q.shape
    assert k.shape == v.shape == (b, h, s, d), (q.shape, k.shape, v.shape)
    scale, block_q, block_k, interpret = _resolve(s, d, scale, block_q,
                                                 block_k, interpret)
    # fold heads into the grid's batch dim — a FREE reshape (leading
    # dims merge; no transpose, unlike a [b,s,h,d]→[b,h,s,d] caller)
    fold = lambda x: x.reshape(b * h, s, 1, d)
    out = _flash(fold(q), fold(k), fold(v), causal, scale, block_q,
                 block_k, interpret)
    return out.reshape(b, h, s, d)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention_bshd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, scale: float | None = None,
                         block_q: int | None = None,
                         block_k: int | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """Flash attention over the MODEL layout [batch, seq, heads,
    head_dim] — one free reshape from the residual stream's
    [b, s, d_model], so no [b,s,h,d]→[b,h,s,d] transpose is ever
    materialized (pallas operand layout constraints would force real
    HBM copies; at d=2048 H=16 S=1024 those copies cost more than
    twice the kernel itself). The head dim rides a grid axis; tiles are
    strided in HBM, which the DMA engine handles natively.
    """
    b, s, h, d = q.shape
    assert k.shape == v.shape == (b, s, h, d), (q.shape, k.shape, v.shape)
    scale, block_q, block_k, interpret = _resolve(s, d, scale, block_q,
                                                 block_k, interpret)
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret)


flash_attention_bshd.layout = "bshd"  # models detect and skip transposes
