"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692 §3; the
``KimiDeltaAttention`` layer of flash-linear-attention): a layer with no
softmax, whose state is a SEQUENCE's and a MATRIX a head: ``S`` [d_k,
d_v], updated by a delta rule under a decay a key channel.

For a token ``t`` of a sequence, ``H`` heads of ``d_k = d_v = D``, a
convolution ``K`` taps wide, normed input ``x``::

    [q, k, v] = silu(conv_K(x W_qkv))        (d -> 3 H D; depth-wise, causal, no bias)
    q, k      <- q / |q|, k / |k|            (a head)
    g   = lower_bound * sigmoid(exp(A_log) * (x W_f + dt_bias))   [H, D], in (lower_bound, 0)
    b   = sigmoid(x W_beta)                                       [H]
    S_t = Diag(e^g) S_{t-1} + b k (v - S_{t-1}^T Diag(e^g) k)^T    [D, D] a head
    o_t = S_t^T q * D^-1/2
    out = (rmsnorm_head(o_t) * sigmoid(x W_og)) W_o               (H D -> d)

The state is kept ``[H, D, D]`` a sequence (the value's width in the
lanes, the key's in the sublanes: the bytes and tiles of ``[H D, D]``, and
the rank at which the token's ``u`` [H, D] is a plain broadcast over a
head's rows: stored ``[slots, H D, D]`` the compiler writes that
broadcast out, 268 MB a layer, before it adds it), in float32 whatever
the products are computed in: a recurrence over thousands of steps. What a decode replica
keeps of a sequence is ``S`` after its last token and the ``K - 1`` inputs
of the three convolutions before its next one (the *tail*, ``3 H D``
wide: q, k and v side by side).

Four functions carry the recurrence, three of them plain XLA and one a
Pallas kernel, and the tests hold them to one another:

* :func:`recurrence`: token by token, the equations as written (the
  oracle);
* :func:`chunked`: the same recurrence re-associated over chunks of
  :data:`CHUNK` tokens, what the prefill runs. In a chunk from state
  ``S_0``, with ``G_i`` the sum of ``g`` up to and with token ``i``: ``A_ij
  = sum_c k_i[c] e^(G_i[c] - G_j[c]) k_j[c]`` and ``B_ij`` the same with
  ``q_i``, for ``j <= i``; the pseudo-values solve the unit lower
  triangular ``u_i + b_i sum_{j<i} A_ij u_j = b_i (v_i - S_0^T (k_i *
  e^G_i))``; ``o_i = (S_0^T (q_i * e^G_i) + sum_{j<=i} B_ij u_j) D^-1/2``;
  ``S_C = Diag(e^G_C) S_0 + sum_j (k_j * e^(G_C - G_j)) u_j^T``. Every
  exponent is ``<= 0``: nothing is divided by a decay (``e^-G`` over 64
  tokens at ``g = -5`` is ``e^320``). A position at or past ``lengths``
  has ``g = 0`` and ``b = 0`` and so leaves ``S`` as it is: a prompt
  padded to a bucket hands on the state of its last real token, and a
  chunk wholly past every prompt is not computed;
* :func:`step`: one token a slot, ``S`` advanced where it lies (the
  caller donates it): two reads and a write of the state, because ``u``
  needs a reduction over a head's whole matrix before the first element
  of the new state can be written. What a decode step runs off the TPU,
  and the kernel's oracle;
* :func:`state_step`: the same token in ONE read and one write: a Pallas
  kernel that holds a block of heads' matrices in VMEM across the update
  and writes them over the block it read (the Mosaic call
  ``kda_state_step``). What a decode step runs on a TPU, for a float32
  state of whole tiles: :func:`state_arm` decides, from the process's
  devices, the shape and the dtype, and there is no option.

:func:`mixer` and :func:`mixer_step` are the whole sublayer body over a
block's leaves (``w_qkv`` [d, 3 H D], ``conv_w`` [K, 3 H D], ``w_f`` [d, H
D], ``a_log`` [H], ``dt_bias`` [H D], ``w_beta`` [d, H], ``w_og`` [d, H D],
``o_norm`` (one scale of D), ``wo`` [H D, d]), under the device scopes
``kda`` > ``kda_conv``, ``kda_gate``, ``kda_chunk`` (a sequence) and
``kda_state`` (a token: the kernel's call on a TPU, :func:`step`'s
fusions elsewhere): obsv/spans.py names their readers.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged_attention import _interpreted
from .ssm import causal_conv, conv_tail

Params = dict[str, Any]

#: tokens a chunk of :func:`chunked`: a chunk's decays between every pair
#: of its tokens are ``[chunk, chunk, D]`` float32 a head (16 MB for 32
#: heads of 128 at 32), and its triangular system is solved by
#: ``log2(chunk)`` products
CHUNK = 32

_HIGHEST = lax.Precision.HIGHEST

#: heads a grid item of :func:`state_step`: 16 matrices of 128 x 128
#: float32 are 1 MB a buffer, in and out double-buffered (PERF.md,
#: PR 47, has the blocks tried)
HEAD_BLOCK = 16
#: heads the kernel's text is written out for (a tile of sublanes)
_GROUP = 8
_LANE = 128


def gate(f: jax.Array, a_log: jax.Array, dt_bias: jax.Array,
         lower_bound: float) -> jax.Array:
    """The log-decay a head a key channel: ``f`` [..., H D] (``x W_f``),
    ``a_log`` [H], ``dt_bias`` [H D] -> ``lower_bound * sigmoid(exp(a_log)
    * (f + dt_bias))`` [..., H, D] float32, in ``(lower_bound, 0)`` (the
    bounded gate: a token forgets at most ``e^lower_bound`` of a
    channel)."""
    heads = a_log.shape[0]
    arg = (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)).reshape(
        *f.shape[:-1], heads, -1)
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(a_log.astype(jnp.float32))[:, None] * arg)


def _l2(x: jax.Array) -> jax.Array:
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _masked(g, beta, lengths):
    """``g`` [b, T, H, D] and ``beta`` [b, T, H] with the positions at or
    past ``lengths`` [b] made no-ops (``g = 0``, ``beta = 0``)."""
    if lengths is None:
        return g, beta
    live = jnp.arange(g.shape[1])[None, :] < lengths[:, None]
    return (jnp.where(live[:, :, None, None], g, 0.0),
            jnp.where(live[:, :, None], beta, 0.0))


def recurrence(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               beta: jax.Array, s0: jax.Array | None = None,
               lengths: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """The recurrence token by token, as the equations are written. ``q``,
    ``k``, ``g`` [batch, T, H, D]; ``v`` [batch, T, H, Dv]; ``beta`` [batch,
    T, H]; ``s0`` [batch, H, D, Dv] float32 (None: zeros); ``lengths``
    [batch] (None: T). Returns ``o`` [batch, T, H, Dv] float32 and the
    state after position ``lengths - 1``."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    g, beta = _masked(g, beta, lengths)
    b, _, h, d = q.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, d, v.shape[-1]), jnp.float32)

    def one_token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.sum(k_t[..., None] * s, axis=-2))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.sum(q_t[..., None] * s, axis=-2)

    time_first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    s_end, o = lax.scan(one_token, f32(s0),
                        tuple(map(time_first, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1) * d ** -0.5, s_end


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + L)^-1`` for ``L`` the strictly lower triangle of ``a`` [...,
    C, C]: ``L`` is nilpotent, so the inverse is the finite product ``(I
    - L)(I + L^2)(I + L^4)...``: ``log2(C)`` products and no loop over the
    rows."""
    c = a.shape[-1]
    x = -jnp.tril(a, -1)
    inv = jnp.eye(c, dtype=a.dtype) + x
    power = 2
    while power < c:
        x = jnp.matmul(x, x, precision=_HIGHEST)
        inv = inv + jnp.matmul(inv, x, precision=_HIGHEST)
        power *= 2
    return inv


def _one_chunk(s, q, k, v, g, beta):
    """A chunk from state ``s`` [b, H, D, Dv]: ``q``, ``k``, ``g`` [b, C, H,
    D], ``v`` [b, C, H, Dv], ``beta`` [b, C, H], float32, dead positions
    already no-ops. Returns the state after the chunk and ``o`` [b, C, H,
    Dv] (unscaled)."""
    c = q.shape[1]
    heads_first = lambda x: jnp.moveaxis(x, 2, 1)  # noqa: E731
    q, k, v, g = map(heads_first, (q, k, v, g))       # [b, H, C, D]
    beta = jnp.moveaxis(beta, 2, 1)[..., None]        # [b, H, C, 1]
    big_g = jnp.cumsum(g, axis=2)
    # decay from after token j to after token i, j <= i: exponents <= 0
    seen = jnp.tril(jnp.ones((c, c), bool))[:, :, None]
    between = jnp.exp(jnp.where(
        seen, big_g[:, :, :, None, :] - big_g[:, :, None, :, :], -jnp.inf))
    kj = k[:, :, None, :, :]
    a = jnp.sum(k[:, :, :, None, :] * between * kj, axis=-1)   # [b,H,C,C]
    bq = jnp.sum(q[:, :, :, None, :] * between * kj, axis=-1)
    from_start = jnp.exp(big_g)
    mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)  # noqa: E731
    rhs = beta * (v - mm(k * from_start, s))
    u = mm(_unit_lower_inverse(beta * a), rhs)                 # [b,H,C,Dv]
    o = mm(q * from_start, s) + mm(bq, u)
    to_end = jnp.exp(big_g[:, :, -1:, :] - big_g)
    s = (from_start[:, :, -1, :, None] * s
         + mm(jnp.swapaxes(k * to_end, -1, -2), u))
    return s, jnp.moveaxis(o, 1, 2)


def chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
            beta: jax.Array, s0: jax.Array | None = None,
            lengths: jax.Array | None = None, *, chunk: int = CHUNK
            ) -> tuple[jax.Array, jax.Array]:
    """:func:`recurrence` over chunks of ``chunk`` tokens (the module
    docstring has the algebra): the same arguments, the same two
    results."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    b, t, h, d = q.shape
    dv = v.shape[-1]
    if s0 is None:
        s0 = jnp.zeros((b, h, d, dv), jnp.float32)
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    chunk = min(chunk, t)
    pad = -t % chunk
    # [chunks, b, chunk, ...]: the chunk outermost for the loop
    split = lambda x: jnp.moveaxis(jnp.pad(  # noqa: E731
        f32(x), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)).reshape(
            b, -1, chunk, *x.shape[2:]), 1, 0)
    g, beta = _masked(f32(g), f32(beta), lengths)
    xs = tuple(map(split, (q, k, v, g, beta)))
    starts = jnp.arange(xs[0].shape[0]) * chunk
    longest = jnp.max(lengths)

    def chunk_or_nothing(s, x):
        # a chunk wholly past every sequence's length (a prompt's bucket
        # beyond the prompt) changes nothing and is not computed
        *x, start = x
        return lax.cond(
            start < longest, lambda s, x: _one_chunk(s, *x),
            lambda s, x: (s, jnp.zeros((b, chunk, h, dv), jnp.float32)),
            s, tuple(x))

    s_end, o = lax.scan(chunk_or_nothing, f32(s0), (*xs, starts))
    o = jnp.moveaxis(o, 0, 1).reshape(b, -1, h, dv)[:, :t]
    return o * d ** -0.5, s_end


def step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
         beta: jax.Array, s: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One token a slot: ``q``, ``k``, ``g`` [slots, H, D]; ``v`` [slots, H,
    Dv]; ``beta`` [slots, H]; ``s`` [slots, H, D, Dv]. Returns ``o``
    [slots, H, Dv] float32 and the new state in ``s``'s dtype. The state
    is read for the two products with the decayed state (``S^T k`` and
    ``S^T q``, one pass) and read again where it is written: ``o = S_t^T
    q`` is ``S_{t-1}^T Diag(e^g) q + (q . k) u``, so nothing reads the new
    state back."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    decayed = jnp.exp(g)[..., None] * f32(s)
    u = beta[..., None] * (v - jnp.sum(k[..., None] * decayed, axis=-2))
    o = (jnp.sum(q[..., None] * decayed, axis=-2)
         + jnp.sum(q * k, axis=-1, keepdims=True) * u)
    new = decayed + k[..., None] * u[..., None, :]
    return o * q.shape[-1] ** -0.5, new.astype(s.dtype)


def state_in_vmem(shape: tuple[int, ...], dtype) -> bool:
    """Whether Mosaic takes a state of ``shape`` ([slots, H, D, Dv]) and
    ``dtype`` for :func:`state_step` where it lies: float32 (the kernel
    rounds nothing the recurrence keeps), a head's matrix whole tiles
    (``D`` down eight sublanes, ``Dv`` along whole lanes). The one
    question :func:`state_arm` and the compiled kernel ask."""
    return (len(shape) == 4 and jnp.dtype(dtype) == jnp.float32
            and shape[2] % 8 == 0 and shape[3] % _LANE == 0)


def state_arm(shape: tuple[int, ...], dtype) -> str:
    """``"kernel"`` or ``"xla"``: what advances a decode step's state of
    ``shape`` and ``dtype`` in :func:`mixer_step`. The kernel where the
    process's devices are TPUs (``jax.devices()``, what its jitted step
    runs on, and not ``jax.default_backend()``, which a test patches: the
    reason ``models/transformer.py::decode_attention_arm`` has) and the
    state is one it compiles for (:func:`state_in_vmem`); anything else (a
    CPU, a toy head, a state not float32) runs :func:`step`."""
    on_tpus = jax.devices()[0].platform == "tpu"
    return "kernel" if on_tpus and state_in_vmem(shape, dtype) else "xla"


def _state_kernel(live_ref, beta_ref, q_ref, k_ref, v_ref, eg_ref, s_ref,
                  o_ref, new_ref, *, heads: int, scale: float):
    """A slot's block of ``hb`` heads: the state's block [1, hb, D, Dv]
    is in VMEM once, advanced by :func:`step`'s equations and written to
    the block it came from. ``live_ref`` [slots] int32 and ``beta_ref``
    [slots H] float32 are scalars (SMEM); ``q_ref``, ``k_ref``, ``eg_ref``
    [1, hb, D] and ``v_ref`` [1, hb, Dv] have the channel in the lanes.
    The heads are walked :data:`_GROUP` at a time, by a loop on the chip
    where a block holds several groups: one group's text whatever the
    block."""
    slot, hb = pl.program_id(0), s_ref.shape[1]
    first_beta = slot * heads + pl.program_id(1) * hb

    def advance(first, n: int):
        """Heads ``first`` to ``first + n`` of the block (``n`` static)."""
        these = pl.ds(first, n)
        q, k = q_ref[0, these], k_ref[0, these]
        # the key-side vectors index a head's ROWS: the three [n, D]
        # tiles, one under the other and filled up to whole lanes, are
        # turned once a group; column ``j n + h`` is then vector ``j``
        # of head ``h`` down the sublanes
        fill = -3 * n % _LANE
        down = jnp.concatenate(
            [eg_ref[0, these], k, q]
            + ([jnp.zeros((fill, q.shape[-1]), jnp.float32)] if fill else []),
            axis=0).T
        qk = jnp.sum(q * k, axis=-1, keepdims=True)               # [n, 1]
        outs = []
        for h in range(n):
            e, kc, qc = (down[:, j * n + h:j * n + h + 1]
                         for j in range(3))                       # [D, 1]
            decayed = e * s_ref[0, first + h]
            # (spread over the lanes once, for the reduction and the
            # update both)
            kc = jnp.broadcast_to(kc, decayed.shape)
            r_k = jnp.sum(kc * decayed, axis=0, keepdims=True)    # [1, Dv]
            r_q = jnp.sum(qc * decayed, axis=0, keepdims=True)
            u = beta_ref[first_beta + first + h] * (
                v_ref[0, pl.ds(first + h, 1)] - r_k)
            new_ref[0, first + h] = decayed + kc * u
            outs.append((r_q + qk[h:h + 1] * u) * scale)
        o_ref[0, these] = jnp.concatenate(outs, axis=0)

    @pl.when(live_ref[slot] == 0)
    def _idle():
        new_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[slot] != 0)
    def _live():
        if hb > _GROUP and hb % _GROUP == 0:
            lax.fori_loop(0, hb // _GROUP, lambda g, _: advance(
                pl.multiple_of(g * _GROUP, _GROUP), _GROUP), None)
        else:
            advance(0, hb)


def state_step(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               beta: jax.Array, s: jax.Array, live: jax.Array, *,
               head_block: int | None = None,
               interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """:func:`step` for the ``live`` slots [slots] (bool) in ONE pass over
    the state: a Pallas kernel (a Mosaic call named ``kda_state_step``)
    whose grid item is a slot's ``head_block`` heads (None:
    :data:`HEAD_BLOCK` where it divides ``H``, else all of them), their
    matrices fetched to VMEM once, advanced there (every product and both
    reductions float32 on the vector unit, ``e^g``, ``k`` and ``q`` turned
    down a head's rows inside the kernel) and written over the block they
    were read from (``input_output_aliases``: donate ``s``, or XLA copies
    it). A slot that is not live keeps its state bit for bit (the block
    written back as read) and answers zeros.

    Compiled, the state has to be one Mosaic takes
    (:func:`state_in_vmem`), or the call raises: :func:`step` is the
    caller's to choose (:func:`state_arm` asks the same question first).
    ``interpret=None`` picks the interpreter off the TPU, which runs any
    shape.

    Returns ``o`` [slots, H, Dv] float32 and the state advanced."""
    # (asked here, outside the jitted call: what is traced is then cached
    # under the answer, not under the question)
    interpret = _interpreted(interpret)
    if not interpret and not state_in_vmem(s.shape, s.dtype):
        raise ValueError(
            f"the delta-rule state kernel compiles for a float32 state "
            f"[slots, H, D, Dv] with D a multiple of 8 and Dv of {_LANE}, "
            f"not for {s.shape} of {s.dtype}: ops.kda.step advances this "
            f"one")
    heads = s.shape[1]
    hb = head_block or (HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads)
    assert heads % hb == 0, (heads, hb)
    return _state_call(q, k, v, g, beta, s, live, hb=hb, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _state_call(q, k, v, g, beta, s, live, *, hb, interpret):
    """The one call of :func:`_state_kernel` over the state whole, its
    grid a slot by a block of ``hb`` heads."""
    slots, heads, d, dv = s.shape
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    vec = lambda w: pl.BlockSpec(  # noqa: E731
        (1, hb, w), lambda i, j, *_: (i, j, 0))
    mat = pl.BlockSpec((1, hb, d, dv), lambda i, j, *_: (i, j, 0, 0))
    return pl.pallas_call(
        functools.partial(_state_kernel, heads=heads, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(slots, heads // hb),
            in_specs=[vec(d), vec(d), vec(dv), vec(d), mat],
            out_specs=(vec(dv), mat)),
        out_shape=(jax.ShapeDtypeStruct((slots, heads, dv), jnp.float32),
                   jax.ShapeDtypeStruct(s.shape, s.dtype)),
        # the state (operand 6, the two scalar arrays counted) is output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the block in and out, double-buffered, and a head's
            # temporaries
            vmem_limit_bytes=4 * hb * d * dv * 4 + (12 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=7 * s.size, transcendentals=0,
            bytes_accessed=4 * (2 * s.size
                                + slots * heads * (3 * d + 2 * dv))),
        interpret=interpret,
        name="kda_state_step",
    )(live.astype(jnp.int32), f32(beta).reshape(-1), f32(q), f32(k), f32(v),
      jnp.exp(f32(g)), s)


def _projected(h: jax.Array, blk: Params, lower_bound: float):
    """What a layer computes of its normed input beside the three
    convolved streams: the log-decay [..., H, D] and ``beta`` [..., H],
    float32 (scope ``kda_gate``), and the output's gate [..., H D]."""
    with jax.named_scope("kda_gate"):
        g = gate(h @ blk["w_f"], blk["a_log"], blk["dt_bias"], lower_bound)
        beta = jax.nn.sigmoid((h @ blk["w_beta"]).astype(jnp.float32))
    return g, beta, jax.nn.sigmoid((h @ blk["w_og"]).astype(jnp.float32))


def _heads(qkv: jax.Array, heads: int):
    """The convolved streams [..., 3 H D] as q, k (a head's L2 norm 1)
    and v, [..., H, D] float32."""
    q, k, v = (x.reshape(*x.shape[:-1], heads, -1) for x in jnp.split(
        qkv.astype(jnp.float32), 3, axis=-1))
    return _l2(q), _l2(k), v


def _output(o: jax.Array, out_gate: jax.Array, blk: Params, norm: Callable,
            dtype) -> jax.Array:
    """``(rmsnorm_head(o) * gate) W_o``: ``o`` [..., H, Dv] float32."""
    o = norm(o, blk["o_norm"]).reshape(out_gate.shape) * out_gate
    return o.astype(dtype) @ blk["wo"]


@jax.named_scope("kda")
def mixer(h: jax.Array, blk: Params, *, lower_bound: float, norm: Callable,
          lengths: jax.Array | None = None, return_state: bool = False,
          chunk: int = CHUNK):
    """The mixer over sequences ``h`` [batch, T, d] (normed) from an empty
    state: the sublayer's output [batch, T, d], and with ``return_state``
    the state after position ``lengths - 1`` [batch, H, D, Dv] float32 and
    the convolutions' tail there [batch, K - 1, 3 H D]."""
    heads = blk["a_log"].shape[0]
    qkv_in = h @ blk["w_qkv"]
    with jax.named_scope("kda_conv"):
        # (float32 in, float32 out: what the recurrence reads is rounded
        # once, where the projection left it)
        qkv = causal_conv(qkv_in.astype(jnp.float32), blk["conv_w"],
                          jnp.zeros((), jnp.float32))
    q, k, v = _heads(qkv, heads)
    g, beta, out_gate = _projected(h, blk, lower_bound)
    with jax.named_scope("kda_chunk"):
        o, s_end = chunked(q, k, v, g, beta, lengths=lengths, chunk=chunk)
    out = _output(o, out_gate, blk, norm, h.dtype)
    if not return_state:
        return out
    if lengths is None:
        lengths = jnp.full((h.shape[0],), h.shape[1], jnp.int32)
    return out, s_end, conv_tail(qkv_in, lengths, blk["conv_w"].shape[0])


@jax.named_scope("kda")
def mixer_step(h: jax.Array, blk: Params, s: jax.Array, tail: jax.Array,
               live: jax.Array, *, lower_bound: float, norm: Callable):
    """The mixer for one token a slot: ``h`` [slots, d] (normed), ``s``
    [slots, H, D, Dv], ``tail`` [K - 1, slots, 3 H D] (oldest first),
    ``live`` [slots] bool. Returns the sublayer's output [slots, d] and
    the two arrays advanced; a slot that is not live keeps both as they
    are."""
    heads = blk["a_log"].shape[0]
    qkv_in = h @ blk["w_qkv"]
    with jax.named_scope("kda_conv"):
        window = jnp.concatenate([tail.astype(qkv_in.dtype), qkv_in[None]])
        qkv = jax.nn.silu(jnp.sum(
            blk["conv_w"].astype(jnp.float32)[:, None, :]
            * window.astype(jnp.float32), axis=0))
        new_tail = jnp.where(live[None, :, None], window[1:].astype(
            tail.dtype), tail)
    q, k, v = _heads(qkv, heads)
    g, beta, out_gate = _projected(h, blk, lower_bound)
    with jax.named_scope("kda_state"):
        if state_arm(s.shape, s.dtype) == "kernel":
            o, new_s = state_step(q, k, v, g, beta, s, live)
        else:
            o, new_s = step(q, k, v, g, beta, s)
            new_s = jnp.where(live[:, None, None, None], new_s, s)
    return _output(o, out_gate, blk, norm, h.dtype), new_s, new_tail
