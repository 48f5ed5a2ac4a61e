"""A compact causal-LM transformer — the long-context model family.

Not a reference-parity model (the reference has no attention anywhere,
SURVEY §5.7); this exists so the framework's sequence-parallel path —
ring attention over the mesh's ``seq`` axis (ops/ring_attention.py) —
has a first-class consumer, and so the aggregation disciplines can be
exercised on a transformer-shaped allreduce payload.

Pure init/apply over a param pytree, pre-norm blocks; learned positional
embeddings and a weight-tied LM head, or (a tree with no ``pos`` and a
``head`` of its own) rotary positions inside the attention and an untied
head.

What a layer computes is decided in ONE place, :func:`make_block`: which
attention (``local_self_attention`` single-device, the flash kernel, or
a closure over ring/Ulysses attention under a seq-sharded shard_map)
behind which projections (``wqkv``, or the latent ones of
:func:`latent_projections`), which feed-forward (the dense ReLU product,
a gated unit, or a routed mixture of experts), how a sublayer reads from
and writes to the residual (:data:`PLAIN`: ``x + F(norm x)``, or
:func:`stream_residual`), whether a sublayer's output is normed before
it joins the residual (``out_norm``), how the block attends to a paged
cache one token at a time (``Block.decode_attn``), and under which mesh axes (Megatron-style tensor parallelism
over a ``model_axis`` when params are sharded per
:func:`param_partition_specs` — qkv/w1 column-parallel, wo/w2
row-parallel with one psum per residual add, attention heads split
across the axis). The six forwards (:func:`apply`,
:func:`prefill_with_kv`, :func:`decode_step`, :func:`apply_pp`,
:func:`grads_pp_1f1b`, :func:`apply_pp_1f1b`) take the block and decide
only their schedule. A new kind of feed-forward is one function
``(h, blk) -> (mlp, aux)`` and its parameters in :func:`init`.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec

from .cnn import truncated_normal_init
from ..core.config import DECODE_ATTENTION_KERNELS
from ..ops.ring_attention import local_self_attention

Params = dict[str, Any]


def init(key: jax.Array, vocab_size: int = 256, model_dim: int = 128,
         num_heads: int = 4, num_layers: int = 2,
         max_seq_len: int = 512, num_experts: int = 0,
         sizes: "Sizes | None" = None) -> Params:
    """``num_experts > 0`` makes every block's FFN a top-1-routed
    mixture of experts (ops/moe.py) instead of a dense MLP. ``sizes``
    (:class:`Sizes`) adds the parameters of what it names; None is the
    block above."""
    if sizes is not None:
        return _init_sized(key, vocab_size, model_dim, num_heads, num_layers,
                           max_seq_len, sizes)
    assert model_dim % num_heads == 0
    keys = iter(jax.random.split(key, 4 + 5 * num_layers))
    scale = 0.02
    params: Params = {
        "embed": truncated_normal_init(next(keys), (vocab_size, model_dim), scale),
        "pos": truncated_normal_init(next(keys), (max_seq_len, model_dim), scale),
        "blocks": [],
        "final_norm": {"scale": jnp.ones((model_dim,), jnp.float32)},
    }
    ff = 4 * model_dim
    for _ in range(num_layers):
        blk = {
            "ln1": {"scale": jnp.ones((model_dim,), jnp.float32)},
            # [d, 3, d] (not [d, 3d]): the last dim is the shardable
            # per-head output dim, so a model-axis column shard keeps
            # whole q/k/v head groups together
            "wqkv": truncated_normal_init(next(keys), (model_dim, 3, model_dim), scale),
            "wo": truncated_normal_init(next(keys), (model_dim, model_dim), scale),
            "ln2": {"scale": jnp.ones((model_dim,), jnp.float32)},
        }
        if num_experts > 0:
            blk["router"] = truncated_normal_init(
                next(keys), (model_dim, num_experts), scale)
            k1, k2 = jax.random.split(next(keys))
            blk["w1"] = truncated_normal_init(k1, (num_experts, model_dim, ff), scale)
            blk["w2"] = truncated_normal_init(k2, (num_experts, ff, model_dim), scale)
        else:
            blk["w1"] = truncated_normal_init(next(keys), (model_dim, ff), scale)
            blk["w2"] = truncated_normal_init(next(keys), (ff, model_dim), scale)
        params["blocks"].append(blk)
    return params


class Sizes(NamedTuple):
    """The sizes of the mechanisms beyond the block above, as
    ``core/config.py::ModelConfig`` names them; each absent at 0 (1 for
    the streams). ``held`` is ``(first, count)`` of ``routed_experts``."""
    q_latent_dim: int = 0
    kv_latent_dim: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    ffn_dim: int = 0
    routed_experts: int = 0
    held: tuple[int, int] = (0, 0)
    shared_experts: int = 0
    expert_ffn_dim: int = 0
    dense_layers: int = 0
    residual_streams: int = 1
    nextn_layers: int = 0
    sandwich_norm: bool = False
    # the scale the selection bias starts at; 0 for a router without one
    # (``router_bias_rate`` 0: the leaf stays zeros and selects nothing)
    router_bias_init: float = 0.03
    # key-value heads an attention layer keeps (0: one a query head)
    kv_heads: int = 0
    # state-space layers (ops/ssm.py): ``ssm_state_dim`` > 0 makes every
    # layer one but those at ``attn_offset`` modulo ``attn_period``
    ssm_state_dim: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    attn_period: int = 0
    attn_offset: int = 0
    # delta-rule linear-attention layers (ops/kda.py): ``kda_head_dim`` >
    # 0 makes every layer one, the model's heads of that many key and
    # value channels each, but those at ``attn_offset`` modulo
    # ``attn_period``; ``kda_conv`` taps in its three convolutions, the
    # log-decay bounded below by ``kda_lower_bound``
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    # an attention layer's output a head times ``sigmoid(w_h . x)``
    attn_head_gate: bool = False

    @property
    def mixer(self) -> str:
        """The kind of the layers that do not attend: ``"ssm"``,
        ``"kda"``, or ``""`` where every layer attends."""
        return ("ssm" if self.ssm_state_dim
                else "kda" if self.kda_head_dim else "")

    def attends(self, layer: int) -> bool:
        """Whether ``layer`` is an attention layer."""
        return (not self.mixer
                or (self.attn_period > 0
                    and layer % self.attn_period == self.attn_offset))


#: leaves that stay float32 in the forward whatever the compute dtype:
#: a top-k and a Sinkhorn iteration amplify what a rounding changes
_F32_LEAVES = ("router", "router_bias", "mix1", "mix2",
               # a state-space layer's decay, step bias and skip, a
               # delta-rule layer's decay and its bias: what a recurrence
               # multiplies by at every token
               "a_log", "b_dt", "d_skip", "dt_bias")


def _init_mix(key: jax.Array, n: int, d: int) -> Params:
    """One sublayer's three maps between ``n`` residual streams
    (:func:`stream_residual`): ``phi`` [n·d, n + n + n²] for the read,
    the write and the stream-to-stream map, their three scales
    ``alpha`` at 0.01 and biases ``beta``, the stream-to-stream one so
    that the map starts near the identity."""
    beta = jnp.concatenate([jnp.zeros((2 * n,), jnp.float32),
                            (8.0 * jnp.eye(n, dtype=jnp.float32)).reshape(-1)])
    return {"phi": truncated_normal_init(key, (n * d, 2 * n + n * n), 0.02),
            "alpha": jnp.full((3,), 0.01, jnp.float32), "beta": beta}


def _norm_scale(n: int) -> Params:
    return {"scale": jnp.ones((n,), jnp.float32)}


def _init_gated(key: jax.Array, lead: tuple, d: int, f: int) -> Params:
    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": truncated_normal_init(kg, (*lead, d, f), 0.02),
            "w_up": truncated_normal_init(ku, (*lead, d, f), 0.02),
            "w_down": truncated_normal_init(kd, (*lead, f, d), 0.02)}


#: Depth-scaled sandwich norm (Pangu Ultra, arXiv:2504.07866 §2): the
#: scale of the norm on a sublayer's output starts at ``c / sqrt(L)``, ``L``
#: the model's depth, so that what ``L`` layers add to the residual stays
#: of the embedding's order: ``c`` for attention and for the feed-forward
SANDWICH_C = (0.283, 0.432)


def _init_mixer(key: jax.Array, d: int, z: Sizes) -> Params:
    """A state-space layer's mixer (ops/ssm.py has the equations and the
    leaves). Matrices at 0.02 as everywhere; what a scale cannot stand
    in for as Mamba initialises it (arXiv:2312.00752 s3.6, its published
    code): ``a_log = log(1..N)`` a channel, ``d_skip = 1``, ``b_dt`` such
    that ``softplus(b_dt)`` is log-uniform in [1e-3, 1e-1], the
    convolution uniform in ``+-K^-1/2``. With ``A`` near 0 a state never
    decays, with the step near 1 it forgets in a token: either way what
    is kept of a sequence stops mattering to its next token."""
    e, n, taps = z.ssm_expand * d, z.ssm_state_dim, z.ssm_conv
    rank = z.ssm_dt_rank or -(-d // 16)
    keys = iter(jax.random.split(key, 8))
    tn = lambda shape: truncated_normal_init(next(keys), shape, 0.02)  # noqa: E731
    bound = taps ** -0.5
    step = jnp.exp(jax.random.uniform(next(keys), (e,), jnp.float32)
                   * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return {
        "w_in": tn((d, 2, e)),
        "conv_w": jax.random.uniform(next(keys), (taps, e), jnp.float32,
                                     -bound, bound),
        "conv_b": jax.random.uniform(next(keys), (e,), jnp.float32,
                                     -bound, bound),
        "w_x": tn((e, rank + 2 * n)),
        "dt_norm": _norm_scale(rank), "b_norm": _norm_scale(n),
        "c_norm": _norm_scale(n),
        "w_dt": tn((rank, e)),
        # the inverse of softplus at ``step``
        "b_dt": step + jnp.log(-jnp.expm1(-step)),
        "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=jnp.float32))[:, None], (n, e)),
        "d_skip": jnp.ones((e,), jnp.float32),
        "w_out": tn((e, d))}


def _init_kda(key: jax.Array, d: int, heads: int, z: Sizes) -> Params:
    """A delta-rule layer's mixer (ops/kda.py has the equations and the
    leaves). Matrices at 0.02 as everywhere (the block's output
    projections then depth-scaled: :func:`_depth_scaled_outputs`), the
    convolutions uniform in
    ``+-K^-1/2``; what a scale cannot stand in for: a channel's log-decay
    is ``lower_bound * sigmoid(exp(a_log) (x W_f + dt_bias))``, so with
    ``a_log = 0`` the bias is the logit of ``-g / -lower_bound`` at ``g``
    log-uniform in ``[-0.5, -1e-3]``: a token keeps between ``e^-0.5 =
    0.61`` and ``0.999`` of a channel before ``x W_f`` (at 0.005, a quarter
    of a unit of logit on a normed input) moves it. With every decay
    near 1 a state never forgets, near ``e^lower_bound`` it forgets in a
    token: either way what is kept of a sequence stops mattering to its
    next token."""
    e, taps = heads * z.kda_head_dim, z.kda_conv
    keys = iter(jax.random.split(key, 8))
    tn = lambda shape, scale=0.02: truncated_normal_init(  # noqa: E731
        next(keys), shape, scale)
    bound = taps ** -0.5
    forget = jnp.exp(jax.random.uniform(next(keys), (e,), jnp.float32)
                     * (math.log(0.5) - math.log(1e-3)) + math.log(1e-3))
    share = forget / -z.kda_lower_bound
    return {
        "w_qkv": tn((d, 3 * e)),
        "conv_w": jax.random.uniform(next(keys), (taps, 3 * e), jnp.float32,
                                     -bound, bound),
        "w_f": tn((d, e), 0.005),
        "a_log": jnp.zeros((heads,), jnp.float32),
        "dt_bias": jnp.log(share) - jnp.log1p(-share),
        "w_beta": tn((d, heads)),
        "w_og": tn((d, e)),
        "o_norm": _norm_scale(z.kda_head_dim),
        "wo": tn((e, d))}


def _init_sized_block(key: jax.Array, d: int, heads: int, z: Sizes,
                      routed: bool, depth: int = 1,
                      attends: bool = True) -> Params:
    keys = iter(jax.random.split(key, 12))
    ones = _norm_scale
    tn = lambda shape: truncated_normal_init(next(keys), shape, 0.02)  # noqa: E731
    blk = {"ln1": ones(d), "ln2": ones(d)}
    if z.sandwich_norm:
        # a norm on each sublayer's output (make_block's ``out_norm``),
        # its scale depth-scaled
        for name, c in zip(("ln1_out", "ln2_out"), SANDWICH_C):
            blk[name] = {"scale": jnp.full((d,), c / math.sqrt(depth),
                                           jnp.float32)}
    if not attends:
        blk.update(_init_kda(next(keys), d, heads, z) if z.mixer == "kda"
                   else _init_mixer(next(keys), d, z))
    elif z.kv_latent_dim:
        qk = z.qk_nope_dim + z.qk_rope_dim
        if z.q_latent_dim:
            blk.update(
                wq_a=tn((d, z.q_latent_dim)), q_norm=ones(z.q_latent_dim),
                wq_b=tn((z.q_latent_dim, heads, qk)))
        else:
            # the query projected at full rank, no latent of its own
            blk.update(wq=tn((d, heads, qk)))
        blk.update(
            wkv_a=tn((d, z.kv_latent_dim + z.qk_rope_dim)),
            kv_norm=ones(z.kv_latent_dim),
            wkv_b=tn((z.kv_latent_dim, heads, z.qk_nope_dim + z.v_head_dim)),
            wo=tn((heads * z.v_head_dim, d)))
        if z.attn_head_gate:
            blk["w_hgate"] = tn((d, heads))
    elif z.kv_heads and z.kv_heads != heads:
        # fewer key-value heads than query heads: one matrix, the
        # queries' columns, then the keys', then the values'
        blk.update(wqkv=tn((d, (heads + 2 * z.kv_heads) * (d // heads))),
                   wo=tn((d, d)))
    else:
        blk.update(wqkv=tn((d, 3, d)), wo=tn((d, d)))
    if z.residual_streams > 1:
        blk["mix1"] = _init_mix(next(keys), z.residual_streams, d)
        blk["mix2"] = _init_mix(next(keys), z.residual_streams, d)
    if routed:
        blk["router"] = tn((d, z.routed_experts))
        # the selection bias: the loss's gradient does not reach it; it
        # moves by each expert's load (ops.moe.balance_term)
        blk["router_bias"] = truncated_normal_init(
            next(keys), (z.routed_experts,), z.router_bias_init)
        blk["experts"] = _init_gated(next(keys), (z.held[1],), d,
                                     z.expert_ffn_dim)
        if z.shared_experts:
            blk["shared"] = _init_gated(
                next(keys), (), d, z.shared_experts * z.expert_ffn_dim)
    elif z.ffn_dim:
        blk.update(_init_gated(next(keys), (), d, z.ffn_dim))
    else:
        blk.update(w1=tn((d, 4 * d)), w2=tn((4 * d, d)))
    if z.mixer == "kda":
        blk = _depth_scaled_outputs(blk, depth)
    return blk


def _depth_scaled_outputs(blk: Params, depth: int) -> Params:
    """A block's output projections (``wo``; ``w_down`` of the dense unit,
    of the experts and of the shared one) times ``(2 depth)^-1/2``: GPT-2's
    rule for the matrices that write to the residual, two sublayers a
    layer. With them at the other matrices' scale a sublayer of this tree
    writes 0.6 of the unit embedding, the residual at thirteen layers is
    the sublayers' and their bfloat16 rounding reaches every later router
    whole: 9 in 100 of a top 8 of 512 under a group limit then differ
    from the float32 reference's at a near tie (the chip and the CPU read
    0.908-0.912 equal sets for the harness's 0.93; PERF.md, PR 45). Scaled,
    the embedding stays most of what a layer reads, as
    :func:`_init_sized` means it to."""
    scale = (2 * depth) ** -0.5
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a * scale if getattr(path[-1], "key", None) in (
            "wo", "w_down") else a, blk)


def _init_sized(key: jax.Array, vocab_size: int, d: int, heads: int,
                num_layers: int, max_seq_len: int, z: Sizes) -> Params:
    """The tree of a model with :class:`Sizes`: no position table and an
    untied ``head`` where the attention is latent (its positions are
    rotary), the first ``dense_layers`` blocks with the dense
    feed-forward and the rest routed, and under ``nextn`` the
    next-next-token module: two norms, the ``[2d, d]`` projection, one
    block of the routed kind and a final norm of its own."""
    if z.nextn_layers > 1:
        raise ValueError("only one next-next-token module is built "
                         f"(nextn_layers={z.nextn_layers})")
    keys = iter(jax.random.split(key, 6 + num_layers))
    ones = _norm_scale
    routed_at = lambda i: z.routed_experts > 0 and i >= z.dense_layers  # noqa: E731
    params: Params = {
        # at unit scale, fifty times the matrices': a token's own
        # embedding then stays most of what its layers read. With the
        # embedding at 0.02 too, the running mean of the values that
        # attention adds (one vector for all late positions, four times
        # the embedding's size) makes every token read alike, and a
        # router sends them all to the same experts from step 1
        "embed": truncated_normal_init(next(keys), (vocab_size, d), 1.0),
        "blocks": [_init_sized_block(next(keys), d, heads, z, routed_at(i),
                                     num_layers, z.attends(i))
                   for i in range(num_layers)],
        "final_norm": ones(d),
    }
    if z.kv_latent_dim:
        params["head"] = truncated_normal_init(next(keys), (d, vocab_size),
                                               0.02)
    elif not z.mixer:
        # (a model with state-space layers has no positional term at
        # all: the recurrence orders its tokens; its head is tied)
        params["pos"] = truncated_normal_init(next(keys), (max_seq_len, d),
                                              0.02)
    if z.nextn_layers:
        params["nextn"] = {
            "norm_h": ones(d), "norm_e": ones(d),
            "proj": truncated_normal_init(next(keys), (2 * d, d), 0.02),
            "block": _init_sized_block(next(keys), d, heads, z,
                                       routed_at(num_layers)),
            "final_norm": ones(d)}
    return params


def param_partition_specs(num_layers: int, model_axis: str | None,
                          num_experts: int = 0,
                          expert_axis: str | None = None) -> Params:
    """Mesh placement for the flat (per-layer list) layout.

    ``model_axis`` (TP) → Megatron layout: qkv & MLP-in column-parallel
    (output dim sharded), their consumers wo & MLP-out row-parallel
    (input dim sharded → one psum each per block); embeddings and norms
    replicated.

    ``expert_axis`` (EP, num_experts > 0) → w1/w2's leading EXPERT dim
    sharded; the router stays replicated. The two compose: EP picks
    which experts a rank holds, TP splits each expert's hidden dim (and
    the attention heads) across the model axis."""
    P = PartitionSpec
    m = model_axis  # None → replicated on the TP dims
    if num_experts > 0:
        e = expert_axis
        blk = {
            "ln1": {"scale": P()},
            "wqkv": P(None, None, m),
            "wo": P(m, None),
            "ln2": {"scale": P()}, "router": P(),
            "w1": P(e, None, m),
            "w2": P(e, m, None),
        }
    else:
        blk = {
            "ln1": {"scale": P()},
            "wqkv": P(None, None, m),
            "wo": P(m, None),
            "ln2": {"scale": P()},
            "w1": P(None, m),
            "w2": P(m, None),
        }
    return {"embed": P(), "pos": P(), "blocks": [dict(blk) for _ in range(num_layers)],
            "final_norm": {"scale": P()}}


def _rms_norm(x: jax.Array, p: Params, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


class Residual(NamedTuple):
    """How a sublayer reads from and writes to the residual: ``read(x,
    maps) -> (u, kept)`` gives the sublayer's input, ``write(kept, y)``
    the residual after its output ``y``; ``start`` makes the residual of
    the embedding and ``end`` what the final norm reads. ``maps`` is the
    sublayer's own parameters of the rule (None where it has none)."""
    read: Callable[[jax.Array, Any], tuple[jax.Array, Any]]
    write: Callable[[Any, jax.Array], jax.Array]
    start: Callable[[jax.Array], jax.Array]
    end: Callable[[jax.Array], jax.Array]
    streams: int = 1


#: ``x + F(norm x)``: the sublayer reads the residual and adds to it
PLAIN = Residual(read=lambda x, maps: (x, x), write=lambda x, y: x + y,
                 start=lambda x: x, end=lambda x: x)


#: the same rule over a float32 residual, whatever the sublayers compute
#: in: what a sublayer adds is rounded once, not again at every later
#: write. For a block whose sublayers add little to a residual the
#: embedding dominates (depth-scaled output norms: 0.13 to 0.19 at five
#: layers): a bfloat16 residual near 1 resolves 0.004, a thirtieth of
#: such an addition, and every rounding on a router's way costs it near
#: ties (top 8 of 256: 0.95 of the sets equal for 0.97, on the chip)
FLOAT32 = Residual(read=lambda x, maps: (x, x), write=lambda x, y: x + y,
                   start=lambda x: x.astype(jnp.float32), end=lambda x: x)


def _sinkhorn(r: jax.Array, iters: int, eps: float, clamp: float) -> jax.Array:
    """``r`` [n, n, tokens] → positive matrices whose rows and columns
    sum to one: ``exp`` of the clipped entries, then ``iters`` times
    rows and then columns divided by their sums plus ``eps``."""
    m = jnp.exp(jnp.clip(r, -clamp, clamp))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
    return m


def _f32_product(x: jax.Array, phi: jax.Array) -> jax.Array:
    """``einsum("ntd,ndk->kt")`` of the residual with a float32 ``phi``,
    to float32's precision. A bfloat16 residual times the three
    bfloat16 pieces a float32 number splits into is that product
    exactly, in three passes of the MXU with no float32 copy of the
    residual (six, and a copy, at ``HIGHEST``); the weight's gradient
    goes through the leading piece."""
    if x.dtype != jnp.bfloat16:
        return jnp.einsum("ntd,ndk->kt", x.astype(jnp.float32), phi,
                          precision=lax.Precision.HIGHEST)
    hi = phi.astype(jnp.bfloat16)
    rest = lax.stop_gradient(phi - hi.astype(jnp.float32))
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    z = jnp.einsum("ntd,ndk->kt", x, jnp.concatenate([hi, mid, low], -1),
                   preferred_element_type=jnp.float32)
    return sum(jnp.split(z, 3, axis=0))


def stream_residual(n: int, *, iters: int, eps: float,
                    clamp: float) -> Residual:
    """``n`` residual streams under three learned maps a sublayer
    (manifold-constrained hyper-connections, arXiv:2512.24880, on
    arXiv:2409.19606). The residual is ``[n, b, s, d]``, streams
    outermost so that the two tiled dimensions stay ``s`` and ``d``. A
    token's ``n·d`` values, RMS-normed without a scale, times ``phi``
    (plus the biases, times the scales) give ``a`` [n], ``p`` [n] and
    ``R`` [n, n]: the sublayer reads ``σ(a) · X``, and the residual
    becomes ``SK(R) X + (2σ(p))ᵀ y``, ``SK`` the Sinkhorn iteration of
    :func:`_sinkhorn`. All of it in float32, whatever the residual is
    stored in, and what the sublayer reads stays float32 through its
    norm: a router downstream is a top-k, and each rounding on its way
    costs it near ties. It starts as ``n`` copies of the embedding and
    ends as the sum of the streams."""
    def read(x, maps):
        _, b, s, d = x.shape
        xf = x.astype(jnp.float32).reshape(n, b * s, d)
        inv_rms = lax.rsqrt(jnp.mean(jnp.square(xf), axis=(0, 2)) + 1e-6)
        z = _f32_product(x.reshape(n, b * s, d),
                         maps["phi"].reshape(n, d, -1)) * inv_rms
        alpha = maps["alpha"][np.repeat(np.arange(3), [n, n, n * n])]
        z = alpha[:, None] * z + maps["beta"][:, None]
        h_pre = jax.nn.sigmoid(z[:n])                        # [n, t]
        h_post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
        h_res = _sinkhorn(z[2 * n:].reshape(n, n, -1), iters, eps, clamp)
        # sums of n scaled streams, spelled out: a contraction over n
        # with the tokens as its batch would be 8,192 tiny products
        u = sum(h_pre[m, :, None] * xf[m] for m in range(n))
        return u.reshape(b, s, d), (x, h_res, h_post)

    def write(kept, y):
        x, h_res, h_post = kept
        _, b, s, d = x.shape
        xf = x.astype(jnp.float32).reshape(n, b * s, d)
        yf = y.astype(jnp.float32).reshape(b * s, d)
        out = jnp.stack([
            sum(h_res[i, m, :, None] * xf[m] for m in range(n))
            + h_post[i, :, None] * yf for i in range(n)])
        return out.reshape(x.shape).astype(x.dtype)

    return Residual(
        read=jax.named_scope("residual_mix")(read),
        write=jax.named_scope("residual_mix")(write),
        start=lambda x: jnp.broadcast_to(x, (n, *x.shape)),
        end=lambda x: jnp.sum(x.astype(jnp.float32), axis=0).astype(x.dtype),
        streams=n)


class Block(NamedTuple):
    """What one transformer layer computes, as its two pre-norm
    sublayers. :func:`make_block` builds it once; every forward below
    takes one and decides only its schedule."""
    # attn(x, blk, return_kv=False, positions=None) -> x, or (x, k, v)
    # for the prefill
    attn: Callable[..., Any]
    # ffn(x, blk) -> (x, aux)
    ffn: Callable[[jax.Array, Params], tuple[jax.Array, Any]]
    # how both read from and write to the residual
    residual: Residual = PLAIN
    # the block's RMSNorm (its epsilon bound), which the final norm shares
    norm: Callable[[jax.Array, Params], jax.Array] = _rms_norm
    # the attention sublayer of :func:`decode_step`, one token against the
    # paged cache: None is the plain block's (:func:`_decode_attn`), else
    # ``(x, blk, li, k_cache, v_cache, block_tables, positions, blk_ids,
    # offs, live, lengths=, attention_kernel=) -> (x, k_cache, v_cache)``
    decode_attn: Callable[..., Any] | None = None
    # set where the first sublayer is a state-space mixer and not
    # attention (``attn`` is then that mixer over sequences, takes
    # ``lengths`` and with ``return_kv`` returns the state and the
    # convolution's tail in place of keys and values): the same sublayer
    # for one token a slot against what a slot keeps of its sequence,
    # ``(x, blk, state, tail, live) -> (x, state, tail)``
    mixer_step: Callable[..., Any] | None = None


def _dense_ffn(h: jax.Array, blk: Params, *,
               model_axis: str | None) -> tuple[jax.Array, jax.Array]:
    mlp = jax.nn.relu(h @ blk["w1"]) @ blk["w2"]
    aux = jnp.zeros((), jnp.float32)
    if model_axis:
        mlp = lax.psum(mlp, model_axis)
    return mlp, aux


def gated_feed_forward(h: jax.Array, blk: Params):
    """The gated SiLU unit over a block's ``w_gate``/``w_up``/``w_down``
    for :func:`make_block`."""
    from ..ops.moe import gated_unit
    return (gated_unit(h.astype(blk["w_gate"].dtype), blk["w_gate"],
                       blk["w_up"], blk["w_down"]),
            jnp.zeros((), jnp.float32))


def moe_feed_forward(**settings) -> Callable:
    """A mixture-of-experts feed-forward for :func:`make_block`.

    With ``held``: per-token routing as it is deployed
    (``ops.moe.routed_ffn`` over a block's ``router``/``router_bias``/
    ``experts``/``shared``): ``total`` experts routed over, ``held =
    (first, count)`` of them computed here, ``top_k`` a token, gates
    scaled by ``scaling``, none dropped, the selection bias moved toward
    even load at ``bias_rate``. Its ``aux`` is a mapping: ``routing`` [b,
    s, k] int32, the ids chosen, ``counts`` [count], the pairs each held
    expert took, and ``loss``, the zero-valued term that carries the
    bias's update.

    Without: ``ops.moe.moe_ffn`` over a block's ``router``/``w1``/``w2``
    with its settings bound (``num_experts``, ``capacity_factor``,
    ``router_top_k``, ``num_groups``, and the mesh axes: ``expert_axis``
    the experts are sharded over, ``tp_axis`` every expert's hidden dim
    is split over — one fused psum covers both — and ``stats_axes``, the
    extra token-sharding axes (the seq axis under SP×MoE) the
    load-balance statistics average over, so the aux loss is the
    full-token value replicated on every shard)."""
    from ..ops.moe import moe_ffn, routed_ffn

    if "held" in settings:
        @jax.named_scope("moe")
        def routed(h, blk):
            # the decode step's tokens [slots, d] are one row of them
            one_row = h.ndim == 2
            out, ids, counts, balance = routed_ffn(
                h[None] if one_row else h, blk["router"],
                blk["router_bias"], blk["experts"], blk.get("shared"),
                **settings)
            if one_row:
                out, ids = out[0], ids[0]
            return out, {"routing": ids, "counts": counts, "loss": balance}
        return routed

    def feed_forward(h, blk):
        return moe_ffn(h, blk["router"], blk["w1"], blk["w2"], **settings)
    return feed_forward


def _yarn_inv_freq(dim: int, theta: float, factor: float, original_len: int,
                   beta_fast: float, beta_slow: float):
    """Rotary frequencies [dim/2] under YaRN (arXiv:2309.00071, as
    DeepSeek-V3 computes them): each frequency a blend of ``f`` and
    ``f / factor`` by a linear ramp between the two correction
    dimensions, the ones that turn ``beta_fast`` and ``beta_slow`` times
    over ``original_len`` positions."""
    f = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1.0:
        return f

    def correction_dim(turns):
        return (dim * math.log(original_len / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f / factor * ramp + f * (1 - ramp)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rotate(x: jax.Array, positions: jax.Array, inv_freq, mscale: float):
    """Rotary embedding of ``x`` [..., s, heads, dim] at ``positions``
    [s], pairs laid out as halves: column ``i`` turns with column ``i +
    dim/2``. In float32."""
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(angle) * mscale)[:, None, :]
    sin = (jnp.sin(angle) * mscale)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def latent_projections(*, num_heads: int, qk_nope_dim: int, qk_rope_dim: int,
                       v_head_dim: int, rope_theta: float = 10000.0,
                       rope_factor: float = 1.0, rope_original_len: int = 0,
                       rope_beta_fast: float = 32.0,
                       rope_beta_slow: float = 1.0, rope_mscale: float = 1.0,
                       rope_mscale_all_dim: float = 0.0,
                       norm_eps: float = 1e-6) -> Callable:
    """The projections of latent attention for :func:`make_block`
    (arXiv:2405.04434 §2.1): ``project(h, blk, positions) -> (q, k, v,
    scale, rows)`` in the [b, s, heads, width] layout, over a block's
    ``wq_a``/``q_norm``/``wq_b``/``wkv_a``/``kv_norm``/``wkv_b`` (or, for
    a query projected at full rank, ``wq`` [d, heads, width] in place of
    the first three). Query and key are ``qk_nope_dim + qk_rope_dim`` wide, the rotated part
    last and the key's one row shared by all heads; the value
    ``v_head_dim``. ``scale`` is ``(qk width)^-½`` times the square of
    YaRN's ``mscale_all_dim`` factor. ``rows`` is what a decode cache
    keeps of a token, one row for all heads: the normed latent [b, s,
    kv_latent] and the rotated key [b, s, qk_rope_dim]. ``project.decode``
    is the same attention for one token against a cache of such rows
    (:func:`_latent_decode_attention`)."""
    inv_freq = _yarn_inv_freq(qk_rope_dim, rope_theta, rope_factor,
                              rope_original_len, rope_beta_fast,
                              rope_beta_slow)
    cos_scale = (_yarn_mscale(rope_factor, rope_mscale)
                 / _yarn_mscale(rope_factor, rope_mscale_all_dim))
    scale = ((qk_nope_dim + qk_rope_dim) ** -0.5
             * _yarn_mscale(rope_factor, rope_mscale_all_dim) ** 2)

    def project(h, blk, positions):
        b, s, _ = h.shape
        if positions is None:
            positions = jnp.arange(s)
        if "wq" in blk:
            q = jnp.einsum("bsd,dhe->bshe", h, blk["wq"])
        else:
            q = jnp.einsum("bsr,rhe->bshe", _rms_norm(
                h @ blk["wq_a"], blk["q_norm"], norm_eps), blk["wq_b"])
        kv_a = h @ blk["wkv_a"]
        latent, k_rope = kv_a[..., :-qk_rope_dim], kv_a[..., -qk_rope_dim:]
        latent = _rms_norm(latent, blk["kv_norm"], norm_eps)
        kv = jnp.einsum("bsr,rhe->bshe", latent, blk["wkv_b"])
        rot = functools.partial(_rotate, positions=positions,
                                inv_freq=inv_freq, mscale=cos_scale)
        q = jnp.concatenate([q[..., :qk_nope_dim],
                             rot(q[..., qk_nope_dim:])], axis=-1)
        k_rope = rot(k_rope[:, :, None, :])
        k = jnp.concatenate(
            [kv[..., :qk_nope_dim],
             jnp.broadcast_to(k_rope, (b, s, num_heads, qk_rope_dim))],
            axis=-1)
        return q, k, kv[..., qk_nope_dim:], scale, (latent, k_rope[:, :, 0])

    project.decode = functools.partial(
        _latent_decode_attention, qk_nope_dim=qk_nope_dim, scale=scale,
        rotate=functools.partial(_rotate, inv_freq=inv_freq,
                                 mscale=cos_scale))
    return project


def _head_gated(o: jax.Array, h: jax.Array, blk: Params) -> jax.Array:
    """An attention output ``o`` [..., heads, width] a head times
    ``sigmoid(w_h . h)``, where the block has ``w_hgate`` [d, heads]."""
    if "w_hgate" not in blk:
        return o
    gate = jax.nn.sigmoid((h @ blk["w_hgate"]).astype(jnp.float32))
    return (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)


def make_block(*, num_heads: int, attention_fn: Callable | None = None,
               model_axis: str | None = None,
               feed_forward: Callable | None = None,
               projections: Callable | None = None,
               residual: Residual = PLAIN, out_norm: bool = False,
               norm_eps: float = 1e-6, kv_heads: int = 0,
               mixer: "bool | str" = False,
               kda_lower_bound: float = -5.0) -> Block:
    """The one place a layer's kind is decided: which attention behind
    which projections, which feed-forward, which residual rule, under
    which mesh axes.

    ``attention_fn``: ``local_self_attention`` when None, the flash
    kernel, or a closure over ring/Ulysses attention under a seq-sharded
    shard_map; its ``layout`` attribute (``bhsd`` default, or ``bshd``)
    says which head layout it reads.

    ``model_axis``: when set (inside shard_map, params sharded per
    :func:`param_partition_specs`), the block is tensor-parallel — this
    rank computes its ``num_heads / axis_size`` heads and its MLP column
    slice; row-parallel projections psum partial sums back to the full
    residual. Activations stay replicated over the axis, so the logits
    (and any loss) are identical on every TP rank.

    ``feed_forward``: ``(h, blk) -> (mlp, aux)`` on the normed residual
    ``h``, returning the WHOLE residual delta (a callable that shards
    its product sums it itself, as ``moe_ffn`` does) and an ``aux``: a
    scalar auxiliary loss, or a mapping (``routing``, ``counts``) from
    the per-token routed one. None is the dense ReLU product over
    ``w1``/``w2``; :func:`gated_feed_forward` the gated unit;
    :func:`moe_feed_forward` the routed ones. The scalar is the mean
    per-group load-balance loss of this block's routing (linear across
    blocks/ticks/shards: forwards sum over layers and average over
    microbatches), kept by a forward only where the block's parameters
    hold a ``router``.

    ``projections``: ``(h, blk, positions) -> (q, k, v, scale)`` in the
    [b, s, heads, width] layout; None is ``wqkv`` split in three
    (:func:`latent_projections` is the other). A value narrower than
    the key is padded with zero columns up to it for the kernel, and the
    output cut back.

    ``residual``: the :class:`Residual` rule of both sublayers,
    :data:`PLAIN` or :func:`stream_residual`; a sublayer's maps are the
    block's ``mix1`` (attention) and ``mix2`` (feed-forward).

    ``out_norm``: a second RMSNorm a sublayer, on its OUTPUT before it
    joins the residual (sandwich norms, arXiv:2504.07866 §2): ``x +
    norm(F(norm x))`` over the block's ``ln1_out`` and ``ln2_out``.
    ``norm_eps`` is the epsilon of every norm of the block.

    ``kv_heads``: key-value heads under ``wqkv`` where they are fewer
    than the query heads (0: one each): ``wqkv`` is then one matrix ``[d,
    (heads + 2 kv_heads) hd]``, a group of ``heads / kv_heads`` queries
    attends to one head's keys and values, and the rows a cache keeps are
    that one head's.

    ``mixer``: the first sublayer is a mixer over the block's leaves and
    not attention: the state-space one of ``ops/ssm.py`` (True or
    ``"ssm"``) or the delta-rule one of ``ops/kda.py`` (``"kda"``, its
    log-decay bounded below by ``kda_lower_bound``): no projections, no
    attention function, no positions; the block's ``mixer_step`` is its
    form for one token.
    """
    norm = (_rms_norm if norm_eps == 1e-6
            else functools.partial(_rms_norm, eps=norm_eps))
    attention = attention_fn or local_self_attention
    if feed_forward is None:
        feed_forward = functools.partial(_dense_ffn, model_axis=model_axis)
    bshd = getattr(attention, "layout", "bhsd") == "bshd"

    def wqkv_projections(h, blk, positions):
        del positions  # the embedding carries them
        b, d = h.shape[0], h.shape[-1]
        # read here, inside the shard_map, not when the block is built
        m = lax.axis_size(model_axis) if model_axis else 1
        if num_heads % m != 0:
            raise ValueError(f"num_heads={num_heads} not divisible by "
                             f"model-parallel size {m}")
        qkv = jnp.einsum("bsd,dte->bste", h, blk["wqkv"])  # e = d/m
        return (*(qkv[:, :, i].reshape(b, -1, num_heads // m,
                                       d // num_heads) for i in range(3)),
                None, None)

    def grouped_projections(h, blk, positions):
        del positions  # such a model has no positional term
        b, d = h.shape[0], h.shape[-1]
        hd = d // num_heads
        qkv = h @ blk["wqkv"]
        q, k, v = jnp.split(qkv, [num_heads * hd,
                                  (num_heads + kv_heads) * hd], axis=-1)
        q = q.reshape(b, -1, num_heads, hd)
        k, v = (t.reshape(b, -1, kv_heads, hd) for t in (k, v))
        # the attention function is handed a key and a value a query
        # head: copies in HBM, a cost and no other departure
        # (ops/pallas_attention.py's index maps could read the one head)
        wide = lambda t: jnp.repeat(t, num_heads // kv_heads, axis=2)  # noqa: E731
        return q, wide(k), wide(v), None, (k, v)

    grouped = bool(kv_heads) and kv_heads != num_heads
    if grouped and (model_axis or num_heads % kv_heads):
        raise ValueError(f"{kv_heads} key-value heads for {num_heads} "
                         "query heads: not a whole group a head, or "
                         "under a model axis, which no rule splits them "
                         "over")
    project = projections or (grouped_projections if grouped
                              else wqkv_projections)

    @jax.named_scope("attention")
    def attn(x: jax.Array, blk: Params, return_kv: bool = False,
             positions: jax.Array | None = None):
        """Pre-norm attention sublayer: the residual after
        wo(attn(project(ln1(read(x))))).

        ``return_kv``: also return what the decode prefill scatters
        into the paged KV cache: this layer's K/V in the [b, s, h, hd]
        residual layout, or the rows the projections say a cache keeps
        (a latent and a rotated key a token, [b, s, width])."""
        u, kept = residual.read(x, blk.get("mix1"))
        b = u.shape[0]
        # the norm in what the rule read; the products in the weights'
        h = norm(u, blk["ln1"]).astype(blk["wo"].dtype)
        q, k, v, scale, rows = project(h, blk, positions)
        kw = {} if scale is None else {"scale": scale}
        wide = q.shape[-1] - v.shape[-1]
        vk = jnp.pad(v, ((0, 0),) * 3 + ((0, wide),)) if wide else v
        if bshd:
            # kernel reads the residual layout directly ([b, s, h, hd] is
            # a free reshape of [b, s, e]) — no head transpose on either
            # side. At d=2048 H=16 S=1024 the transposes a bhsd
            # attention forces cost ~20 ms/step, 2.5× the kernel itself.
            # (A fully fused qkv-packed kernel input was also measured:
            # the strided k/v lane reads cost MORE than the slice copies
            # they save.)
            o = attention(q, k, vk, **kw)
        else:
            heads = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731
            o = attention(heads(q), heads(k), heads(vk), **kw)
            o = o.transpose(0, 2, 1, 3)
        if wide:
            o = o[..., :v.shape[-1]]
        o = _head_gated(o, h, blk)
        # row-parallel: partial sum of the full d
        proj = o.reshape(b, -1, o.shape[2] * o.shape[3]) @ blk["wo"]
        if model_axis:
            proj = lax.psum(proj, model_axis)
        if out_norm:
            proj = norm(proj, blk["ln1_out"])
        out = residual.write(kept, proj)
        if return_kv:
            return (out, *(rows or (k, v)))
        return out

    @jax.named_scope("ffn")
    def ffn(x: jax.Array, blk: Params) -> tuple[jax.Array, Any]:
        """Pre-norm FFN sublayer: the residual after
        feed_forward(ln2(read(x))), aux."""
        u, kept = residual.read(x, blk.get("mix2"))
        mlp, aux = feed_forward(norm(u, blk["ln2"]), blk)
        if out_norm:
            mlp = norm(mlp, blk["ln2_out"])
        return residual.write(kept, mlp), aux

    decode_attn = None
    if hasattr(project, "decode"):
        decode_attn = functools.partial(project.decode, norm=norm,
                                        out_norm=out_norm)
    if not mixer:
        return Block(attn, ffn, residual, norm, decode_attn)

    # (the functions looked up in their module at each call, not bound
    # here: what a trace runs is what the module holds then)
    if mixer == "kda":
        from ..ops import kda as ops
        out_leaf, how = "wo", {"lower_bound": kda_lower_bound}
    else:
        from ..ops import ssm as ops
        out_leaf, how = "w_out", {}

    def mix(x: jax.Array, blk: Params, return_kv: bool = False,
            positions: jax.Array | None = None,
            lengths: jax.Array | None = None):
        """Pre-norm mixer sublayer over sequences from an empty state:
        the residual after mixer(ln1(read(x))); with ``return_kv`` also
        the state after position ``lengths - 1`` and the convolution's
        tail there (``lengths`` [batch]; None: the whole sequence)."""
        del positions  # the recurrence orders the tokens
        u, kept = residual.read(x, blk.get("mix1"))
        h = norm(u, blk["ln1"]).astype(blk[out_leaf].dtype)
        out = ops.mixer(h, blk, norm=norm, lengths=lengths,
                        return_state=return_kv, **how)
        if not return_kv:
            return residual.write(kept, out)
        return (residual.write(kept, out[0]), *out[1:])

    def mix_step(x, blk, state, tail, live):
        h = norm(x, blk["ln1"]).astype(blk[out_leaf].dtype)
        out, state, tail = ops.mixer_step(h, blk, state, tail, live,
                                          norm=norm, **how)
        return x + out.astype(x.dtype), state, tail

    return Block(mix, ffn, residual, norm, None, mix_step)


def apply(params: Params, tokens: jax.Array, *,
          block: "Block | tuple[Block, ...]",
          positions: jax.Array | None = None,
          compute_dtype=jnp.bfloat16, remat: bool = False,
          remat_policy: str = "full",
          return_aux: bool = False, train: bool = False,
          nextn_loss_weight: float = 0.0) -> jax.Array:
    """tokens [batch, seq] int32 → logits [batch, seq, vocab] float32
    through ``block`` (:func:`make_block`), layer after layer; a tuple
    gives each layer its own (the last also serves the next-next-token
    module where the tree has one).

    ``positions`` (global positions of this shard's tokens) must be
    passed when the sequence is sharded; defaults to arange(seq).
    ``return_aux``: also return the mapping ``aux``: ``loss``, the term
    the train step adds to the next-token loss (the summed
    load-balancing loss; with ``train`` and a ``nextn`` module in the
    tree, ``nextn_loss_weight`` times the module's loss, which nothing
    else runs), and from per-token routed layers ``routing`` [layers, b,
    s, k] and ``counts`` [layers, held] (the module's layer last, when
    it ran). The flag adds outputs and changes nothing else.
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    p = _cast(params, compute_dtype)
    blocks = _per_layer(block, len(p["blocks"]))
    layers = {id(bk): _layer(bk, positions, remat, remat_policy)
              for bk in blocks}
    x = blocks[0].residual.start(_embed(p, tokens, positions))
    aux_total = jnp.zeros((), jnp.float32)
    routed = []
    for bk, blk in zip(blocks, p["blocks"]):
        x, aux = layers[id(bk)](x, blk)
        aux_total = aux_total + _loss_of(aux, routed)
    h = blocks[-1].residual.end(x)
    logits = _head(p, h, norm=blocks[-1].norm)
    if train and "nextn" in p:
        loss, aux = _nextn_loss(p, h, tokens, layers[id(blocks[-1])],
                                blocks[-1].residual)
        aux_total = (aux_total + nextn_loss_weight * loss
                     + _loss_of(aux, routed))
    if not return_aux:
        return logits
    return logits, {"loss": aux_total, **_stacked(routed)}


def _per_layer(block: "Block | tuple[Block, ...]", layers: int
               ) -> tuple[Block, ...]:
    """One block a layer: a forward's ``block`` is one for all, or one
    each."""
    return tuple(block) if not isinstance(block, Block) else (block,) * layers


def _stacked(routed: list) -> dict:
    """What per-token routed layers said, a leading dimension a layer
    (empty without such layers)."""
    return ({k: jnp.stack([a[k] for a in routed]) for k in routed[0]}
            if routed else {})


def _loss_of(aux, routed: list):
    """A feed-forward's ``aux`` as the term it adds to the loss; a
    mapping (per-token routing) leaves the rest of itself on ``routed``."""
    if not isinstance(aux, dict):
        return aux
    routed.append({k: v for k, v in aux.items() if k != "loss"})
    return aux["loss"]


def _layer(block: Block, positions, remat: bool, remat_policy: str):
    """One layer of ``block`` under the recomputation policy."""
    def layer(x, blk):
        return block.ffn(block.attn(x, blk, positions=positions), blk)

    if not remat:
        return layer
    if remat_policy == "save_attn":
        # Selective remat: the FFN sublayer (and its norms)
        # recomputes in the backward, but the attention sublayer
        # stays OUTSIDE the checkpoint, so the flash kernel's
        # custom-vjp residuals (q/k/v/out/lse) remain resident and
        # the backward never re-runs the attention forward. Costs
        # O(b·s·d) extra bytes per layer over full remat; at the
        # S=8192 (d=1024, L=2, v5e, July 2026) it bought 1.14x tokens/sec.
        ffn_ckpt = jax.checkpoint(block.ffn)
        return lambda x, blk: ffn_ckpt(
            block.attn(x, blk, positions=positions), blk)
    if remat_policy == "full":
        # trade one extra forward per block for O(layer-boundary)
        # activation memory — the long-sequence HBM lever
        return jax.checkpoint(layer)
    raise ValueError(f"unknown remat_policy {remat_policy!r} "
                     "(expected 'full' or 'save_attn')")


@jax.named_scope("mtp")
def _nextn_loss(p: Params, h: jax.Array, tokens: jax.Array, layer,
                residual: Residual):
    """The next-next-token module (arXiv:2412.19437 §2.2, depth 1) on
    the trunk's output ``h`` [b, s, d] (before the final norm): position
    ``i`` joins ``norm(h_i)`` and ``norm(Emb(t_{i+1}))`` through ``proj``,
    runs one layer and its own final norm, and predicts ``t_{i+2}``
    through the shared head. Returns its mean cross-entropy over the
    positions that have such a target, and the layer's ``aux``. The
    last position is given ``t_0`` for want of a successor: no position
    before it attends to it, and it has no target."""
    m = p["nextn"]
    joined = jnp.concatenate(
        [_rms_norm(h, m["norm_h"]),
         _rms_norm(p["embed"][jnp.roll(tokens, -1, axis=1)], m["norm_e"])],
        axis=-1) @ m["proj"]
    x, aux = layer(residual.start(joined), m["block"])
    logits = _head(p, residual.end(x), m["final_norm"])
    return loss_fn(logits[:, :-1], tokens[:, 1:]), aux


# Device scopes (obsv/spans.py SCOPES): every HLO operation carries the
# scope it was traced under in its op_name, backward and recomputed
# operations included, so a profiler trace splits a step by layer.

@jax.named_scope("cast")
def _cast(params: Params, compute_dtype) -> Params:
    """The stored weights in the compute dtype, once per program; the
    leaves of :data:`_F32_LEAVES` stay as they are stored."""
    def cast(path, a):
        keep = any(getattr(k, "key", None) in _F32_LEAVES for k in path)
        return a if keep else a.astype(compute_dtype)
    return jax.tree_util.tree_map_with_path(cast, params)


@jax.named_scope("embed")
def _embed(p: Params, tokens: jax.Array, positions: jax.Array) -> jax.Array:
    """The embedding, plus the learned position where the tree has a
    table (one without rotates inside its attention)."""
    x = p["embed"][tokens]
    return x + p["pos"][positions] if "pos" in p else x


@jax.named_scope("head")
def _head(p: Params, x: jax.Array, final_norm: Params | None = None,
          norm: Callable = _rms_norm) -> jax.Array:
    """Final norm (the tree's, or the one handed in; ``norm`` the
    block's) and the head: [..., d] → float32 logits, through the tree's
    own ``head`` or the embedding transposed."""
    x = norm(x, final_norm or p["final_norm"])
    w = p["head"] if "head" in p else p["embed"].T
    # (a float32 residual meets the head in the head's dtype)
    return (x.astype(w.dtype) @ w).astype(jnp.float32)


def _one_stream(block: Block, forward: str) -> None:
    """The forwards that carry one ``[.., d]`` residual between stages
    or into a cache refuse a rule with more streams."""
    if block.residual.streams != 1:
        raise NotImplementedError(
            f"{forward} carries one residual stream; the block has "
            f"{block.residual.streams} (stream_residual)")


# ---------------------------------------------------------------------------
# Autoregressive decode: prompt prefill with K/V export + one-token
# incremental step over a paged KV cache (servesvc/decode.py)
# ---------------------------------------------------------------------------

_DECODE_NEG = -1e30  # finite mask value: an all-masked idle slot's
# softmax degrades to uniform-over-garbage (ignored) instead of NaN


def prefill_with_kv(params: Params, tokens: jax.Array, *,
                    block: "Block | tuple[Block, ...]",
                    positions: jax.Array | None = None,
                    compute_dtype=jnp.bfloat16, return_routing: bool = False
                    ) -> tuple[jax.Array, ...]:
    """Prompt prefill: the standard causal forward (through the block's
    CONFIGURED attention — the fused pallas flash path or dense) that
    also returns what every layer keeps of a token for seeding a decode
    cache.

    tokens [b, s] int32 → (logits [b, s, vocab] float32,
    k [L, b, s, h, hd], v [L, b, s, h, hd]) with K/V in the compute
    dtype (the cache dtype); under latent projections the normed latent
    [L, b, s, kv_latent] and the rotated key [L, b, s, qk_rope] in their
    place. ``block`` as :func:`apply` takes it. Capacity routing is
    batch-shaped and the registry exports no decode for it; per-token
    routed layers route the prompt as :func:`apply` does, and
    ``return_routing`` adds their choices [routed_layers, b, s, k] as a
    fourth output and changes nothing else."""
    for bk in _per_layer(block, 1):
        _one_stream(bk, "prefill_with_kv")
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    p = _cast(params, compute_dtype)
    blocks = _per_layer(block, len(p["blocks"]))
    x = blocks[0].residual.start(_embed(p, tokens, positions))
    ks, vs, routed = [], [], []
    for bk, blk in zip(blocks, p["blocks"]):
        x, k, v = bk.attn(x, blk, return_kv=True, positions=positions)
        ks.append(k)
        vs.append(v)
        x, aux = bk.ffn(x, blk)
        _loss_of(aux, routed)
    out = (_head(p, blocks[-1].residual.end(x), norm=blocks[-1].norm),
           jnp.stack(ks), jnp.stack(vs))
    if return_routing:
        out += (_stacked(routed)["routing"],)
    return out


def decode_step(params: Params, tokens: jax.Array, positions: jax.Array,
                k_cache: jax.Array, v_cache: jax.Array,
                block_tables: jax.Array, lengths: jax.Array, *,
                ffn: "Callable | tuple[Callable, ...]",
                attn: Callable | None = None, norm: Callable = _rms_norm,
                residual: Residual = PLAIN,
                num_heads: int = 4, block_size: int = 16,
                compute_dtype=jnp.bfloat16,
                attention_kernel: str = "auto", return_aux: bool = False
                ) -> tuple[jax.Array, ...]:
    """One incremental decode step over S slots sharing one paged KV
    cache — the single compiled shape every in-flight sequence runs
    in, whatever its length.

    * ``tokens`` [S] int32 — each slot's newest token,
    * ``positions`` [S] — that token's 0-based sequence position,
    * ``k_cache``/``v_cache`` [L, N, B, h, hd] — the paged cache
      (N blocks of B positions; block 0 is the reserved null block).
      A row may be stored wider than the head
      (servesvc/kv_cache.py::stored_head_dim): the step writes and
      reads its first ``hd`` elements and leaves the rest as they are,
    * ``block_tables`` [S, P] int32 — each slot's position→block map
      (idle slots: all zeros),
    * ``lengths`` [S] — context length INCLUDING this token
      (``positions + 1``; 0 for idle slots, whose rows compute masked
      garbage the caller ignores).

    ``ffn`` is the feed-forward half of the model's block
    (:func:`make_block`), or one a layer; ``attn`` the block's
    ``decode_attn``, its attention for one token against the paged cache
    (None: the plain block's, :func:`_decode_attn`; a latent block's
    keeps ``k_cache`` [L, N, B, kv_latent] and ``v_cache`` [L, N, B,
    qk_rope], one row a token for all heads); ``norm`` the block's norm,
    for the final one, and ``residual`` its rule (one stream).
    ``return_aux`` adds a fourth output and changes nothing else: what
    the per-token routed layers said of this step's tokens, ``routing``
    [routed_layers, S, k] and ``counts`` [routed_layers, held] (empty
    without such layers).
    ``attention_kernel`` says how the plain block reads the cache
    (:func:`decode_attention_arm`): ``"auto"``, the default, lets the
    block decide by what it is handed: rows stored in whole lanes on a
    TPU go through the Pallas kernel that walks the table over the rows
    as stored (O(live context) traffic a token;
    ops/pallas_paged_attention.py), anything else through the gather of
    every table entry into a [S, context, h, hd] view (O(table width),
    and the oracle); ``"dense"`` and ``"paged"`` name an arm whatever
    the input. Both share the pinned numerics below; parity across them
    is tested in tests/test_paged_attention.py. A latent block has its
    own read (``attn``), which takes the same arms by the same rule
    through the latent form of the kernel.

    Returns (logits [S, vocab] float32, k_cache, v_cache) with this
    token's K/V written at its block/offset. Attention numerics match
    ``local_self_attention`` (f32 scores/softmax, 1/sqrt(hd) scale),
    so greedy decode through the cache reproduces the full-context
    forward (pinned in tests/test_decode.py)."""
    arm = decode_attention_arm(attention_kernel, k_cache.shape)
    p = _cast(params, compute_dtype)
    num_slots = tokens.shape[0]
    x = residual.start(_embed(p, tokens, positions))  # [S, d]
    d = x.shape[-1]
    hd = d // num_heads
    scale = 1.0 / (hd ** 0.5)
    ctx = block_tables.shape[1] * block_size
    ctx_pos = jnp.arange(ctx)
    blk_ids = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    offs = positions % block_size
    live = ctx_pos[None, :] < lengths[:, None]  # [S, ctx]
    ffns = ffn if isinstance(ffn, tuple) else (ffn,) * len(p["blocks"])
    routed = []
    for li, blk in enumerate(p["blocks"]):
        if attn is None:
            x, k_cache, v_cache = _decode_attn(
                x, blk, li, k_cache, v_cache, block_tables, lengths, blk_ids,
                offs, live, num_heads=num_heads, scale=scale, arm=arm)
        else:
            x, k_cache, v_cache = attn(
                x, blk, li, k_cache, v_cache, block_tables, positions,
                blk_ids, offs, live, lengths=lengths,
                attention_kernel=attention_kernel)
        x, aux = ffns[li](x, blk)
        _loss_of(aux, routed)
    out = (_head(p, residual.end(x), norm=norm), k_cache, v_cache)
    if return_aux:
        out += (_stacked(routed),)
    return out


def prefill_with_state(params: Params, tokens: jax.Array,
                       lengths: jax.Array, *, block: tuple[Block, ...],
                       compute_dtype=jnp.bfloat16,
                       return_routing: bool = False
                       ) -> tuple[jax.Array, ...]:
    """Prompt prefill of a model whose layers are attention or mixers
    (state-space or delta-rule), one ``block`` a layer (a mixer's has
    ``mixer_step``): the causal forward, and what every layer keeps of
    a sequence for a decode replica.

    tokens [b, s] int32, padded to a bucket; ``lengths`` [b] the real
    lengths. Attention ignores the padding (causal, logits read at
    ``lengths - 1``); a recurrence does not, so each mixer hands on the
    state after token ``lengths - 1`` and the convolution's inputs before
    ``lengths``, whatever the bucket. Returns (logits [b, 1, vocab]
    float32 of position ``lengths - 1`` alone (the one a first token is
    sampled from: the head over a whole bucket is 0.5 GB of logits at
    these widths), k and v [attention layers, b, s, kv_heads, hd] in the
    compute dtype (a latent block's rows, [attention layers, b, s,
    kv_latent] and [.., qk_rope], in their place), state [mixer layers,
    b, N, E] float32, tail [mixer layers, K - 1, b, width] in the compute
    dtype, oldest input first). Per-token routed layers route the prompt
    as :func:`apply` does; ``return_routing`` adds their choices
    [routed_layers, b, s, k] as a last output and changes nothing
    else."""
    for bk in block:
        _one_stream(bk, "prefill_with_state")
    p = _cast(params, compute_dtype)
    x = block[0].residual.start(_embed(p, tokens, jnp.arange(tokens.shape[1])))
    ks, vs, states, tails, routed = [], [], [], [], []
    for bk, blk in zip(block, p["blocks"]):
        if bk.mixer_step is None:
            x, k, v = bk.attn(x, blk, return_kv=True)
            ks.append(k)
            vs.append(v)
        else:
            x, state, tail = bk.attn(x, blk, return_kv=True, lengths=lengths)
            states.append(state)
            tails.append(tail.transpose(1, 0, 2))
        x, aux = bk.ffn(x, blk)
        _loss_of(aux, routed)
    last = jnp.take_along_axis(block[-1].residual.end(x),
                               (lengths - 1)[:, None, None], axis=1)
    out = (_head(p, last, norm=block[-1].norm),
           jnp.stack(ks), jnp.stack(vs), jnp.stack(states),
           jnp.stack(tails))
    if return_routing:
        out += (_stacked(routed)["routing"],)
    return out


def decode_step_with_state(params: Params, tokens: jax.Array,
                           positions: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, state: tuple,
                           tail: tuple, *, block: tuple[Block, ...],
                           num_heads: int, kv_heads: int,
                           block_size: int = 16,
                           compute_dtype=jnp.bfloat16,
                           attention_kernel: str = "auto",
                           return_aux: bool = False
                           ) -> tuple[jax.Array, ...]:
    """:func:`decode_step` of a model whose layers are attention or
    mixers, one ``block`` a layer. The paged cache holds the attention
    layers only, ``[attention layers, N, B, kv_heads, hd]``, read and
    written by :func:`_decode_attn` as the plain block's, or by the
    block's own ``decode_attn`` (a latent block's pair of rows a token,
    ``[attention layers, N, B, width]``). Beside it the arrays a slot
    owns whole, whatever its sequence's length, a pair a mixer layer:
    ``state[l]`` [slots, N, E] float32 and ``tail[l]`` [K - 1, slots,
    width]; a mixer layer advances its pair for the live slots (``lengths
    > 0``) and leaves an idle slot's as it was. Hand both donated: a
    layer's state is then read and written where it lies.

    Returns (logits [S, vocab] float32, k_cache, v_cache, state, tail);
    ``return_aux`` adds what the per-token routed layers said of this
    step's tokens, as :func:`decode_step` does, and changes nothing
    else."""
    arm = decode_attention_arm(attention_kernel, k_cache.shape)
    p = _cast(params, compute_dtype)
    x = block[0].residual.start(_embed(p, tokens, positions))  # [S, d]
    hd = x.shape[-1] // num_heads
    ctx_pos = jnp.arange(block_tables.shape[1] * block_size)
    blk_ids = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    offs = positions % block_size
    live = ctx_pos[None, :] < lengths[:, None]  # [S, ctx]
    stepping = lengths > 0
    state, tail = list(state), list(tail)
    attended = mixed = 0
    routed = []
    for bk, blk in zip(block, p["blocks"]):
        if bk.mixer_step is not None:
            x, state[mixed], tail[mixed] = bk.mixer_step(
                x, blk, state[mixed], tail[mixed], stepping)
            mixed += 1
        elif bk.decode_attn is not None:
            x, k_cache, v_cache = bk.decode_attn(
                x, blk, attended, k_cache, v_cache, block_tables, positions,
                blk_ids, offs, live, lengths=lengths,
                attention_kernel=attention_kernel)
            attended += 1
        else:
            x, k_cache, v_cache = _decode_attn(
                x, blk, attended, k_cache, v_cache, block_tables, lengths,
                blk_ids, offs, live, num_heads=num_heads,
                scale=1.0 / (hd ** 0.5), arm=arm,
                kv_heads=None if kv_heads == num_heads else kv_heads,
                norm=bk.norm)
            attended += 1
        x, aux = bk.ffn(x, blk)
        _loss_of(aux, routed)
    out = (_head(p, block[-1].residual.end(x), norm=block[-1].norm),
           k_cache, v_cache, tuple(state), tuple(tail))
    if return_aux:
        out += (_stacked(routed),)
    return out


def decode_attention_arm(attention_kernel: str,
                         *cache_shapes: tuple[int, ...]) -> str:
    """``"paged"`` or ``"gather"``: how a decode step asked for
    ``attention_kernel`` (``decode.attention_kernel``) reads cache
    arrays of ``cache_shapes`` (one where both are alike). ``"dense"``
    and ``"paged"`` name the arm.
    ``"auto"`` decides by what is there: where the process's devices
    are TPUs (``jax.devices()``: what its jitted step runs on), arrays
    the kernel compiles for as they lie go through it, anything else
    through the gather. Keys and values a head, [L, N, B, h, width]:
    rows stored in whole lanes (what ``kv_cache.stored_head_dim``
    answers on a TPU, and not on a CPU or for a toy head). A latent's
    one row a token for all heads, [L, N, B, width] and another width
    for its rotated key: every array's rows whole lanes AND a page whole
    tiles, because the kernel writes the token's row through its tile
    (``ops/pallas_paged_attention.py::latent_rows_as_they_lie``, the
    question the compiled kernel itself raises on)."""
    if attention_kernel not in DECODE_ATTENTION_KERNELS:
        raise ValueError(
            f"decode.attention_kernel must be one of "
            f"{', '.join(DECODE_ATTENTION_KERNELS)}, got "
            f"{attention_kernel!r}")
    if attention_kernel == "auto":
        if len(cache_shapes[0]) == 4:
            from ..ops.pallas_paged_attention import latent_rows_as_they_lie
            as_they_lie = latent_rows_as_they_lie(*cache_shapes)
        else:
            as_they_lie = all(shape[-1] % 128 == 0 for shape in cache_shapes)
        on_tpus = jax.devices()[0].platform == "tpu"
        return "paged" if as_they_lie and on_tpus else "gather"
    return "paged" if attention_kernel == "paged" else "gather"


@jax.named_scope("attention")
def _decode_attn(x, blk, li, k_cache, v_cache, block_tables, lengths,
                 blk_ids, offs, live, *, num_heads, scale, arm,
                 kv_heads=None, norm=_rms_norm):
    """One layer's attention sublayer of :func:`decode_step`: this
    token's K/V written through the block table and the context read
    back by ``arm`` (:func:`decode_attention_arm`), x + wo(attn). The
    gather arm scatters the rows (scope ``cache_write``) and gathers
    every table entry (``cache_gather``); on the paged arm the kernel
    that walks the table copies the rows into their page first, and
    ``cache_write`` holds what is left outside it: the rows cast and
    padded to the stored width. ``kv_heads`` (None: one a query head):
    the heads the cache keeps, where a group of ``num_heads / kv_heads``
    queries shares one (``wqkv`` one matrix: :func:`make_block`);
    ``norm`` the block's."""
    if kv_heads is not None:
        return _grouped_decode_attn(
            x, blk, li, k_cache, v_cache, block_tables, lengths, blk_ids,
            offs, live, num_heads=num_heads, kv_heads=kv_heads, scale=scale,
            arm=arm, norm=norm)
    num_slots, d = x.shape
    hd = d // num_heads
    ctx = live.shape[1]
    h = norm(x, blk["ln1"])
    qkv = jnp.einsum("sd,dte->ste", h, blk["wqkv"])
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # [S, d]
    if arm == "paged":
        # the kernel walks the block table over the rows as stored, the
        # cache arrays passed whole with the layer's index: no gathered
        # copy of the context, no float32 view of one, and what a token
        # reads is what its slot's live pages hold. It takes the cache
        # arrays as input and output in one buffer and puts this
        # token's rows into them itself, a copy a live slot: nothing
        # but zeros is ever written beside a head's values, so the
        # padded row is what a scatter of its first hd elements leaves
        from ..ops.pallas_paged_attention import paged_attention_write
        with jax.named_scope("cache_write"):
            beside = ((0, 0), (0, 0), (0, k_cache.shape[-1] - hd))
            kh = jnp.pad(k.reshape(num_slots, num_heads, hd).astype(
                k_cache.dtype), beside)
            vh = jnp.pad(v.reshape(num_slots, num_heads, hd).astype(
                v_cache.dtype), beside)
        o, k_cache, v_cache = paged_attention_write(
            q.reshape(num_slots, num_heads, hd), kh, vh, k_cache, v_cache,
            block_tables, lengths, layer=li, scale=scale)
    else:
        with jax.named_scope("cache_write"):
            kh = k.reshape(num_slots, num_heads, hd)
            vh = v.reshape(num_slots, num_heads, hd)
            k_cache = k_cache.at[li, blk_ids, offs, :, :hd].set(
                kh.astype(k_cache.dtype))
            v_cache = v_cache.at[li, blk_ids, offs, :, :hd].set(
                vh.astype(v_cache.dtype))
        qh = q.reshape(num_slots, num_heads, hd)
        # gather the slot's pages into one dense context view: the
        # block table IS the indirection, so this read is identical
        # for a 3-token and a 90-token sequence, at the width of the
        # table it is handed: one compiled shape a width, and the
        # decode loop picks the width (at most four) by its longest
        # live sequence (servesvc/decode.py::_table_width)
        with jax.named_scope("cache_gather"):
            kp = k_cache[li][block_tables][..., :hd].reshape(
                num_slots, ctx, num_heads, hd)
            vp = v_cache[li][block_tables][..., :hd].reshape(
                num_slots, ctx, num_heads, hd)
        scores = jnp.einsum("shd,skhd->shk", qh.astype(jnp.float32),
                            kp.astype(jnp.float32)) * scale
        scores = jnp.where(live[:, None, :], scores, _DECODE_NEG)
        w = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("shk,skhd->shd", w, vp.astype(jnp.float32))
    o = o.astype(x.dtype).reshape(num_slots, d)
    return x + o @ blk["wo"], k_cache, v_cache


def _grouped_decode_attn(x, blk, li, k_cache, v_cache, block_tables,
                         lengths, blk_ids, offs, live, *, num_heads,
                         kv_heads, scale, arm, norm):
    """:func:`_decode_attn` where ``kv_heads`` heads' rows are cached and
    ``num_heads / kv_heads`` queries read each: the same two arms, the
    kernel handed the queries of all heads against the rows of the few
    (ops/pallas_paged_attention.py), the gather one product a group."""
    num_slots, d = x.shape
    hd = d // num_heads
    group = num_heads // kv_heads
    h = norm(x, blk["ln1"]).astype(blk["wo"].dtype)
    q, k, v = jnp.split(h @ blk["wqkv"], [num_heads * hd,
                                          (num_heads + kv_heads) * hd],
                        axis=-1)
    qh = q.reshape(num_slots, num_heads, hd)
    kh, vh = (t.reshape(num_slots, kv_heads, hd) for t in (k, v))
    if arm == "paged":
        from ..ops.pallas_paged_attention import paged_attention_write
        with jax.named_scope("cache_write"):
            beside = ((0, 0), (0, 0), (0, k_cache.shape[-1] - hd))
            kh = jnp.pad(kh.astype(k_cache.dtype), beside)
            vh = jnp.pad(vh.astype(v_cache.dtype), beside)
        o, k_cache, v_cache = paged_attention_write(
            qh, kh, vh, k_cache, v_cache, block_tables, lengths, layer=li,
            scale=scale)
    else:
        with jax.named_scope("cache_write"):
            k_cache = k_cache.at[li, blk_ids, offs, :, :hd].set(
                kh.astype(k_cache.dtype))
            v_cache = v_cache.at[li, blk_ids, offs, :, :hd].set(
                vh.astype(v_cache.dtype))
        with jax.named_scope("cache_gather"):
            kp = k_cache[li][block_tables][..., :hd].reshape(
                num_slots, -1, kv_heads, hd)
            vp = v_cache[li][block_tables][..., :hd].reshape(
                num_slots, -1, kv_heads, hd)
        qg = qh.reshape(num_slots, kv_heads, group, hd)
        scores = jnp.einsum("sgqd,skgd->sgqk", qg.astype(jnp.float32),
                            kp.astype(jnp.float32)) * scale
        scores = jnp.where(live[:, None, None, :], scores, _DECODE_NEG)
        w = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("sgqk,skgd->sgqd", w, vp.astype(jnp.float32))
    o = o.astype(x.dtype).reshape(num_slots, d)
    return x + o @ blk["wo"], k_cache, v_cache


@jax.named_scope("attention")
def _latent_decode_attention(x, blk, li, k_cache, v_cache, block_tables,
                             positions, blk_ids, offs, live, *, lengths,
                             qk_nope_dim, scale, rotate, norm, out_norm,
                             attention_kernel):
    """One layer's latent attention sublayer of :func:`decode_step`
    (arXiv:2405.04434 §2.1, the absorbed form): the same function as
    :func:`latent_projections`' expanded one, reassociated so that no
    key or value a head of a cached token is ever built.

    The cache keeps one row a token for all heads: ``k_cache`` [L, N, B,
    kv_latent] the normed latent ``c``, ``v_cache`` [L, N, B, qk_rope or
    wider] the rotated key ``k_r`` in its first ``qk_rope`` elements.
    With ``wkv_b`` = ``[W_uk | W_uv]`` a head: the query's unrotated part
    goes through ``W_uk`` (``q_c[h] = q_n[h] W_uk[h]ᵀ``, scope
    ``latent_absorb``), scores are ``(q_c[h]·c_t + q_r[h]·k_r,t)·scale``
    over every live row, the softmax in float32, the weighted sum of
    latents ``o_c[h]`` (the weights rounded to the cache's dtype once)
    goes through ``W_uv`` (``latent_absorb``), then ``wo``, the output's
    norm where the block has one, and the residual. A block with ``wq``
    projects its query at full rank; one with ``w_hgate`` gates the
    output a head before ``wo`` (:func:`_head_gated`).

    The rows are read by the arm :func:`decode_attention_arm` answers
    for both arrays. ``"paged"``: one call of the latent kernel
    (ops/pallas_paged_attention.py::paged_latent_attention_write) over
    the two arrays whole, which puts the token's rows where the table
    says and walks the live pages; ``cache_write`` holds what is left
    outside it, the rows cast and padded to the stored widths.
    ``"gather"`` (the oracle, a CPU, ``dense``): the rows scattered
    (``cache_write``), the table's width gathered once for all heads
    (``cache_gather``)."""
    arm = decode_attention_arm(attention_kernel, k_cache.shape,
                               v_cache.shape)
    num_slots = x.shape[0]
    latent_dim = blk["wkv_b"].shape[0]
    rope_dim = blk["wkv_a"].shape[1] - latent_dim
    ctx = live.shape[1]
    h = norm(x, blk["ln1"]).astype(blk["wo"].dtype)
    if "wq" in blk:
        q = jnp.einsum("sd,dhe->she", h, blk["wq"])
    else:
        q = jnp.einsum("sr,rhe->she", norm(h @ blk["wq_a"], blk["q_norm"]),
                       blk["wq_b"])
    kv_a = h @ blk["wkv_a"]
    c = norm(kv_a[:, :latent_dim], blk["kv_norm"])
    # a slot's token is its own sequence's: positions [S] turn rows [S]
    q_r = rotate(q[..., qk_nope_dim:], positions)
    k_r = rotate(kv_a[:, None, latent_dim:], positions)[:, 0]

    def absorbed_query():
        with jax.named_scope("latent_absorb"):
            return jnp.einsum("shn,rhn->shr", q[..., :qk_nope_dim],
                              blk["wkv_b"][..., :qk_nope_dim])

    if arm == "paged":
        # the kernel takes the two cache arrays whole, as input and
        # output in one buffer each, with the layer's index: it puts
        # this token's rows into them, then walks the slot's live pages;
        # no slice of a layer, no gathered view of the table's width, no
        # float32 scores of it. Nothing but zeros is ever written beside
        # a row's values, so the padded row is what a scatter of its
        # first elements leaves
        from ..ops.pallas_paged_attention import paged_latent_attention_write
        q_c = absorbed_query()
        with jax.named_scope("cache_write"):
            new_c, new_kr = (
                jnp.pad(row.astype(cache.dtype),
                        ((0, 0), (0, cache.shape[-1] - row.shape[-1])))
                for row, cache in ((c, k_cache), (k_r, v_cache)))
        o_c, k_cache, v_cache = paged_latent_attention_write(
            q_c, q_r, new_c, new_kr, k_cache, v_cache, block_tables, lengths,
            layer=li, scale=scale)
        o_c = o_c.astype(q_c.dtype)
    else:
        with jax.named_scope("cache_write"):
            k_cache = k_cache.at[li, blk_ids, offs, :latent_dim].set(
                c.astype(k_cache.dtype))
            v_cache = v_cache.at[li, blk_ids, offs, :rope_dim].set(
                k_r.astype(v_cache.dtype))
        q_c = absorbed_query()
        with jax.named_scope("cache_gather"):
            cs = k_cache[li][block_tables][..., :latent_dim].reshape(
                num_slots, ctx, latent_dim)
            krs = v_cache[li][block_tables][..., :rope_dim].reshape(
                num_slots, ctx, rope_dim)
        scores = (jnp.einsum("shr,skr->shk", q_c, cs,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("she,ske->shk", q_r, krs,
                               preferred_element_type=jnp.float32)) * scale
        scores = jnp.where(live[:, None, :], scores, _DECODE_NEG)
        w = jax.nn.softmax(scores, axis=-1)
        o_c = jnp.einsum("shk,skr->shr", w.astype(cs.dtype), cs)
    with jax.named_scope("latent_absorb"):
        o = jnp.einsum("shr,rhv->shv", o_c, blk["wkv_b"][..., qk_nope_dim:])
    o = _head_gated(o, h, blk)
    proj = o.astype(blk["wo"].dtype).reshape(num_slots, -1) @ blk["wo"]
    if out_norm:
        proj = norm(proj, blk["ln1_out"])
    return x + proj, k_cache, v_cache


# ---------------------------------------------------------------------------
# Pipeline parallelism: layer-stacked params + microbatched apply
# ---------------------------------------------------------------------------

def stack_block_params(params: Params) -> Params:
    """Convert ``blocks`` from a list of per-layer dicts to one dict of
    leaves stacked on a leading layer dim — the shardable layout for a
    mesh ``stage`` axis (layer dim split across stages)."""
    blocks = params["blocks"]
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *blocks)
    return {**{k: v for k, v in params.items() if k != "blocks"},
            "blocks": stacked}


def pp_param_partition_specs(stage_axis: str,
                             model_axis: str | None = None,
                             num_experts: int = 0,
                             expert_axis: str | None = None) -> Params:
    """Stacked-layout specs: block leaves sharded on the layer dim over
    the stage axis; embeddings/norms replicated (their gradients psum
    over stages via the AD transpose of the replication).

    ``model_axis`` composes Megatron TP inside each stage: the same
    column/row dims as :func:`param_partition_specs`, one position to
    the right of the stacked layer dim (PP outermost, TP within the
    stage's layer slice). ``expert_axis`` (MoE, num_experts > 0)
    additionally shards each block's expert dim — PP picks the layer,
    EP the expert, TP the expert's hidden slice."""
    P = PartitionSpec
    m = model_axis  # None → replicated on the TP dims
    if num_experts > 0:
        e = expert_axis
        blk = {"ln1": {"scale": P(stage_axis)},
               "wqkv": P(stage_axis, None, None, m),
               "wo": P(stage_axis, m, None),
               "ln2": {"scale": P(stage_axis)},
               "router": P(stage_axis),
               "w1": P(stage_axis, e, None, m),
               "w2": P(stage_axis, e, m, None)}
    else:
        blk = {"ln1": {"scale": P(stage_axis)},
               "wqkv": P(stage_axis, None, None, m),
               "wo": P(stage_axis, m, None),
               "ln2": {"scale": P(stage_axis)},
               "w1": P(stage_axis, None, m),
               "w2": P(stage_axis, m, None)}
    return {"embed": P(), "pos": P(), "blocks": blk,
            "final_norm": {"scale": P()}}


def apply_pp(params: Params, tokens: jax.Array, *, block: Block,
             stage_axis: str, num_microbatches: int,
             positions: jax.Array | None = None,
             compute_dtype=jnp.bfloat16, remat: bool = False,
             return_aux: bool = False) -> jax.Array:
    """Pipeline-parallel forward (inside shard_map, params in the
    stacked layout with block leaves sharded over ``stage_axis``).

    The batch is split into ``num_microbatches``; each stage scans its
    local layer slice; activations flow via the microbatch pipeline
    (ops/pipeline.py). Embedding/head run replicated on every stage —
    outputs are stage-replicated logits, so loss code is unchanged.

    A block built with a ``model_axis`` composes tensor parallelism
    INSIDE each stage: block params additionally carry Megatron
    column/row shards (``pp_param_partition_specs(stage, model)``), each
    rank computes its head/MLP slice, and the row-parallel psums inside
    the block reassemble activations per tick — PP outermost, TP within.

    Sequence parallelism composes through the block's attention +
    ``positions``: a seq-sharded attention (ring/Ulysses over the seq
    axis) and this shard's global positions; every (stage, seq) device
    runs the same tick schedule, so the attention collectives stay
    lockstep inside the pipeline scan — bubbles included.

    A mixture-of-experts feed-forward (optionally expert-sharded)
    composes too: each tick's MoE calls run the
    grouped dispatch on that microbatch's tokens, all-to-alls lockstep
    across stages since every device runs every tick. Token groups nest
    inside sequence rows (ops/moe.py), so routing capacity, drops, and
    the per-group aux are IDENTICAL for every microbatch count — the
    aux is linear in per-group contributions, so each real tick's aux
    simply accumulates (pipeline_apply ``with_stats``, bubbles masked)
    and the mean over microbatches equals the dense full-batch value
    exactly. ``return_aux`` returns it (under PP×SP×EP the
    feed-forward's ``stats_axes`` name the seq axis, which each call's
    aux additionally pmeans over).
    """
    from ..ops.pipeline import pipeline_apply

    _one_stream(block, "apply_pp")
    b, s = tokens.shape
    if b % num_microbatches != 0:
        raise ValueError(f"batch {b} not divisible by "
                         f"num_microbatches={num_microbatches}")
    if positions is None:
        positions = jnp.arange(s)
    p = _cast(params, compute_dtype)
    x = _embed(p, tokens, positions)
    d = x.shape[-1]
    mb = b // num_microbatches
    micro = x.reshape(num_microbatches, mb, s, d)

    moe = "router" in p["blocks"]

    def stage_fn(act):
        def layer(carry, blk):
            out, aux_l = block.ffn(block.attn(carry, blk), blk)
            return out, (aux_l if moe else None)

        if remat:
            layer = jax.checkpoint(layer)
        out, aux_layers = lax.scan(layer, act, p["blocks"])
        # aux_layers: per-LOCAL-layer mean-per-group aux [L_local] (MoE)
        return (out, aux_layers) if moe else out

    if moe:
        out, aux_layers = pipeline_apply(stage_fn, micro, stage_axis,
                                         with_stats=True)
        # pipeline_apply averaged each layer's aux over the real ticks
        # (= over microbatches — exact, the aux is per-group linear);
        # stages hold disjoint layers, so one psum totals the model
        aux = lax.psum(jnp.sum(aux_layers.astype(jnp.float32)), stage_axis)
    else:
        out = pipeline_apply(stage_fn, micro, stage_axis)
        aux = jnp.zeros((), jnp.float32)
    logits = _head(p, out.reshape(b, s, d))
    return (logits, {"loss": aux}) if return_aux else logits


def stack_block_params_chunked(params: Params, num_stages: int,
                               num_chunks: int) -> Params:
    """Chunk-interleaved stacking for the 1F1B schedule: like
    :func:`stack_block_params`, but layer ORDER is permuted so that the
    contiguous stage shard of device ``d`` holds global chunks
    ``{d, S+d, …, (v-1)·S+d}`` (slot-major: [slot j, layers of chunk
    j·S+d]) — the placement the interleaved schedule's ring traversal
    requires (ops/pipeline.py). Sharding specs are unchanged
    (:func:`pp_param_partition_specs`); only the order differs.
    """
    blocks = params["blocks"]
    L = len(blocks)
    if L % (num_stages * num_chunks):
        raise ValueError(
            f"num_layers={L} not divisible by stages×chunks="
            f"{num_stages}×{num_chunks}")
    per = L // (num_stages * num_chunks)
    order = [c * per + l
             for d in range(num_stages)
             for j in range(num_chunks)
             for c in [j * num_stages + d]
             for l in range(per)]
    stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                           *[blocks[i] for i in order])
    return {**{k: v for k, v in params.items() if k != "blocks"},
            "blocks": stacked}


def grads_pp_1f1b(params: Params, tokens: jax.Array, labels: jax.Array, *,
                  block: Block, stage_axis: str, num_microbatches: int,
                  num_chunks: int, seq_axis: str | None = None,
                  aux_weight: float = 0.0,
                  compute_dtype=jnp.bfloat16):
    """Fused interleaved-1F1B training step body (inside shard_map,
    params in the chunk-interleaved stacked layout of
    :func:`stack_block_params_chunked`).

    Unlike :func:`apply_pp` + AD (the GPipe path), forward and backward
    chunk-works interleave inside ONE scan (ops/pipeline.py:
    pipeline_1f1b_grads), shrinking the pipeline bubble by the chunk
    factor; the backward recomputes each chunk from its saved input
    (rematerialization built in). Embedding/positions run replicated
    outside the pipeline; their gradients combine the lookup transpose
    (via the banked input-cotangents) with the tied head's
    contribution. Returns (loss, train_acc, grads) with ``grads``
    matching the parameter layout.

    A block built with a ``model_axis`` composes Megatron TP inside
    every chunk and ``seq_axis`` composes SP (the block's seq-sharded
    attention + cross-shard partial loss). Chunk-internal collectives execute
    INSIDE the engine's device-varying ``lax.switch`` branches; that is
    safe exactly when the collective's runtime rendezvous is
    GROUP-LOCAL and its participant group shares one stage coordinate
    (so every participant takes the same branch each tick): psum /
    all_to_all over the model, seq, or expert axes qualify. It is NOT
    safe for ``lax.ppermute`` — XLA lowers collective-permute with a
    GLOBAL participant list, so devices on other stages (in other
    branches) would be waited on forever (measured deadlock on the CPU
    backend's rendezvous). Hence SP under this schedule requires the
    all-to-all (Ulysses) attention — the registry refuses ring — and
    the cross-shard target shift runs OUTSIDE the engine, below.
    Stage-axis collectives stay forbidden in branches entirely (the
    engine's lockstep ppermutes handle stage transfer).

    Under SP the returned loss/accuracy/grads are this seq shard's
    PARTIALS (normalized so a psum over the seq axis reassembles the
    exact dense values — same contract as the GPipe PP×SP path); the
    caller performs that psum.

    A mixture-of-experts feed-forward composes: the
    per-row-group aux (ops/moe.py) is LINEAR across chunks and
    microbatches, so each chunk returns its summed layer aux, the
    engine accumulates it over forward works and seeds each backward
    chunk's aux output with the constant weight — no cross-chunk
    statistics. The returned loss includes the aux term.
    """
    from ..ops.pipeline import pipeline_1f1b_grads

    _one_stream(block, "grads_pp_1f1b")
    b, s_loc = tokens.shape
    if b % num_microbatches != 0:
        raise ValueError(f"batch {b} not divisible by "
                         f"num_microbatches={num_microbatches}")
    n_seq = lax.axis_size(seq_axis) if seq_axis else 1
    p = _cast(params, compute_dtype)
    d = p["embed"].shape[-1]
    if seq_axis is not None:
        positions = lax.axis_index(seq_axis) * s_loc + jnp.arange(s_loc)
    else:
        positions = jnp.arange(s_loc)
    mb = b // num_microbatches
    M = num_microbatches

    def emb_fn(embed, pos):
        return _embed({"embed": embed, "pos": pos}, tokens,
                      positions).reshape(M, mb, s_loc, d)

    micro, emb_vjp = jax.vjp(emb_fn, p["embed"], p["pos"])

    L_local = jax.tree.leaves(p["blocks"])[0].shape[0]
    per = L_local // num_chunks
    chunk_params = jax.tree.map(
        lambda a: a.reshape((num_chunks, per) + a.shape[1:]), p["blocks"])

    moe = "router" in p["blocks"]

    def chunk_fn(slot_params, act):
        def layer(carry, blk):
            out, aux_l = block.ffn(block.attn(carry, blk), blk)
            return out, (aux_l if moe else None)
        out, aux_layers = lax.scan(layer, act, slot_params)
        return (out, jnp.sum(aux_layers)) if moe else out

    labels_mb = labels.reshape(M, mb, s_loc)
    head_params = {"embed": p["embed"], "final_norm": p["final_norm"]}

    if seq_axis is None:
        def head_fn(hp, y, m):
            logits = _head(hp, y)
            lab = lax.dynamic_index_in_dim(labels_mb, m, 0, keepdims=False)
            return loss_fn(logits, lab), accuracy(logits, lab)
    else:
        # the SP partial loss (same math as parallel.api.make_sp_loss):
        # shard j's last-token target lives on shard j+1. The fetching
        # ppermute must run OUT HERE, unconditionally on every device —
        # collective-permute rendezvouses globally and would deadlock
        # inside the engine's stage-varying branches (docstring above).
        s_global = s_loc * n_seq
        seq_perm = [((j + 1) % n_seq, j) for j in range(n_seq)]
        nxt = lax.ppermute(labels[:, :1], seq_axis, seq_perm)
        tgt_mb = jnp.concatenate([labels[:, 1:], nxt],
                                 axis=1).astype(jnp.int32).reshape(M, mb,
                                                                   s_loc)

        def head_fn(hp, y, m):
            logits = _head(hp, y)
            tgt = lax.dynamic_index_in_dim(tgt_mb, m, 0, keepdims=False)
            # this microbatch's global valid-token count normalizes the
            # partials (shared kernel with the GPipe/DP SP loss path)
            return sp_partial_token_loss(logits, tgt, positions, s_global,
                                         mb * (s_global - 1))

    # The backward aux seed is the FULL weight: the aux primal is the
    # pmean over (expert, seq) of per-shard contributions, and the
    # pmean's transpose (cotangent/n per shard) composed with the
    # caller's psum-over-seq of grads already yields exactly
    # aux_weight·d(aux)/dθ — pre-dividing the SEED (as the loss VALUE
    # must be, below) would undercount aux gradients by n_seq.
    if moe:
        losses, accs, dinputs, dchunk, dhead, aux_sum = pipeline_1f1b_grads(
            chunk_fn, head_fn, chunk_params, head_params, micro,
            stage_axis, num_chunks, with_aux=True,
            aux_cotangent=aux_weight)
    else:
        losses, accs, dinputs, dchunk, dhead = pipeline_1f1b_grads(
            chunk_fn, head_fn, chunk_params, head_params, micro,
            stage_axis, num_chunks)
    # the engine seeds every microbatch's loss with cotangent 1.0 (sum
    # convention); the step's loss is the MEAN over microbatches
    scale = 1.0 / M
    dinputs = dinputs * jnp.asarray(scale, dinputs.dtype)
    dchunk = jax.tree.map(lambda a: a * jnp.asarray(scale, a.dtype), dchunk)
    dhead = jax.tree.map(lambda a: a * jnp.asarray(scale, a.dtype), dhead)

    demb_lookup, dpos = emb_vjp(dinputs.astype(micro.dtype))
    grads = {
        "embed": demb_lookup + dhead["embed"],  # lookup + tied head
        "pos": dpos,
        "blocks": jax.tree.map(
            lambda a: a.reshape((L_local,) + a.shape[2:]), dchunk),
        "final_norm": dhead["final_norm"],
    }
    # the engine differentiates the compute-dtype cast of the params;
    # apply the cast's transpose so grads match the master param dtypes
    grads = jax.tree.map(lambda g, p0: g.astype(p0.dtype), grads, params)
    loss = jnp.mean(losses)
    if moe:
        # the VALUE term pre-divides by n_seq (the aux is already the
        # full pmean'd value on every shard; the caller's psum over the
        # seq axis reassembles exactly one copy — make_sp_loss's
        # aux/n_seq convention)
        loss = loss + (aux_weight / n_seq) * aux_sum * scale
    return loss, jnp.mean(accs), grads


def apply_pp_1f1b(params: Params, tokens: jax.Array, *, block: Block,
                  stage_axis: str, num_microbatches: int, num_chunks: int,
                  compute_dtype=jnp.bfloat16) -> jax.Array:
    """Forward-only apply for the chunk-interleaved layout (eval under
    schedule="1f1b"): the chunked ring (ops/pipeline.py:
    pipeline_chunked_forward) with embedding/head outside, same
    contract as :func:`apply_pp`. The block's Megatron TP and MoE
    expert sharding compose inside each chunk — the
    forward ring computes every chunk unconditionally (``jnp.where``
    select, not a branch), so the TP psums / EP all-to-alls run
    lockstep on every device every tick."""
    from ..ops.pipeline import pipeline_chunked_forward

    _one_stream(block, "apply_pp_1f1b")
    b, s = tokens.shape
    if b % num_microbatches != 0:
        raise ValueError(f"batch {b} not divisible by "
                         f"num_microbatches={num_microbatches}")
    p = _cast(params, compute_dtype)
    x = _embed(p, tokens, jnp.arange(s))
    d = x.shape[-1]
    mb = b // num_microbatches
    micro = x.reshape(num_microbatches, mb, s, d)

    L_local = jax.tree.leaves(p["blocks"])[0].shape[0]
    per = L_local // num_chunks
    chunk_params = jax.tree.map(
        lambda a: a.reshape((num_chunks, per) + a.shape[1:]), p["blocks"])

    def chunk_fn(act, slot):
        from ..ops.pipeline import _index_pytree
        slot_params = _index_pytree(chunk_params, slot)

        def layer(carry, blk):
            out, _aux = block.ffn(block.attn(carry, blk), blk)
            return out, None
        out, _ = lax.scan(layer, act, slot_params)
        return out

    out = pipeline_chunked_forward(chunk_fn, micro, stage_axis, num_chunks)
    return _head(p, out.reshape(b, s, d))


def sp_partial_token_loss(logits: jax.Array, tgt: jax.Array,
                          positions: jax.Array, s_global: int,
                          total: int) -> tuple[jax.Array, jax.Array]:
    """The sequence-parallel partial next-token (loss, accuracy) kernel
    — the ONE implementation both SP consumers share (the train step's
    ``make_sp_loss`` in parallel/api.py and the 1F1B engine's seed-tick
    head above), so the masking/normalization conventions cannot drift
    between schedules.

    Args: ``logits`` [b, s_loc, V] this shard's logits; ``tgt``
    [b, s_loc] the already-shifted global targets (the caller fetches
    the cross-shard column); ``positions`` this shard's global
    positions; ``total`` the GLOBAL valid-token count the partial sums
    normalize by — psum over the seq axis of the returned pair equals
    the dense ``loss_fn``/``accuracy`` exactly.
    """
    w = (positions < s_global - 1).astype(jnp.float32)[None, :]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    correct = (jnp.argmax(logp, axis=-1) == tgt).astype(jnp.float32)
    return jnp.sum(nll * w) / total, jnp.sum(correct * w) / total


@jax.named_scope("loss")
def loss_fn(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Next-token mean xent. ``labels`` are the input tokens; targets
    are labels shifted left (last position dropped)."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    tgt = labels[:, 1:].astype(jnp.int32)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


@jax.named_scope("loss")
def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    pred = jnp.argmax(logits[:, :-1], axis=-1)
    return jnp.mean((pred == labels[:, 1:]).astype(jnp.float32))
