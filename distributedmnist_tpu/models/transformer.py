"""A compact causal-LM transformer — the long-context model family.

Not a reference-parity model (the reference has no attention anywhere,
SURVEY §5.7); this exists so the framework's sequence-parallel path —
ring attention over the mesh's ``seq`` axis (ops/ring_attention.py) —
has a first-class consumer, and so the aggregation disciplines can be
exercised on a transformer-shaped allreduce payload.

Pure init/apply over a param pytree, pre-norm blocks, learned
positional embeddings, weight-tied LM head.

What a layer computes is decided in ONE place, :func:`make_block`: which
attention (``local_self_attention`` single-device, the flash kernel, or
a closure over ring/Ulysses attention under a seq-sharded shard_map),
which feed-forward (the dense ReLU product, or a routed mixture of
experts), and under which mesh axes (Megatron-style tensor parallelism
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
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from .cnn import truncated_normal_init
from ..ops.ring_attention import local_self_attention

Params = dict[str, Any]


def init(key: jax.Array, vocab_size: int = 256, model_dim: int = 128,
         num_heads: int = 4, num_layers: int = 2,
         max_seq_len: int = 512, num_experts: int = 0) -> Params:
    """``num_experts > 0`` makes every block's FFN a top-1-routed
    mixture of experts (ops/moe.py) instead of a dense MLP."""
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


def _rms_norm(x: jax.Array, p: Params) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6) * p["scale"]).astype(x.dtype)


class Block(NamedTuple):
    """What one transformer layer computes, as its two pre-norm
    sublayers. :func:`make_block` builds it once; every forward below
    takes one and decides only its schedule."""
    # attn(x, blk, return_kv=False) -> x, or (x, k, v) for the prefill
    attn: Callable[..., Any]
    # ffn(x, blk) -> (x, aux)
    ffn: Callable[[jax.Array, Params], tuple[jax.Array, jax.Array]]


def _dense_ffn(h: jax.Array, blk: Params, *,
               model_axis: str | None) -> tuple[jax.Array, jax.Array]:
    mlp = jax.nn.relu(h @ blk["w1"]) @ blk["w2"]
    aux = jnp.zeros((), jnp.float32)
    if model_axis:
        mlp = lax.psum(mlp, model_axis)
    return mlp, aux


def moe_feed_forward(**settings) -> Callable:
    """A mixture-of-experts feed-forward for :func:`make_block`:
    ``ops.moe.moe_ffn`` over a block's ``router``/``w1``/``w2`` with its
    settings bound (``num_experts``, ``capacity_factor``,
    ``router_top_k``, ``num_groups``, and the mesh axes: ``expert_axis``
    the experts are sharded over, ``tp_axis`` every expert's hidden dim
    is split over — one fused psum covers both — and ``stats_axes``, the
    extra token-sharding axes (the seq axis under SP×MoE) the
    load-balance statistics average over, so the aux loss is the
    full-token value replicated on every shard)."""
    from ..ops.moe import moe_ffn

    def feed_forward(h, blk):
        return moe_ffn(h, blk["router"], blk["w1"], blk["w2"], **settings)
    return feed_forward


def make_block(*, num_heads: int, attention_fn: Callable | None = None,
               model_axis: str | None = None,
               feed_forward: Callable | None = None) -> Block:
    """The one place a layer's kind is decided: which attention, which
    feed-forward, under which mesh axes.

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
    its product sums it itself, as ``moe_ffn`` does) and a scalar
    auxiliary loss. None is the dense ReLU product over ``w1``/``w2``;
    :func:`moe_feed_forward` is the routed one. Its ``aux`` is the mean
    per-group load-balance loss of this block's routing (linear across
    blocks/ticks/shards: forwards sum over layers and average over
    microbatches), kept by a forward only where the block's parameters
    hold a ``router``.
    """
    attention = attention_fn or local_self_attention
    if feed_forward is None:
        feed_forward = functools.partial(_dense_ffn, model_axis=model_axis)

    @jax.named_scope("attention")
    def attn(x: jax.Array, blk: Params, return_kv: bool = False):
        """Pre-norm attention sublayer: x + wo(attn(qkv(ln1(x)))).

        ``return_kv``: also return this layer's K/V in the [b, s, h, hd]
        residual layout (a free reshape) — what the decode prefill
        scatters into the paged KV cache."""
        b, d = x.shape[0], x.shape[-1]
        # read here, inside the shard_map, not when the block is built
        m = lax.axis_size(model_axis) if model_axis else 1
        if num_heads % m != 0:
            raise ValueError(f"num_heads={num_heads} not divisible by "
                             f"model-parallel size {m}")
        h_local, hd = num_heads // m, d // num_heads
        h = _rms_norm(x, blk["ln1"])
        qkv = jnp.einsum("bsd,dte->bste", h, blk["wqkv"])  # e = d/m
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if getattr(attention, "layout", "bhsd") == "bshd":
            # kernel reads the residual layout directly ([b, s, h, hd] is
            # a free reshape of [b, s, e]) — no head transpose on either
            # side. At the flash bench shape the transposes a bhsd
            # attention forces cost ~20 ms/step, 2.5× the kernel itself.
            # (A fully fused qkv-packed kernel input was also measured:
            # the strided k/v lane reads cost MORE than the slice copies
            # they save.)
            bshd = lambda t: t.reshape(b, -1, h_local, hd)
            o = attention(bshd(q), bshd(k), bshd(v)).reshape(
                b, -1, h_local * hd)
        else:
            def heads(t):
                return t.reshape(b, -1, h_local, hd).transpose(0, 2, 1, 3)

            o = attention(heads(q), heads(k), heads(v))
            o = o.transpose(0, 2, 1, 3).reshape(b, -1, h_local * hd)
        proj = o @ blk["wo"]  # row-parallel: partial sum of the full d
        if model_axis:
            proj = lax.psum(proj, model_axis)
        out = x + proj
        if return_kv:
            return (out, k.reshape(b, -1, h_local, hd),
                    v.reshape(b, -1, h_local, hd))
        return out

    @jax.named_scope("ffn")
    def ffn(x: jax.Array, blk: Params) -> tuple[jax.Array, jax.Array]:
        """Pre-norm FFN sublayer: x + feed_forward(ln2(x)), aux."""
        mlp, aux = feed_forward(_rms_norm(x, blk["ln2"]), blk)
        return x + mlp, aux

    return Block(attn, ffn)


def apply(params: Params, tokens: jax.Array, *, block: Block,
          positions: jax.Array | None = None,
          compute_dtype=jnp.bfloat16, remat: bool = False,
          remat_policy: str = "full",
          return_aux: bool = False) -> jax.Array:
    """tokens [batch, seq] int32 → logits [batch, seq, vocab] float32
    through ``block`` (:func:`make_block`), layer after layer.

    ``positions`` (global positions of this shard's tokens) must be
    passed when the sequence is sharded; defaults to arange(seq).
    ``return_aux``: also return the summed load-balancing aux loss.
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    p = _cast(params, compute_dtype)
    x = _embed(p, tokens, positions)

    def layer(x, blk):
        return block.ffn(block.attn(x, blk), blk)

    if remat:
        if remat_policy == "save_attn":
            # Selective remat: the FFN sublayer (and its norms)
            # recomputes in the backward, but the attention sublayer
            # stays OUTSIDE the checkpoint, so the flash kernel's
            # custom-vjp residuals (q/k/v/out/lse) remain resident and
            # the backward never re-runs the attention forward. Costs
            # O(b·s·d) extra bytes per layer over full remat; at the
            # S=8192 long-context bench it buys 1.14x tokens/sec.
            ffn_ckpt = jax.checkpoint(block.ffn)

            def layer(x, blk):  # noqa: F811 — policy-selected body
                return ffn_ckpt(block.attn(x, blk), blk)
        elif remat_policy == "full":
            # trade one extra forward per block for O(layer-boundary)
            # activation memory — the long-sequence HBM lever
            layer = jax.checkpoint(layer)
        else:
            raise ValueError(f"unknown remat_policy {remat_policy!r} "
                             "(expected 'full' or 'save_attn')")
    aux_total = jnp.zeros((), jnp.float32)
    for blk in p["blocks"]:
        x, aux = layer(x, blk)
        aux_total = aux_total + aux
    logits = _head(p, x)
    return (logits, aux_total) if return_aux else logits


# Device scopes (obsv/spans.py SCOPES): every HLO operation carries the
# scope it was traced under in its op_name, backward and recomputed
# operations included, so a profiler trace splits a step by layer.

@jax.named_scope("cast")
def _cast(params: Params, compute_dtype) -> Params:
    """The stored weights in the compute dtype, once per program."""
    return jax.tree.map(lambda a: a.astype(compute_dtype), params)


@jax.named_scope("embed")
def _embed(p: Params, tokens: jax.Array, positions: jax.Array) -> jax.Array:
    return p["embed"][tokens] + p["pos"][positions]


@jax.named_scope("head")
def _head(p: Params, x: jax.Array) -> jax.Array:
    """Final norm and the tied head: [..., d] → float32 logits."""
    x = _rms_norm(x, p["final_norm"])
    return (x @ p["embed"].T).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Autoregressive decode: prompt prefill with K/V export + one-token
# incremental step over a paged KV cache (servesvc/decode.py)
# ---------------------------------------------------------------------------

_DECODE_NEG = -1e30  # finite mask value: an all-masked idle slot's
# softmax degrades to uniform-over-garbage (ignored) instead of NaN


def prefill_with_kv(params: Params, tokens: jax.Array, *, block: Block,
                    positions: jax.Array | None = None,
                    compute_dtype=jnp.bfloat16
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Prompt prefill: the standard causal forward (through the block's
    CONFIGURED attention — the fused pallas flash path or dense) that
    also returns every layer's K/V for seeding a decode cache.

    tokens [b, s] int32 → (logits [b, s, vocab] float32,
    k [L, b, s, h, hd], v [L, b, s, h, hd]) with K/V in the compute
    dtype (the cache dtype). Dense-FFN models only (MoE routing is
    batch-shaped; the registry never exports decode for it)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    p = _cast(params, compute_dtype)
    x = _embed(p, tokens, positions)
    ks, vs = [], []
    for blk in p["blocks"]:
        x, k, v = block.attn(x, blk, return_kv=True)
        ks.append(k)
        vs.append(v)
        x, _ = block.ffn(x, blk)
    return _head(p, x), jnp.stack(ks), jnp.stack(vs)


def decode_step(params: Params, tokens: jax.Array, positions: jax.Array,
                k_cache: jax.Array, v_cache: jax.Array,
                block_tables: jax.Array, lengths: jax.Array, *,
                ffn: Callable, num_heads: int = 4, block_size: int = 16,
                compute_dtype=jnp.bfloat16,
                attention_kernel: str = "dense"
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One incremental decode step over S slots sharing one paged KV
    cache — the single compiled shape every in-flight sequence runs
    in, whatever its length.

    * ``tokens`` [S] int32 — each slot's newest token,
    * ``positions`` [S] — that token's 0-based sequence position,
    * ``k_cache``/``v_cache`` [L, N, B, h, hd] — the paged cache
      (N blocks of B positions; block 0 is the reserved null block),
    * ``block_tables`` [S, P] int32 — each slot's position→block map
      (idle slots: all zeros),
    * ``lengths`` [S] — context length INCLUDING this token
      (``positions + 1``; 0 for idle slots, whose rows compute masked
      garbage the caller ignores).

    ``ffn`` is the feed-forward half of the model's block
    (:func:`make_block`); the attention half is this step's own
    (:func:`_decode_attn`: one token against the paged cache).
    ``attention_kernel`` selects the cache read: ``"dense"`` gathers
    every table entry into a [S, max_context, h, hd] view (the oracle
    path — O(max context) traffic per token), ``"paged"`` runs the
    fused Pallas kernel that walks the table in-kernel (O(actual
    context); see ops/pallas_paged_attention.py). Both share the
    pinned numerics below; parity across them is tested in
    tests/test_paged_attention.py.

    Returns (logits [S, vocab] float32, k_cache, v_cache) with this
    token's K/V written at its block/offset. Attention numerics match
    ``local_self_attention`` (f32 scores/softmax, 1/sqrt(hd) scale),
    so greedy decode through the cache reproduces the full-context
    forward (pinned in tests/test_decode.py)."""
    if attention_kernel not in ("dense", "paged"):
        raise ValueError(
            f"decode.attention_kernel must be 'dense' or 'paged', "
            f"got {attention_kernel!r}")
    p = _cast(params, compute_dtype)
    num_slots = tokens.shape[0]
    x = _embed(p, tokens, positions)  # [S, d]
    d = x.shape[-1]
    hd = d // num_heads
    scale = 1.0 / (hd ** 0.5)
    ctx = block_tables.shape[1] * block_size
    ctx_pos = jnp.arange(ctx)
    blk_ids = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1)[:, 0]
    offs = positions % block_size
    live = ctx_pos[None, :] < lengths[:, None]  # [S, ctx]
    for li, blk in enumerate(p["blocks"]):
        x, k_cache, v_cache = _decode_attn(
            x, blk, li, k_cache, v_cache, block_tables, lengths, blk_ids,
            offs, live, num_heads=num_heads, scale=scale,
            attention_kernel=attention_kernel)
        x, _ = ffn(x, blk)
    return _head(p, x), k_cache, v_cache


@jax.named_scope("attention")
def _decode_attn(x, blk, li, k_cache, v_cache, block_tables, lengths,
                 blk_ids, offs, live, *, num_heads, scale,
                 attention_kernel):
    """One layer's attention sublayer of :func:`decode_step`: this
    token's K/V written through the block table (scope ``cache_write``),
    the context read back (``cache_gather`` on the dense arm; the paged
    kernel walks the table itself), x + wo(attn)."""
    num_slots, d = x.shape
    hd = d // num_heads
    ctx = live.shape[1]
    h = _rms_norm(x, blk["ln1"])
    qkv = jnp.einsum("sd,dte->ste", h, blk["wqkv"])
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # [S, d]
    with jax.named_scope("cache_write"):
        kh = k.reshape(num_slots, num_heads, hd)
        vh = v.reshape(num_slots, num_heads, hd)
        k_cache = k_cache.at[li, blk_ids, offs].set(
            kh.astype(k_cache.dtype))
        v_cache = v_cache.at[li, blk_ids, offs].set(
            vh.astype(v_cache.dtype))
    qh = q.reshape(num_slots, num_heads, hd)
    if attention_kernel == "paged":
        # fused path: the kernel walks the block table itself, so
        # per-token traffic is O(actual context) — no dense view
        from ..ops.pallas_paged_attention import paged_attention
        o = paged_attention(qh, k_cache[li], v_cache[li],
                            block_tables, lengths, scale=scale)
    else:
        # gather the slot's pages into one dense context view: the
        # block table IS the indirection, so this read is identical
        # for a 3-token and a 90-token sequence, at the width of the
        # table it is handed: one compiled shape a width, and the
        # decode loop picks the width (at most four) by its longest
        # live sequence (servesvc/decode.py::_table_width)
        with jax.named_scope("cache_gather"):
            kp = k_cache[li][block_tables].reshape(
                num_slots, ctx, num_heads, hd)
            vp = v_cache[li][block_tables].reshape(
                num_slots, ctx, num_heads, hd)
        scores = jnp.einsum("shd,skhd->shk", qh.astype(jnp.float32),
                            kp.astype(jnp.float32)) * scale
        scores = jnp.where(live[:, None, :], scores, _DECODE_NEG)
        w = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("shk,skhd->shd", w, vp.astype(jnp.float32))
    o = o.astype(x.dtype).reshape(num_slots, d)
    return x + o @ blk["wo"], k_cache, v_cache


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
    return (logits, aux) if return_aux else logits


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
