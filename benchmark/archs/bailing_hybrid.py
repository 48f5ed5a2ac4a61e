"""Architecture ``bailing_hybrid``: the decoder of Ling-3.0-flash
(``model_type`` ``bailing_hybrid``), as one chip of a deployment that
shares each layer over 16 chips holds it. The keys are those of its
``config.json``. The plain reference, written from the published
equations (Kimi Delta Attention: Kimi Linear, arXiv:2510.26692 §3, and the
``KimiDeltaAttention`` layer of flash-linear-attention; latent attention:
DeepSeek-V2, arXiv:2405.04434 §2.1; the group-limited router: DeepSeek-V3,
arXiv:2412.19437 §2.1.2) and importing nothing of the program: float32,
matrix products at ``highest`` precision, the delta rule token by token.
It runs beside the 6.5 GB of bfloat16 weights it checks, so a weight is
cast where it is used, the experts one at a time inside a scan, and
nothing makes a float32 copy of the tree.

Equations (``d = hidden_size``, ``H`` heads of ``D = head_dim``; every
norm an RMSNorm with a scale, epsilon ``rms_norm_eps`` inside the root;
no bias anywhere; one residual stream, a norm before each sublayer):

* **A layer**: ``x <- x + Mix_i(N1 x)``, then ``x <- x + F_i(N2 x)``.
  Layer ``i`` is latent attention where ``(i + 1) % layer_group_size ==
  0``, else Kimi Delta Attention. After the last layer a final norm and
  the untied head.
* **KDA** (``num_kv_heads_for_linear_attn`` 0: keys and values a head
  each): ``[q, k, v] = silu(conv(h W_qkv))``, a causal depth-wise
  convolution of ``short_conv_kernel_size`` taps on each of the ``3 H D``
  channels; ``q`` and ``k`` divided by their L2 norm a head
  (``use_qk_norm``); ``g = kda_lower_bound · sigmoid(exp(A_log) · (h W_f +
  dt_bias))`` a head a key channel, in ``(kda_lower_bound, 0)``
  (``kda_safe_gate``); ``β = sigmoid(h W_β)`` a head; the state ``S`` [D,
  D] a head: ``S_t = Diag(e^g) S_{t−1} + β k (v − S_{t−1}ᵀ Diag(e^g) k)ᵀ``,
  ``o_t = S_tᵀ q · D^−½``; output ``(rmsnorm_head(o) ⊙ sigmoid(h W_og))
  W_o`` (``group_norm_size`` 1: the norm a head, one scale of ``D``).
* **Latent attention**: ``[q_n | q_r] = h W_q`` a head (``q_lora_rank``
  null: full rank); ``[c | k_r] = h W_dkv``, ``c = Nkv(c)``; ``q_r`` and
  ``k_r`` rotated at the token's position (plain rotary, base
  ``rope_theta``; ``k_r`` one row for all heads); ``[k_n | v] = c W_ukv`` a
  head; scores ``(q_n·k_n + q_r·k_r) / sqrt(nope + rope)``, causal
  softmax, ``o_h = Σ w v``, then ``o_h <- sigmoid(h w_h) · o_h`` a head
  (``gated_attention_proj_granularity_type`` ``head_wise``), output
  ``concat(o) W_o``.
* **F.** The first ``first_k_dense_replace`` layers: ``W_d(silu(h W_g) ⊙ h
  W_u)``. The others: ``Shared(h) + Σ_{e in top-k} g_e Expert_e(h)`` over
  the chosen experts THIS CHIP HOLDS, each a gated unit
  ``moe_intermediate_size`` wide. ``s = sigmoid(h W_r)`` over all the
  layer's experts; selection on ``s + bias``: the experts lie in
  ``n_group`` equal groups of consecutive ids, a group's score is the sum
  of its two best, the ``topk_group`` best groups stay and the
  ``num_experts_per_tok`` best experts inside them are taken; ``g_e =
  routed_scaling_factor · s_e / (Σ_chosen s + 1e-20)``, from ``s`` without
  the bias.

Departures and what the config does not say (the configuration file says
the same under ``assumed`` and ``departures``):

* the order of the layer types, the form of the bounded gate, ``W_og`` at
  full rank a channel: ASSUMED, each in the file's ``assumed``;
* the rotated columns of a head's query and key are its last
  ``qk_rope_head_dim``, their pairs laid out as halves (column ``i`` turns
  with column ``i + rope/2``) where the published code interleaves them
  (``rope_interleave``): with seeded weights a permutation of columns;
* the next-token module is not loaded; the gated units' clamp
  (``expert_swiglu_limit_list``, ``share_expert_swiglu_limit_list``) is 0
  in every layer the cut keeps and nothing of it is written here;
* weights are stored and served in bfloat16; this file reads them as
  float32 values.

The parameter tree is data, in the program's layout: ``embed`` [V, d],
``head`` [d, V], ``final_norm``, ``blocks``: every layer ``ln1``, ``ln2``;
a KDA layer ``w_qkv`` [d, 3 H D] (q, k, v in that order, a head's ``D``
columns together), ``conv_w`` [K, 3 H D] (oldest tap first), ``w_f`` [d, H
D], ``a_log`` [H], ``dt_bias`` [H D], ``w_beta`` [d, H], ``w_og`` [d, H D],
``o_norm``, ``wo`` [H D, d]; a latent layer ``wq`` [d, H, nope + rope],
``wkv_a`` [d, r_kv + rope], ``kv_norm``, ``wkv_b`` [r_kv, H, nope + v],
``w_hgate`` [d, H], ``wo``; then ``w_gate``/``w_up``/``w_down``, or
``router`` [d, experts], ``router_bias`` [experts], ``experts`` and
``shared``. The routed layers of ``routing`` are the trunk's, in order,
``k = num_experts_per_tok``.
"""

from __future__ import annotations

import itertools

import numpy as np

import jax
import jax.numpy as jnp

from benchmark.lib.cell import BenchmarkError

_QUERY_BLOCK = 512


def routed_experts(config: dict) -> int:
    """Experts a layer routes over: the published count, whatever share
    of them this chip holds."""
    return int(config["published"]["num_experts"])


def _held(config: dict) -> tuple[int, int]:
    return (int(config["assumed"]["first_held_expert"]),
            int(config["num_experts"]))


def attends(config: dict, layer: int) -> bool:
    return (layer + 1) % config["layer_group_size"] == 0


def layer_counts(config: dict) -> tuple[int, int]:
    """(latent-attention layers, KDA layers)."""
    a = sum(attends(config, i) for i in range(config["num_hidden_layers"]))
    return a, config["num_hidden_layers"] - a


def routed_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def _program_model_keys() -> set[str]:
    """The keys the program's ``model`` section takes in this checkout:
    the one thing this file asks of the program, and only so that a
    program that predates this architecture is refused in the
    benchmark's own words. The equations below import nothing."""
    import dataclasses

    from distributedmnist_tpu.core.config import ModelConfig
    return {f.name for f in dataclasses.fields(ModelConfig)}


def model_section(config: dict) -> dict:
    """The program's ``model`` section: sizes only."""
    c = config
    kept = c["num_hidden_layers"]
    if (c["hidden_act"] != "silu" or c["tie_word_embeddings"]
            or c["q_lora_rank"] is not None or c.get("rope_scaling")
            or c["score_function"] != "sigmoid"
            or c["topk_method"] != "noaux_tc" or not c["norm_topk_prob"]
            or not c["moe_router_enable_expert_bias"]
            or c["num_nextn_predict_layers"] != 0
            or c["num_kv_heads_for_linear_attn"] != 0
            or c["num_key_value_heads"] != c["num_attention_heads"]
            or c["group_norm_size"] != 1 or not c["linear_silu"]
            or not c["use_qk_norm"] or not c["kda_safe_gate"]
            or not c["no_kda_lora"] or c["use_kda_lora"]
            or c["gated_attention_proj_granularity_type"] != "head_wise"
            or c["use_mla_nope"] or c["use_bias"] or c["use_qkv_bias"]
            or c["use_nGPT"] or c["value_norm"] or c["up_proj_norm"]
            or c["scale_router_input"]
            or c["moe_shared_expert_intermediate_size"]
            != c["moe_intermediate_size"]
            or not (c["head_dim"] == c["v_head_dim"] == c["qk_nope_head_dim"])
            or any(c["expert_swiglu_limit_list"][:kept])
            or any(c["share_expert_swiglu_limit_list"][:kept])):
        raise BenchmarkError(
            "the program serves this family with SiLU gated units that no "
            "kept layer clamps, an untied head, a full-rank query, plain "
            "rotary positions, sigmoid scores under a group limit with an "
            "expert bias and renormalised gates, one key and value a KDA "
            "head, the bounded gate at full rank, a norm a head, a "
            "head-wise gate on latent attention and no next-token module "
            "loaded; this configuration asks for something else")
    first, count = _held(c)
    section = {
        "name": "transformer", "model_dim": c["hidden_size"],
        "num_heads": c["num_attention_heads"],
        "num_layers": c["num_hidden_layers"],
        "seq_len": c["assumed"]["seq_len"],
        "vocab_size": c["vocab_size"],
        "q_latent_dim": 0, "kv_latent_dim": c["kv_lora_rank"],
        "qk_nope_dim": c["qk_nope_head_dim"],
        "qk_rope_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "rope_theta": float(c["rope_theta"]),
        "attn_head_gate": True,
        "ffn_dim": c["intermediate_size"],
        "routed_experts": routed_experts(c),
        "held_experts": count, "first_held_expert": first,
        "experts_per_token": c["num_experts_per_tok"],
        "shared_experts": c["num_shared_experts"],
        "expert_ffn_dim": c["moe_intermediate_size"],
        "routed_scaling": float(c["routed_scaling_factor"]),
        "router_groups": c["n_group"],
        "router_topk_groups": c["topk_group"],
        "router_bias_rate": c["assumed"]["router_bias_rate"],
        "dense_layers": c["first_k_dense_replace"],
        "kda_head_dim": c["head_dim"],
        "kda_conv": c["short_conv_kernel_size"],
        "kda_lower_bound": float(c["kda_lower_bound"]),
        "attn_layer_period": c["layer_group_size"],
        "attn_layer_offset": c["layer_group_size"] - 1,
        "norm_eps": c["rms_norm_eps"],
        **c.get("model_assumed", {})}
    unknown = sorted(set(section) - _program_model_keys())
    if unknown:
        raise BenchmarkError(
            "the program in this checkout cannot run this architecture: "
            f"its model section has no {', '.join(unknown)}")
    return section


# -- the equations -----------------------------------------------------------

def _w(a):
    """A stored weight as float32 values, where it is used."""
    return jnp.asarray(a, jnp.float32)


def _norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _w(p["scale"])


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(h, blk, config):
    """``h`` [S, d], normed → the KDA sublayer's output [S, d]: the delta
    rule token by token from an empty state."""
    s = h.shape[0]
    heads, dim = config["num_attention_heads"], config["head_dim"]
    taps = config["short_conv_kernel_size"]
    lower, eps = float(config["kda_lower_bound"]), config["rms_norm_eps"]
    qkv = h @ _w(blk["w_qkv"])
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    conv = sum(_w(blk["conv_w"])[j] * padded[j:j + s] for j in range(taps))
    q, k, v = (x.reshape(s, heads, dim)
               for x in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q, k = _l2(q), _l2(k)
    arg = (h @ _w(blk["w_f"]) + _w(blk["dt_bias"])).reshape(s, heads, dim)
    g = lower * jax.nn.sigmoid(jnp.exp(_w(blk["a_log"]))[:, None] * arg)
    beta = jax.nn.sigmoid(h @ _w(blk["w_beta"]))

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, :, None] * state          # [H, D, D]
        read = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + jnp.einsum("hk,hv->hkv", k_t,
                                   b_t[:, None] * (v_t - read))
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dim, dim)),
                        (q, k, v, g, beta))
    o = _norm(o * dim ** -0.5, blk["o_norm"], eps)
    gate = jax.nn.sigmoid(h @ _w(blk["w_og"]))
    return (o.reshape(s, -1) * gate) @ _w(blk["wo"])


def _rope_tables(config: dict, positions: int):
    """cos, sin [positions, rope/2]: plain rotary, no scaling."""
    dim, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.arange(positions, dtype=np.float64)[:, None] * freq[None]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def _rope(x, cos, sin):
    """``x`` [S, heads, rope], pairs laid out as halves."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _latent_attention(h, blk, config):
    """``h`` [S, d], normed → the attention sublayer's output [S, d]."""
    s = h.shape[0]
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    latent, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    cos, sin = _rope_tables(config, s)
    q = jnp.einsum("sd,dhe->she", h, _w(blk["wq"]))
    kv_a = h @ _w(blk["wkv_a"])
    kv = jnp.einsum("sr,rhe->she",
                    _norm(kv_a[:, :latent], blk["kv_norm"], eps),
                    _w(blk["wkv_b"]))
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    k_rope = _rope(kv_a[:, None, latent:], cos, sin)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads, rope))], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    # the largest block of queries that divides the sequence
    block = max(b for b in range(1, min(_QUERY_BLOCK, s) + 1) if s % b == 0)
    key_pos = jnp.arange(s)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhe,khe->hqk", qb, k) * scale
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khe->qhe", probs, v)

    o = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(s, heads, -1)
    o = o * jax.nn.sigmoid(h @ _w(blk["w_hgate"]))[:, :, None]
    return o.reshape(s, -1) @ _w(blk["wo"])


def _gated_unit(x, w):
    return ((jax.nn.silu(x @ _w(w["w_gate"])) * (x @ _w(w["w_up"])))
            @ _w(w["w_down"]))


def _group_scores(biased, config):
    """``biased`` [S, experts] → a group's score [S, n_group]: the sum of
    its two best."""
    grouped = biased.reshape(biased.shape[0], config["n_group"], -1)
    return jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)


def _within(biased, groups, config):
    """``biased`` with every expert outside ``groups`` [S, n_group] (bool)
    at ``-inf``."""
    s = biased.shape[0]
    grouped = biased.reshape(s, config["n_group"], -1)
    return jnp.where(groups[:, :, None], grouped, -jnp.inf).reshape(s, -1)


def _routed(h, blk, config, ids):
    """The routed feed-forward of one sequence ``h`` [S, d] (normed), as
    the chip that holds experts ``_held(config)`` computes it. ``ids`` [S,
    k] forces the experts; None lets the reference choose. Returns the
    output, the ids used and the slack of ``ids`` [S].

    The slack knows the group limit: it is the least amount by which
    scores would have to be off for the forced experts to be the right
    choice, in units of the spread of the position's biased scores over
    the experts. The forced experts lie in some groups ``P``; every set
    ``M`` of ``topk_group`` groups that holds ``P`` could have been the
    program's, at a cost: the larger of how far ``M``'s worst group lies
    below the reference's ``topk_group``-th best group, and how far the
    worst forced expert lies below the ``k``-th best inside ``M``. The
    slack is the cheapest ``M``'s cost (all ``C(n_group, topk_group)``
    are tried: 70 at 4 of 8). So a group that was a near tie is followed
    at the tie's size, whether or not an expert was taken from it, and
    one that was not is counted at its distance; experts in more than
    ``topk_group`` groups cost the distance of the worst of them. 0
    exactly where the two sets are equal."""
    k, keep = config["num_experts_per_tok"], config["topk_group"]
    n_group = config["n_group"]
    first, count = _held(config)
    score = jax.nn.sigmoid(h @ _w(blk["router"]))
    biased = score + _w(blk["router_bias"])
    group_score = _group_scores(biased, config)
    best_groups, own_groups = jax.lax.top_k(group_score, keep)
    own = jax.lax.top_k(_within(
        biased, jnp.any(own_groups[:, :, None] == jnp.arange(n_group), 1),
        config), k)[1]
    if ids is None:
        ids = own
    gates = jnp.take_along_axis(score, ids, axis=-1)
    gates = (gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
             * config["routed_scaling_factor"])
    # [S, held]: the gate of each held expert at each position
    share = jnp.sum((ids[..., None] == first + jnp.arange(count))
                    * gates[..., None], axis=1)

    def add_expert(acc, expert):
        w, g = expert            # one expert's weights, cast in here
        return acc + g[:, None] * _gated_unit(h, w), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (blk["experts"], share.T))
    if "shared" in blk:
        out = out + _gated_unit(h, blk["shared"])
    # the slack of the forced ids
    per_group = score.shape[1] // n_group
    forced = jnp.any((ids // per_group)[:, :, None] == jnp.arange(n_group),
                     axis=1)                               # [S, n_group]
    below = jnp.maximum(best_groups[:, keep - 1:keep] - group_score, 0.0)
    worst = jnp.min(jnp.take_along_axis(biased, ids, axis=-1), axis=-1)
    # every set of `keep` groups, [sets, n_group]
    member = jnp.asarray(np.array([
        np.isin(np.arange(n_group), chosen) for chosen in
        itertools.combinations(range(n_group), keep)]))
    holds = jnp.all(member[None] | ~forced[:, None], axis=-1)   # [S, sets]
    group_cost = jnp.max(jnp.where(member[None], below[:, None], 0.0), -1)
    grouped = biased.reshape(biased.shape[0], 1, n_group, per_group)
    kth = jax.lax.top_k(jnp.where(
        member[None, :, :, None], grouped, -jnp.inf).reshape(
            biased.shape[0], member.shape[0], -1), k)[0][..., k - 1]
    cost = jnp.maximum(group_cost, jnp.maximum(kth - worst[:, None], 0.0))
    cheapest = jnp.min(jnp.where(holds, cost, jnp.inf), axis=-1)
    # more groups than a token may take: the worst of them, at least
    spilled = jnp.max(jnp.where(forced, below, 0.0), axis=-1)
    slack = jnp.where(jnp.any(holds, axis=-1), cheapest,
                      spilled) / jnp.std(biased, axis=-1)
    return out, ids, slack


def _layer(x, blk, config, ids):
    """One layer on one sequence ``x`` [S, d]. Returns the residual, and
    from a routed layer the slack of ``ids`` (else None)."""
    eps = config["rms_norm_eps"]
    h = _norm(x, blk["ln1"], eps)
    x = x + (_kda(h, blk, config) if "w_qkv" in blk
             else _latent_attention(h, blk, config))
    h = _norm(x, blk["ln2"], eps)
    if "router" not in blk:
        return x + _gated_unit(h, blk), None
    f, _, slack = _routed(h, blk, config, ids)
    return x + f, slack


def _trunk(params, seq, config, routing):
    """One sequence [S] → the trunk's output before its final norm [S,
    d], and the slack [routed_layers, S] of ``routing`` [routed_layers,
    S, k] (zeros for None)."""
    x = _w(params["embed"][seq])
    slacks = []
    for blk in params["blocks"]:
        ids = (routing[len(slacks)]
               if routing is not None and "router" in blk else None)
        x, slack = _layer(x, blk, config, ids)
        if slack is not None:
            slacks.append(slack)
    return x, jnp.stack(slacks)


def _per_sequence(params, tokens, config, routing, fn):
    with jax.default_matmul_precision("highest"):
        return [fn(*_trunk(params, seq, config,
                           None if routing is None else routing[:, b]), seq)
                for b, seq in enumerate(tokens)]


def logits(params, tokens, config: dict, last: int | None = None,
           routing=None):
    """Logits [B, S or last, V] of the trunk through the untied head."""
    def head(h, _, seq):
        h = _norm(h, params["final_norm"], config["rms_norm_eps"])
        return (h if last is None else h[-last:]) @ _w(params["head"])
    return jnp.stack(_per_sequence(params, tokens, config, routing, head))


def loss(params, tokens, config: dict, routing=None):
    """Mean next-token cross-entropy over every position but the last."""
    def nll(h, _, seq):
        h = _norm(h, params["final_norm"], config["rms_norm_eps"])
        logp = jax.nn.log_softmax(h[:-1] @ _w(params["head"]), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, seq[1:, None], axis=-1))
    total = sum(_per_sequence(params, tokens, config, routing, nll))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def routing_slack(params, tokens, config: dict, routing):
    """Float32 [routed_layers, batch, seq]: see ``lib/cell.py`` (and
    :func:`_routed` for what it knows of the group limit)."""
    return jnp.stack(_per_sequence(params, tokens, config, routing,
                                   lambda h, slack, seq: slack), axis=1)


# -- the model's own counts (lib/flops.py's rules: a multiply-add is two
# operations; recomputation, padding, casts and copies never count) ----------

def kda_params(c: dict) -> int:
    """One KDA mixer: the three projections and their convolutions, the
    decay's, beta's, the output gate's, the head norm's scale and the
    output projection."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    e = h * c["head_dim"]
    return (d * 3 * e + c["short_conv_kernel_size"] * 3 * e + d * e + h + e
            + d * h + d * e + c["head_dim"] + e * d)


def latent_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * h * qk + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"]
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + d * h + h * c["v_head_dim"] * d)


def unit_params(c: dict) -> int:
    """One expert, routed or shared: three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def param_count(c: dict) -> int:
    """Every stored parameter of the share this chip holds."""
    d = c["hidden_size"]
    attn, kda = layer_counts(c)
    dense, routed = c["first_k_dense_replace"], routed_layers(c)
    return (2 * c["vocab_size"] * d + d
            + attn * latent_params(c) + kda * kda_params(c)
            + (attn + kda) * 2 * d
            + dense * 3 * d * c["intermediate_size"]
            + routed * (d * routed_experts(c) + routed_experts(c)
                        + (c["num_shared_experts"] + c["num_experts"])
                        * unit_params(c)))


def _attention_flops_per_token(c: dict, context: float) -> float:
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return 2.0 * context * c["num_attention_heads"] * (qk + c["v_head_dim"])


def _delta_rule_flops_per_token(c: dict) -> float:
    """One KDA layer's recurrence for one token: per element of a head's
    matrix the decay's product, the two reads' multiply-adds and the
    update's."""
    return c["num_attention_heads"] * c["head_dim"] ** 2 * 7.0


def forward_flops_per_token(c: dict, context: float) -> float:
    """One token through the layers and the head this chip holds, in
    expectation under even routing."""
    d = c["hidden_size"]
    attn, kda = layer_counts(c)
    dense, routed = c["first_k_dense_replace"], routed_layers(c)
    held = c["num_experts"] / routed_experts(c)
    matmul = (attn * latent_params(c) + kda * kda_params(c)
              + dense * 3 * d * c["intermediate_size"]
              + routed * (d * routed_experts(c)
                          + (c["num_shared_experts"]
                             + c["num_experts_per_tok"] * held)
                          * unit_params(c))
              + d * c["vocab_size"])
    return (2.0 * matmul + attn * _attention_flops_per_token(c, context)
            + kda * _delta_rule_flops_per_token(c))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward plus backward (twice the forward), causal, per token. No
    cell trains this configuration; the count is the interface's."""
    return 3.0 * forward_flops_per_token(config, (seq_len + 1) / 2.0)


def attention_train_flops_per_token(config: dict, seq_len: int) -> float:
    return (3.0 * layer_counts(config)[0]
            * _attention_flops_per_token(config, (seq_len + 1) / 2.0))


def expected_experts_touched(config: dict, tokens: int) -> float:
    """How many of the held experts of one layer take at least one of
    ``tokens`` tokens, in expectation UNDER UNIFORM ROUTING (the group
    limit keeps the symmetry: an expert is one of a token's ``k`` of ``E``
    with probability ``k / E``). Seeded random weights route close to
    uniformly; a trained router does not (``decode_experts_touched_p50``
    has the count that was)."""
    miss = 1.0 - config["num_experts_per_tok"] / routed_experts(config)
    return config["num_experts"] * (1.0 - miss ** tokens)


def kda_state_bytes_per_step(config: dict, live_slots: int,
                             state_bytes: int = 4) -> float:
    """Bytes the KDA layers' matrix state costs one decode step at the
    least: every live slot's state, ``H · D · D`` float32 a layer, read
    once and written once, and nothing else (not the convolutions' tail,
    not the token's vectors, not idle slots): the same count whatever
    implements the update."""
    c = config
    a_layer = c["num_attention_heads"] * c["head_dim"] ** 2 * state_bytes
    return 2.0 * live_slots * layer_counts(c)[1] * a_layer


def decode_bytes_per_step(config: dict, contexts: list[int],
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step must move. Every matrix outside the routed
    experts once (the mixers', the dense layer's, the routers', the shared
    experts', the head); the embedding's live rows; of the routed experts
    the held ones that some live token takes
    (:func:`expected_experts_touched` a routed layer); the KDA layers'
    state of each live sequence both ways
    (:func:`kda_state_bytes_per_step`) and its convolution tail both ways
    (``(K − 1) · 3 H D`` values a layer); and what the latent layers' cache
    keeps of each live sequence's tokens, ``kv_lora_rank +
    qk_rope_head_dim`` values a token a layer, however wide the device
    stores the row."""
    c = config
    d = c["hidden_size"]
    attn, kda = layer_counts(c)
    dense, routed = c["first_k_dense_replace"], routed_layers(c)
    live = len(contexts)
    weights = (attn * latent_params(c) + kda * kda_params(c)
               + dense * 3 * d * c["intermediate_size"]
               + routed * (d * routed_experts(c) + routed_experts(c)
                           + c["num_shared_experts"] * unit_params(c)
                           + expected_experts_touched(c, live)
                           * unit_params(c))
               + live * d + d * c["vocab_size"]) * weight_bytes
    tail = (2.0 * live * kda * (c["short_conv_kernel_size"] - 1) * 3
            * c["num_attention_heads"] * c["head_dim"] * kv_bytes)
    row = (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * kv_bytes
    return float(weights + kda_state_bytes_per_step(c, live) + tail
                 + sum(contexts) * attn * row)
