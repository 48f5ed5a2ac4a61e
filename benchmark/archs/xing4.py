"""Architecture ``xing4``: the decoder of Xing4.0-29B-A4B (``model_type``
``xing4_0``), as one chip of its training job holds it. The keys are
those of its ``config.json``. The plain reference, written from the
published equations and importing nothing of the program: float32,
matrix products at ``highest`` precision, attention a block of queries
at a time and experts one at a time (it runs beside the run's 7 GB of
state; the scores of one sequence at once would be 2 GB).

Equations (``d = hidden_size``, ``H`` heads, ``n = hc_mult`` streams; all
norms RMSNorm with a scale, epsilon ``rms_norm_eps`` inside the root):

* **Latent attention** (DeepSeek-V2, arXiv:2405.04434 §2.1; DeepSeek-V3's
  modelling code). ``c_q = norm(h W_qa)``; ``[q_n | q_r] = c_q W_qb`` a
  head; ``[c_kv | k_r] = h W_kva``, ``c_kv = norm(c_kv)``; ``[k_n | v] =
  c_kv W_kvb`` a head; ``q = [q_n | rope(q_r)]``, ``k = [k_n | rope(k_r)]``,
  ``k_r`` one row shared by all heads; causal ``softmax(q kᵀ · scale) v``
  with ``scale = (qk width)^-½ · m²``, ``m = 0.1 · mscale_all_dim ·
  ln(factor) + 1``; output ``[H · v] W_o``. Rotary: YaRN (arXiv:2309.00071)
  as DeepSeek-V3 computes it: per frequency a blend of ``f`` and ``f /
  factor`` by the linear ramp between the two correction dimensions,
  cosine and sine scaled by ``mscale``'s factor over ``mscale_all_dim``'s.
* **Feed-forward.** Dense layers: ``(silu(h W_g) ⊙ h W_u) W_d``. Routed
  layers (DeepSeek-V3, arXiv:2412.19437 §2.1.2, ``noaux_tc``, one group):
  ``s = sigmoid(h W_r)`` over all ``n_routed_experts`` of the layer; the
  ``num_experts_per_tok`` largest of ``s + b`` are chosen; gates ``s_i /
  (Σ_chosen s + 1e-20) · routed_scaling_factor``; the output is ``Σ g_i
  E_i(h)`` over the chosen experts THIS CHIP HOLDS plus the shared
  expert ``S(h)``, each a gated unit ``moe_intermediate_size`` wide. ``b``
  is data here: no gradient of the loss reaches it (training moves it by
  each expert's load, ``assumed.router_bias_rate``).
* **The residual** (manifold-constrained hyper-connections,
  arXiv:2512.24880, on hyper-connections, arXiv:2409.19606), one set of
  maps a sublayer. The residual of a token is ``X`` [n, d]. ``x̃ =
  rmsnorm(vec X)`` without a scale; ``a = α_pre · x̃ φ_pre + β_pre`` [n],
  ``p = α_post · x̃ φ_post + β_post`` [n], ``R = α_res · mat(x̃ φ_res) +
  β_res`` [n, n]; ``H_pre = σ(a)``, ``H_post = 2σ(p)``, ``H_res =
  SK(clip(R, clamp))``, ``SK``: ``exp``, then ``hc_sinkhorn_iters`` times
  rows and then columns divided by their sums plus ``hc_eps``. The
  sublayer ``F`` reads ``u = H_pre X``; ``X' = H_res X + H_postᵀ F(norm
  u)``. ``X_0`` is ``n`` copies of the embedding; the final norm reads
  the sum of the streams.
* **The next-next-token module** (arXiv:2412.19437 §2.2, depth 1): ``h'_i
  = [norm(h_i) | norm(Emb(t_{i+1}))] W_p`` over the trunk's output before
  its final norm, one routed layer (streams started and ended as above),
  a final norm of its own, the shared head: it predicts ``t_{i+2}``.
  :func:`train_loss` adds its mean cross-entropy at
  ``assumed.nextn_loss_weight``; :func:`logits` and :func:`loss` are the
  trunk's.

The parameter tree is data, in the program's layout: ``embed`` [V, d],
``head`` [d, V], ``final_norm``, ``blocks`` (``ln1``, ``ln2``; ``wq_a``,
``q_norm``, ``wq_b`` [r_q, H, qk], ``wkv_a`` [d, r_kv + rope], ``kv_norm``,
``wkv_b`` [r_kv, H, nope + v], ``wo``; ``mix1`` and ``mix2``, each ``phi``
[n·d, n + n + n²] (columns: read, write, stream-to-stream row-major),
``alpha`` [3], ``beta`` [n + n + n²]; ``w_gate``/``w_up``/``w_down``, or
``router`` [d, experts], ``router_bias``, ``experts`` and ``shared``) and
``nextn`` (``norm_h``, ``norm_e``, ``proj`` [2d, d], ``block``,
``final_norm``). The rotated columns are the last ``qk_rope_head_dim`` of a
head, their pairs laid out as halves; the routed layers of ``routing``
are the trunk's, in order, ``k = num_experts_per_tok``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.cell import BenchmarkError

_QUERY_BLOCK = 512


def routed_experts(config: dict) -> int:
    """Experts a layer routes over: the published count, whatever share
    of them this chip holds."""
    return int(config["published"]["n_routed_experts"])


def _held(config: dict) -> tuple[int, int]:
    return (int(config["assumed"]["first_held_expert"]),
            int(config["n_routed_experts"]))


def routed_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def model_section(config: dict) -> dict:
    """The program's ``model`` section: sizes only."""
    rope = config["rope_scaling"]
    if (config["scoring_func"] != "sigmoid" or config["n_group"] != 1
            or config["topk_group"] != 1 or config["topk_method"] != "noaux_tc"
            or not config["norm_topk_prob"] or config["hidden_act"] != "silu"
            or config["attention_bias"] or config["tie_word_embeddings"]
            or rope["type"] != "yarn" or config["moe_layer_freq"] != 1
            or config["num_key_value_heads"] != config["num_attention_heads"]
            or config["num_nextn_predict_layers"] != 1
            or (config["mhc_h_res_clamp_min"]
                != -config["mhc_h_res_clamp_max"])):
        raise BenchmarkError(
            "the program runs sigmoid noaux_tc routing in one group with "
            "renormalised gates, SiLU gated units, YaRN rotary, an untied "
            "head, one next-next-token module and a symmetric clamp; this "
            "configuration asks for something else")
    first, count = _held(config)
    return {
        "name": "transformer", "model_dim": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "num_layers": config["num_hidden_layers"],
        "seq_len": config["assumed"]["seq_len"],
        "vocab_size": config["vocab_size"],
        "q_latent_dim": config["q_lora_rank"],
        "kv_latent_dim": config["kv_lora_rank"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "rope_factor": float(rope["factor"]),
        "rope_original_len": rope["original_max_position_embeddings"],
        "rope_beta_fast": float(rope["beta_fast"]),
        "rope_beta_slow": float(rope["beta_slow"]),
        "rope_mscale": float(rope["mscale"]),
        "rope_mscale_all_dim": float(rope["mscale_all_dim"]),
        "ffn_dim": config["intermediate_size"],
        "routed_experts": routed_experts(config),
        "held_experts": count, "first_held_expert": first,
        "experts_per_token": config["num_experts_per_tok"],
        "shared_experts": config["n_shared_experts"],
        "expert_ffn_dim": config["moe_intermediate_size"],
        "routed_scaling": float(config["routed_scaling_factor"]),
        "router_bias_rate": config["assumed"]["router_bias_rate"],
        "dense_layers": config["first_k_dense_replace"],
        "residual_streams": config["hc_mult"],
        "sinkhorn_iters": config["hc_sinkhorn_iters"],
        "residual_eps": config["hc_eps"],
        "residual_clamp": float(config["mhc_h_res_clamp_max"]),
        "nextn_layers": config["num_nextn_predict_layers"],
        "nextn_loss_weight": config["assumed"]["nextn_loss_weight"],
        **config.get("model_assumed", {})}


# -- the equations -----------------------------------------------------------

def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * p["scale"]


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_tables(config: dict, positions: int):
    """cos, sin [positions, rope/2] under YaRN."""
    dim, theta = config["qk_rope_head_dim"], float(config["rope_theta"])
    rope = config["rope_scaling"]
    factor, original = rope["factor"], rope["original_max_position_embeddings"]
    freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = freq / factor * ramp + freq * (1 - ramp)
    scale = (_yarn_mscale(factor, rope["mscale"])
             / _yarn_mscale(factor, rope["mscale_all_dim"]))
    angle = np.arange(positions, dtype=np.float64)[:, None] * inv_freq[None]
    return (jnp.asarray(np.cos(angle) * scale, jnp.float32),
            jnp.asarray(np.sin(angle) * scale, jnp.float32))


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
    eps = config["rms_norm_eps"]
    cos, sin = _rope_tables(config, s)
    q = jnp.einsum("sr,rhe->she", _norm(h @ blk["wq_a"], blk["q_norm"], eps),
                   blk["wq_b"])
    kv_a = h @ blk["wkv_a"]
    kv = jnp.einsum("sr,rhe->she",
                    _norm(kv_a[:, :-rope], blk["kv_norm"], eps), blk["wkv_b"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    k_rope = _rope(kv_a[:, None, -rope:], cos, sin)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (s, heads, rope))], -1)
    v = kv[..., nope:]
    m = _yarn_mscale(config["rope_scaling"]["factor"],
                     config["rope_scaling"]["mscale_all_dim"])
    scale = (nope + rope) ** -0.5 * m * m
    # the largest block of queries that divides the sequence (the module
    # runs one position short of a power of two)
    block = max(b for b in range(1, min(_QUERY_BLOCK, s) + 1) if s % b == 0)
    key_pos = jnp.arange(s)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhe,khe->hqk", qb, k) * scale
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khe->qhe", probs, v)

    o = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(s, -1)
    return o @ blk["wo"]


def _gated_unit(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _routed(h, blk, config, ids):
    """The routed feed-forward of one sequence ``h`` [S, d] (normed), as
    the chip that holds experts ``_held(config)`` computes it. ``ids`` [S,
    k] forces the experts; None lets the reference choose. Returns the
    output, the ids used and the slack of ``ids`` [S] in units of the
    spread of the position's selection scores."""
    k = config["num_experts_per_tok"]
    first, count = _held(config)
    score = jax.nn.sigmoid(h @ blk["router"])
    select = score + blk["router_bias"]
    own_best, own = jax.lax.top_k(select, k)
    if ids is None:
        ids = own
    gates = jnp.take_along_axis(score, ids, axis=-1)
    gates = (gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
             * config["routed_scaling_factor"])
    # [S, held]: the gate of each held expert at each position
    share = jnp.sum((ids[..., None] == first + jnp.arange(count))
                    * gates[..., None], axis=1)

    def add_expert(acc, expert):
        w, g = expert
        return acc + g[:, None] * _gated_unit(h, w), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (blk["experts"], share.T))
    if "shared" in blk:
        out = out + _gated_unit(h, blk["shared"])
    worst = jnp.min(jnp.take_along_axis(select, ids, axis=-1), axis=-1)
    slack = (jnp.maximum(own_best[:, k - 1] - worst, 0.0)
             / jnp.std(select, axis=-1))
    return out, ids, slack


def sinkhorn(r, iters: int, eps: float, clamp: float):
    """``r`` [..., n, n] → ``exp(clip(r))`` with rows, then columns,
    divided by their sums plus ``eps``, ``iters`` times."""
    m = jnp.exp(jnp.clip(r, -clamp, clamp))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _mixed(x, mix, config, sublayer):
    """One sublayer under its three maps: ``x`` [S, n, d] → ``x'``, and
    whatever else ``sublayer(u) -> (y, extra)`` returns."""
    s, n, d = x.shape
    flat = x.reshape(s, n * d)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                                + config["rms_norm_eps"])
    z = flat @ mix["phi"]
    a = mix["alpha"][0] * z[:, :n] + mix["beta"][:n]
    p = mix["alpha"][1] * z[:, n:2 * n] + mix["beta"][n:2 * n]
    r = (mix["alpha"][2] * z[:, 2 * n:] + mix["beta"][2 * n:]).reshape(s, n, n)
    h_pre, h_post = jax.nn.sigmoid(a), 2.0 * jax.nn.sigmoid(p)
    h_res = sinkhorn(r, config["hc_sinkhorn_iters"], config["hc_eps"],
                     float(config["mhc_h_res_clamp_max"]))
    y, extra = sublayer(jnp.einsum("sn,snd->sd", h_pre, x))
    return (jnp.einsum("snm,smd->snd", h_res, x)
            + h_post[:, :, None] * y[:, None, :]), extra


def _layer(x, blk, config, ids):
    """One layer on the streams ``x`` [S, n, d] of one sequence. Returns
    the streams, and from a routed layer the ids used and their slack
    (else two Nones)."""
    eps = config["rms_norm_eps"]
    x, _ = _mixed(x, blk["mix1"], config, lambda u: (
        _latent_attention(_norm(u, blk["ln1"], eps), blk, config), None))

    def feed_forward(u):
        h = _norm(u, blk["ln2"], eps)
        if "router" not in blk:
            return _gated_unit(h, blk), (None, None)
        out, used, slack = _routed(h, blk, config, ids)
        return out, (used, slack)
    return _mixed(x, blk["mix2"], config, feed_forward)


def _streams(e, config):
    return jnp.broadcast_to(e[:, None, :], (e.shape[0], config["hc_mult"],
                                            e.shape[1]))


def _trunk(params, seq, config, routing):
    """One sequence [S] → the trunk's output before its final norm [S,
    d], and the slack [routed_layers, S] of ``routing`` [routed_layers,
    S, k] (zeros for None)."""
    x = _streams(params["embed"][seq], config)
    slacks = []
    for blk in params["blocks"]:
        ids = (routing[len(slacks)]
               if routing is not None and "router" in blk else None)
        x, (_, slack) = _layer(x, blk, config, ids)
        if slack is not None:
            slacks.append(slack)
    return jnp.sum(x, axis=1), jnp.stack(slacks)


def _per_sequence(params, tokens, config, routing, fn):
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        return [fn(params, *_trunk(params, seq, config,
                                   None if routing is None
                                   else routing[:, b]), seq)
                for b, seq in enumerate(tokens)]


def _nll(lg, targets):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def logits(params, tokens, config: dict, last: int | None = None,
           routing=None):
    """Logits [B, S or last, V] of the trunk through the untied head."""
    def head(p, h, _, seq):
        h = _norm(h, p["final_norm"], config["rms_norm_eps"])
        return (h if last is None else h[-last:]) @ p["head"]
    return jnp.stack(_per_sequence(params, tokens, config, routing, head))


def loss(params, tokens, config: dict, routing=None):
    """Mean next-token cross-entropy over every position but the last."""
    def nll(p, h, _, seq):
        h = _norm(h, p["final_norm"], config["rms_norm_eps"])
        return _nll(h[:-1] @ p["head"], seq[1:])
    total = sum(_per_sequence(params, tokens, config, routing, nll))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def routing_slack(params, tokens, config: dict, routing):
    """Float32 [routed_layers, batch, seq]: see ``lib/cell.py``."""
    return jnp.stack(_per_sequence(params, tokens, config, routing,
                                   lambda p, h, slack, seq: slack), axis=1)


def train_loss(params, tokens, config: dict):
    """What the train step minimises, free-running: :func:`loss` plus
    ``assumed.nextn_loss_weight`` times the module's mean cross-entropy
    over the positions that have a next-next token."""
    eps = config["rms_norm_eps"]

    def both(p, h, _, seq):
        main = _nll(_norm(h, p["final_norm"], eps)[:-1] @ p["head"], seq[1:])
        m = p["nextn"]
        joined = jnp.concatenate(
            [_norm(h[:-1], m["norm_h"], eps),
             _norm(p["embed"][seq[1:]], m["norm_e"], eps)], axis=-1)
        x, _ = _layer(_streams(joined @ m["proj"], config), m["block"],
                      config, None)
        hm = _norm(jnp.sum(x, axis=1), m["final_norm"], eps)
        return main, _nll(hm[:-1] @ p["head"], seq[2:])
    b, s = tokens.shape
    parts = _per_sequence(params, tokens, config, None, both)
    return (sum(p[0] for p in parts) / (b * (s - 1))
            + config["assumed"]["nextn_loss_weight"]
            * sum(p[1] for p in parts) / (b * (s - 2)))


# -- the model's own counts (lib/flops.py's rules: a multiply-add is two
# operations; recomputation, padding, casts and copies never count) --------

def _attention_matmul_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def _mix_matmul_params(c: dict) -> int:
    n = c["hc_mult"]
    return 2 * n * c["hidden_size"] * (2 * n + n * n)    # two sublayers


def _routed_ffn_params_per_token(c: dict) -> float:
    """Weights a token's feed-forward meets on this chip, in expectation
    under even routing: the router, the shared expert, and its
    ``num_experts_per_tok`` experts times the share of experts held."""
    d, unit = c["hidden_size"], 3 * c["hidden_size"] * c["moe_intermediate_size"]
    held = c["n_routed_experts"] / routed_experts(c)
    return (d * routed_experts(c)
            + (c["n_shared_experts"] + c["num_experts_per_tok"] * held) * unit)


def _attention_flops_per_token(c: dict, context: float) -> float:
    """QKᵀ over the query-key width and PV over the value's, one layer,
    one query token attending to ``context`` keys, heads summed."""
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return 2.0 * context * c["num_attention_heads"] * (qk + c["v_head_dim"])


def _layer_count(c: dict) -> tuple[int, int]:
    """(dense, routed) layers a train step runs, the module's included."""
    dense = c["first_k_dense_replace"]
    return dense, (c["num_hidden_layers"] - dense
                   + c["num_nextn_predict_layers"])


def forward_flops_per_token(c: dict, context: float) -> float:
    d = c["hidden_size"]
    dense, routed = _layer_count(c)
    every = (_attention_matmul_params(c) + _mix_matmul_params(c))
    matmul = ((dense + routed) * every
              + dense * 3 * d * c["intermediate_size"]
              + routed * _routed_ffn_params_per_token(c)
              # the module's projection, and a head for it and the trunk
              + c["num_nextn_predict_layers"] * (2 * d * d + d * c["vocab_size"])
              + d * c["vocab_size"])
    return (2.0 * matmul
            + (dense + routed) * _attention_flops_per_token(c, context))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward plus backward (twice the forward) of the train step: the
    trunk, the module and both heads, causal, per token."""
    return 3.0 * forward_flops_per_token(config, (seq_len + 1) / 2.0)


def attention_train_flops_per_token(config: dict, seq_len: int) -> float:
    """Attention's own part: QKᵀ at 192 and PV at 128 of every layer the
    step runs, forward plus backward, causal."""
    return (3.0 * sum(_layer_count(config))
            * _attention_flops_per_token(config, (seq_len + 1) / 2.0))


def decode_bytes_per_step(config: dict, contexts: list[int],
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    raise BenchmarkError(
        "the program has no decode step for latent attention: no serving "
        "cell can run this architecture")
