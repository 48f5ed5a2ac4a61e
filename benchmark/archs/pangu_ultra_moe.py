"""Architecture ``pangu_ultra_moe``: the decoder of openPangu-Ultra-MoE-718B
(``model_type`` ``pangu_ultra_moe``), as one chip of a deployment that
shares each layer over 16 chips holds it. The keys are those of its
``config.json``. The plain reference, written from the published
equations (Pangu Ultra, arXiv:2504.07866 §2, for the sandwich norms;
Pangu Ultra MoE, arXiv:2505.04519; latent attention as DeepSeek-V2,
arXiv:2405.04434 §2.1) and importing nothing of the program: float32,
matrix products at ``highest`` precision. It runs beside the 9.84 GB of
bfloat16 weights it checks, so nothing here makes a float32 copy of the
tree: a weight is cast where it is used, the experts one at a time
inside a scan, the embedding after its rows are picked, and attention
takes a block of queries at a time.

Equations (``d = hidden_size``, ``H`` heads; every norm an RMSNorm with a
scale, epsilon ``rms_norm_eps`` inside the root):

* **A layer** (``sandwich_norm``): ``a = x + N2(Attn(N1 x))``, ``y = a +
  N4(F(N3 a))``: a norm before each sublayer and one on its output before
  it joins the residual. After the last layer a final norm and the
  untied head.
* **Attn**, in the expanded form: ``c_q = Nq(h W_dq)``; ``[q_n | q_r] =
  c_q W_uq`` a head; ``[c | k_r] = h W_dkv``, ``c = Nkv(c)``; ``q_r`` and
  ``k_r`` rotated at the token's position (plain rotary, base
  ``rope_theta``, no scaling; ``k_r`` one row for all heads); ``[k_n | v]
  = c W_ukv`` a head; scores ``(q_n·k_n + q_r·k_r) / sqrt(nope + rope)``,
  causal softmax, ``o = Σ w v``, output ``concat(o) W_o``. (The program's
  decode step computes the same function reassociated, ``W_uk`` absorbed
  into the query and ``W_uv`` into the output, over a cache of ``(c,
  k_r)``; its prefill this form. Both are held to this file.)
* **F.** The first ``first_k_dense_replace`` layers: ``W_d(silu(h W_g) ⊙ h
  W_u)``. The others: ``Shared(h) + Σ_{e in top-k} g_e Expert_e(h)`` over
  the chosen experts THIS CHIP HOLDS, each a gated unit
  ``moe_intermediate_size`` wide; ``s = sigmoid(h W_r)`` over all
  ``n_routed_experts`` of the layer, the ``num_experts_per_tok`` largest
  ``s``, ``g_e = routed_scaling_factor · s_e / (Σ_chosen s + 1e-20)``.

Departures and what the config does not say (``assumed`` in the
configuration file says the same):

* the config has no scoring key: sigmoid scores, no selection bias and
  no group limit are ASSUMED, the published gate of the family this
  ``config.json`` follows. The program's tree keeps a ``router_bias``
  leaf, zeros, which this file does not read;
* the rotated columns of a head's query and key are its last
  ``qk_rope_head_dim``, their pairs laid out as halves (column ``i`` turns
  with column ``i + rope/2``) where the published code interleaves them:
  with seeded weights a permutation of columns;
* the next-token module (``num_nextn_predict_layers``) is not loaded,
  as the published inference code drops it;
* weights are stored and served in bfloat16; this file reads them as
  float32 values. The norms' scales are data like any weight: the
  program starts the output norms' depth-scaled by the depth of the tree
  it holds (``c / sqrt(5)``, the family's published rule at the cut's
  depth) and this file reads what it is given.

The parameter tree is data, in the program's layout: ``embed`` [V, d],
``head`` [d, V], ``final_norm``, ``blocks`` (``ln1``, ``ln1_out``, ``ln2``,
``ln2_out``; ``wq_a``, ``q_norm``, ``wq_b`` [r_q, H, nope + rope], ``wkv_a``
[d, r_kv + rope], ``kv_norm``, ``wkv_b`` [r_kv, H, nope + v], ``wo``;
``w_gate``/``w_up``/``w_down``, or ``router`` [d, experts], ``experts`` and
``shared``). The routed layers of ``routing`` are the trunk's, in order,
``k = num_experts_per_tok``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

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
    if (not config["sandwich_norm"] or not config["norm_topk_prob"]
            or config["hidden_act"] != "silu" or config["attention_bias"]
            or config["tie_word_embeddings"]
            or config["num_key_value_heads"] != config["num_attention_heads"]
            or config["num_nextn_predict_layers"] != 0
            or config.get("rope_scaling")):
        raise BenchmarkError(
            "the program serves this family with sandwich norms, "
            "renormalised sigmoid gates, SiLU gated units, plain rotary "
            "positions, an untied head and no next-token module loaded; "
            "this configuration asks for something else")
    first, count = _held(config)
    section = {
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
        "ffn_dim": config["intermediate_size"],
        "routed_experts": routed_experts(config),
        "held_experts": count, "first_held_expert": first,
        "experts_per_token": config["num_experts_per_tok"],
        "shared_experts": config["n_shared_experts"],
        "expert_ffn_dim": config["moe_intermediate_size"],
        "routed_scaling": float(config["routed_scaling_factor"]),
        "dense_layers": config["first_k_dense_replace"],
        "sandwich_norm": True, "norm_eps": config["rms_norm_eps"],
        **config.get("model_assumed", {})}
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
    q = jnp.einsum("sr,rhe->she",
                   _norm(h @ _w(blk["wq_a"]), blk["q_norm"], eps),
                   _w(blk["wq_b"]))
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

    o = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(s, -1)
    return o @ _w(blk["wo"])


def _gated_unit(x, w):
    return ((jax.nn.silu(x @ _w(w["w_gate"])) * (x @ _w(w["w_up"])))
            @ _w(w["w_down"]))


def _routed(h, blk, config, ids):
    """The routed feed-forward of one sequence ``h`` [S, d] (normed), as
    the chip that holds experts ``_held(config)`` computes it. ``ids`` [S,
    k] forces the experts; None lets the reference choose. Returns the
    output, the ids used and the slack of ``ids`` [S] in units of the
    spread of the position's scores."""
    k = config["num_experts_per_tok"]
    first, count = _held(config)
    score = jax.nn.sigmoid(h @ _w(blk["router"]))
    own_best, own = jax.lax.top_k(score, k)
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
    worst = jnp.min(jnp.take_along_axis(score, ids, axis=-1), axis=-1)
    slack = (jnp.maximum(own_best[:, k - 1] - worst, 0.0)
             / jnp.std(score, axis=-1))
    return out, ids, slack


def _layer(x, blk, config, ids):
    """One layer on one sequence ``x`` [S, d]. Returns the residual, and
    from a routed layer the slack of ``ids`` (else None)."""
    eps = config["rms_norm_eps"]
    a = x + _norm(_latent_attention(_norm(x, blk["ln1"], eps), blk, config),
                  blk["ln1_out"], eps)
    h = _norm(a, blk["ln2"], eps)
    if "router" not in blk:
        f, slack = _gated_unit(h, blk), None
    else:
        f, _, slack = _routed(h, blk, config, ids)
    return a + _norm(f, blk["ln2_out"], eps), slack


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
    """Float32 [routed_layers, batch, seq]: see ``lib/cell.py``."""
    return jnp.stack(_per_sequence(params, tokens, config, routing,
                                   lambda h, slack, seq: slack), axis=1)


# -- the model's own counts (lib/flops.py's rules: a multiply-add is two
# operations; recomputation, padding, casts and copies never count; norm
# scales multiply elementwise and are left out) ------------------------------

def _attention_matmul_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def _unit_params(c: dict) -> int:
    """One expert, routed or shared: three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _layer_count(c: dict) -> tuple[int, int]:
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def _attention_flops_per_token(c: dict, context: float) -> float:
    """QKᵀ over the query-key width and PV over the value's, one layer,
    one query token attending to ``context`` keys, heads summed (the
    expanded form's count: the absorbed one does more and counts no
    more)."""
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return 2.0 * context * c["num_attention_heads"] * (qk + c["v_head_dim"])


def forward_flops_per_token(c: dict, context: float) -> float:
    """One token through the layers and the head this chip holds, in
    expectation under even routing: its ``num_experts_per_tok`` experts
    times the share of experts held."""
    d = c["hidden_size"]
    dense, routed = _layer_count(c)
    held = c["n_routed_experts"] / routed_experts(c)
    matmul = ((dense + routed) * _attention_matmul_params(c)
              + dense * 3 * d * c["intermediate_size"]
              + routed * (d * routed_experts(c)
                          + (c["n_shared_experts"]
                             + c["num_experts_per_tok"] * held)
                          * _unit_params(c))
              + d * c["vocab_size"])
    return (2.0 * matmul
            + (dense + routed) * _attention_flops_per_token(c, context))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward plus backward (twice the forward), causal, per token. No
    cell trains this configuration; the count is the interface's."""
    return 3.0 * forward_flops_per_token(config, (seq_len + 1) / 2.0)


def attention_train_flops_per_token(config: dict, seq_len: int) -> float:
    return (3.0 * sum(_layer_count(config))
            * _attention_flops_per_token(config, (seq_len + 1) / 2.0))


def expected_experts_touched(config: dict, tokens: int) -> float:
    """How many of the held experts of one layer take at least one of
    ``tokens`` tokens, in expectation UNDER UNIFORM ROUTING: a token
    takes ``k`` different experts of ``E``, so a given expert is missed
    by all of them with probability ``(1 - k/E)^tokens``. Seeded random
    weights route close to uniformly; a trained router does not, and a
    roofline built on this then reads what the traffic would allow, not
    what it did (``decode_experts_touched_p50`` has the count that
    was)."""
    miss = 1.0 - config["num_experts_per_tok"] / routed_experts(config)
    return config["n_routed_experts"] * (1.0 - miss ** tokens)


def decode_bytes_per_step(config: dict, contexts: list[int],
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step must move. Every matrix outside the routed
    experts once: attention's five a layer, the dense layers' three, the
    routers, the shared experts, the head. The embedding's live rows,
    one a sequence. Of the routed experts the held ones that some live
    token takes, :func:`expected_experts_touched` a routed layer (uniform
    routing: said there). And what the cache keeps of each live
    sequence's tokens, one latent and one rotated key a token a layer,
    ``kv_lora_rank + qk_rope_head_dim`` values: 1,152 B at 2 bytes,
    however wide the device stores the row."""
    c = config
    d = c["hidden_size"]
    dense, routed = _layer_count(c)
    live = len(contexts)
    weights = ((dense + routed) * _attention_matmul_params(c)
               + dense * 3 * d * c["intermediate_size"]
               + routed * (d * routed_experts(c)
                           + c["n_shared_experts"] * _unit_params(c)
                           + expected_experts_touched(c, live)
                           * _unit_params(c))
               + live * d + d * c["vocab_size"]) * weight_bytes
    row = (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * kv_bytes
    return float(weights + sum(contexts) * (dense + routed) * row)
