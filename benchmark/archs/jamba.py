"""Architecture ``jamba``: the decoder of AI21-Jamba2-3B (``model_type``
``jamba``): the Jamba layer (arXiv:2403.19887) whose mixer is Mamba's
selective state space (arXiv:2312.00752 §3), 1 attention layer in
``attn_layer_period``, no positional term anywhere. The keys are those
of its ``config.json``. The plain reference, written from the published
equations and importing nothing of the program: float32, matrix products
at ``highest`` precision, the recurrence token by token. It runs beside
the 6.06 GB of bfloat16 weights it checks, so a weight is cast where it
is used and nothing makes a float32 copy of the tree.

Equations (``d = hidden_size``, ``E = mamba_expand · d``, ``N =
mamba_d_state``, ``K = mamba_d_conv``, ``R = mamba_dt_rank``; every norm
an RMSNorm with a scale, epsilon ``rms_norm_eps`` inside the root; no
bias unless said):

* **A layer**: ``x ← x + mixer_i(N1 x)``, then ``x ← x + W_down(silu(W_gate
  h) ⊙ W_up h)`` with ``h = N2 x`` (``num_experts`` 1: every layer's
  feed-forward is the dense gated unit). Layer ``i`` attends where ``i %
  attn_layer_period == attn_layer_offset``, else its mixer is Mamba's.
  After the last layer a final norm; logits through the embedding
  transposed (tied).
* **Mamba mixer**: ``[u, z] = h W_in``; ``u_t ← silu(b_c + Σ_j w_c[j] ⊙
  u_{t−K+1+j})`` (depth-wise, causal, with bias); ``[δ, B, C] = u W_x``;
  **δ, B, C each through an RMSNorm of its own width** (Jamba's addition);
  ``Δ = softplus(δ W_dt + b_dt)``; ``A = −exp(A_log)``; ``S_t = exp(Δ_t ⊗
  A) ⊙ S_{t−1} + (Δ_t ⊙ u_t) ⊗ B_t``; ``y_t = S_t C_t + D ⊙ u_t``; out ``(y ⊙
  silu(z)) W_out``.
* **Attention**: ``num_attention_heads`` query heads of ``d / heads``,
  ``num_key_value_heads`` key-value heads, a group of queries reading
  each; scores scaled by ``hd^−½``, causal softmax, output ``d → d``. No
  rotation, no window.

Departures and what the config does not say (the configuration file says
the same under ``assumed`` and ``departures``):

* the catalog marks the order of the layer types ``not_given``: the
  family's published convention for ``attn_layer_period`` and
  ``attn_layer_offset`` is ASSUMED;
* weights are stored and served in bfloat16; this file reads them as
  float32 values;
* the program keeps the state as ``[N, E]`` (channels in the lanes) and
  ``A_log`` in that layout: the equations' ``[E, N]`` transposed, data and
  no other difference; this file reads ``a_log`` [N, E] as it is stored.

The parameter tree is data, in the program's layout: ``embed`` [V, d],
``final_norm``, ``blocks``: every layer ``ln1``, ``ln2``, ``w_gate``,
``w_up``, ``w_down``; a Mamba layer ``w_in`` [d, 2, E] (u then z),
``conv_w`` [K, E] (oldest tap first), ``conv_b``, ``w_x`` [E, R + 2N] (δ,
B, C in that order), ``dt_norm``, ``b_norm``, ``c_norm``, ``w_dt`` [R, E],
``b_dt``, ``a_log`` [N, E], ``d_skip``, ``w_out`` [E, d]; an attention
layer ``wqkv`` [d, (H + 2G)·hd] (the queries' columns, then the keys',
then the values') and ``wo`` [d, d].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.lib.cell import BenchmarkError

_QUERY_BLOCK = 512


def attends(config: dict, layer: int) -> bool:
    return (layer % config["attn_layer_period"]
            == config["attn_layer_offset"])


def layer_counts(config: dict) -> tuple[int, int]:
    """(attention layers, Mamba layers)."""
    a = sum(attends(config, i) for i in range(config["num_hidden_layers"]))
    return a, config["num_hidden_layers"] - a


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
    if (config["hidden_act"] != "silu" or not config["tie_word_embeddings"]
            or config["num_experts"] != 1 or config["mamba_proj_bias"]
            or not config["mamba_conv_bias"]
            or config.get("sliding_window") is not None
            or config["hidden_size"] % config["num_attention_heads"]):
        raise BenchmarkError(
            "the program serves this family with SiLU gated units, a "
            "dense feed-forward every layer, a tied head, a biased "
            "convolution, unbiased projections and no window; this "
            "configuration asks for something else")
    section = {
        "name": "transformer", "model_dim": config["hidden_size"],
        "num_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "num_layers": config["num_hidden_layers"],
        "seq_len": config["assumed"]["seq_len"],
        "vocab_size": config["vocab_size"],
        "ffn_dim": config["intermediate_size"],
        "ssm_state_dim": config["mamba_d_state"],
        "ssm_expand": config["mamba_expand"],
        "ssm_conv": config["mamba_d_conv"],
        "ssm_dt_rank": config["mamba_dt_rank"],
        "attn_layer_period": config["attn_layer_period"],
        "attn_layer_offset": config["attn_layer_offset"],
        "norm_eps": config["rms_norm_eps"],
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


def _mamba(h, blk, config):
    """``h`` [S, d], normed → the mixer's output [S, d]."""
    eps, n = config["rms_norm_eps"], config["mamba_d_state"]
    rank, taps = config["mamba_dt_rank"], config["mamba_d_conv"]
    s = h.shape[0]
    uz = jnp.einsum("sd,dte->ste", h, _w(blk["w_in"]))
    u, z = uz[:, 0], uz[:, 1]
    before = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1])), u])
    u = jax.nn.silu(_w(blk["conv_b"]) + sum(
        _w(blk["conv_w"])[j] * before[j:j + s] for j in range(taps)))
    x = u @ _w(blk["w_x"])
    delta = jax.nn.softplus(
        _norm(x[:, :rank], blk["dt_norm"], eps) @ _w(blk["w_dt"])
        + _w(blk["b_dt"]))
    b = _norm(x[:, rank:rank + n], blk["b_norm"], eps)
    c = _norm(x[:, rank + n:], blk["c_norm"], eps)
    a = -jnp.exp(_w(blk["a_log"]))                       # [N, E]

    def token(state, xs):
        u_t, delta_t, b_t, c_t = xs
        state = (jnp.exp(delta_t[None, :] * a) * state
                 + (delta_t * u_t)[None, :] * b_t[:, None])
        return state, c_t @ state

    _, y = jax.lax.scan(token, jnp.zeros_like(a), (u, delta, b, c))
    y = y + _w(blk["d_skip"]) * u
    return (y * jax.nn.silu(z)) @ _w(blk["w_out"])


def _attention(h, blk, config):
    """``h`` [S, d], normed → the attention sublayer's output [S, d]."""
    s, d = h.shape
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // heads
    qkv = h @ _w(blk["wqkv"])
    q = qkv[:, :heads * hd].reshape(s, kv, heads // kv, hd)
    k = qkv[:, heads * hd:(heads + kv) * hd].reshape(s, kv, hd)
    v = qkv[:, (heads + kv) * hd:].reshape(s, kv, hd)
    # the largest block of queries that divides the sequence
    block = max(b for b in range(1, min(_QUERY_BLOCK, s) + 1) if s % b == 0)
    key_pos = jnp.arange(s)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qgje,kge->gjqk", qb, k) * hd ** -0.5
        seen = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("gjqk,kge->qgje", probs, v)

    o = jax.lax.map(rows, jnp.arange(0, s, block)).reshape(s, d)
    return o @ _w(blk["wo"])


def _gated_unit(x, w):
    return ((jax.nn.silu(x @ _w(w["w_gate"])) * (x @ _w(w["w_up"])))
            @ _w(w["w_down"]))


def _trunk(params, seq, config):
    """One sequence [S] → the trunk's output after its final norm."""
    eps = config["rms_norm_eps"]
    x = _w(params["embed"][seq])
    for i, blk in enumerate(params["blocks"]):
        mixer = _attention if attends(config, i) else _mamba
        x = x + mixer(_norm(x, blk["ln1"], eps), blk, config)
        x = x + _gated_unit(_norm(x, blk["ln2"], eps), blk)
    return _norm(x, params["final_norm"], eps)


def logits(params, tokens, config: dict, last: int | None = None):
    """Logits [B, S or last, V] through the embedding transposed."""
    with jax.default_matmul_precision("highest"):
        out = []
        for seq in tokens:
            h = _trunk(params, seq, config)
            out.append((h if last is None else h[-last:])
                       @ _w(params["embed"]).T)
        return jnp.stack(out)


def loss(params, tokens, config: dict):
    """Mean next-token cross-entropy over every position but the last."""
    with jax.default_matmul_precision("highest"):
        total = 0.0
        for seq in tokens:
            h = _trunk(params, seq, config)
            logp = jax.nn.log_softmax(h[:-1] @ _w(params["embed"]).T, -1)
            total = total - jnp.sum(
                jnp.take_along_axis(logp, seq[1:, None], axis=-1))
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))


# -- the model's own counts (lib/flops.py's rules: a multiply-add is two
# operations; recomputation, padding, casts and copies never count; norm
# scales multiply elementwise and are left out) ------------------------------

def mamba_mixer_params(c: dict) -> int:
    """Every stored number of one mixer, its vectors included."""
    d, n, r = c["hidden_size"], c["mamba_d_state"], c["mamba_dt_rank"]
    e = c["mamba_expand"] * d
    return (d * 2 * e + e * c["mamba_d_conv"] + e + e * (r + 2 * n)
            + (r + 2 * n) + r * e + e + e * n + e + e * d)


def attention_params(c: dict) -> int:
    d = c["hidden_size"]
    hd = d // c["num_attention_heads"]
    return 2 * d * d + 2 * d * c["num_key_value_heads"] * hd


def unit_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def param_count(c: dict) -> int:
    """Every stored parameter: the tied embedding, per layer the mixer,
    the gated unit and two norm scales, and the final norm."""
    d = c["hidden_size"]
    attn, mamba = layer_counts(c)
    return (c["vocab_size"] * d + d
            + attn * (attention_params(c) + unit_params(c) + 2 * d)
            + mamba * (mamba_mixer_params(c) + unit_params(c) + 2 * d))


def _matmul_params(c: dict) -> int:
    """Weights that enter a matrix product, all layers and the head."""
    d, n, r = c["hidden_size"], c["mamba_d_state"], c["mamba_dt_rank"]
    e = c["mamba_expand"] * d
    attn, mamba = layer_counts(c)
    mixer = d * 2 * e + e * (r + 2 * n) + r * e + e * d
    return (attn * attention_params(c) + mamba * mixer
            + (attn + mamba) * unit_params(c) + d * c["vocab_size"])


def _attention_flops_per_token(c: dict, context: float) -> float:
    """QKᵀ and PV of one layer for one query token that attends to
    ``context`` keys, query heads summed."""
    return 4.0 * context * c["hidden_size"]


def _recurrence_flops_per_token(c: dict) -> float:
    """One Mamba layer's recurrence for one token: per (channel, state)
    the decay's product, the drive's two, the update's multiply-add and
    the read-out's; the convolution's K multiply-adds a channel."""
    e = c["mamba_expand"] * c["hidden_size"]
    return e * c["mamba_d_state"] * 7.0 + 2.0 * e * c["mamba_d_conv"]


def forward_flops_per_token(c: dict, context: float) -> float:
    attn, mamba = layer_counts(c)
    return (2.0 * _matmul_params(c)
            + attn * _attention_flops_per_token(c, context)
            + mamba * _recurrence_flops_per_token(c))


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward plus backward (twice the forward), causal, per token. No
    cell trains this configuration; the count is the interface's."""
    return 3.0 * forward_flops_per_token(config, (seq_len + 1) / 2.0)


def attention_train_flops_per_token(config: dict, seq_len: int) -> float:
    return (3.0 * layer_counts(config)[0]
            * _attention_flops_per_token(config, (seq_len + 1) / 2.0))


def state_bytes_per_step(config: dict, live_slots: int,
                         state_bytes: int = 4, tail_bytes: int = 2) -> float:
    """Bytes the Mamba layers' per-sequence state costs one decode step:
    every live slot's recurrent state (``E · N`` float32 a layer) and its
    convolution tail (``(K − 1) · E`` in the compute dtype) read AND
    written. Idle slots, padding and the step's other operands (the
    weights, the token's activations) do not count."""
    c = config
    e = c["mamba_expand"] * c["hidden_size"]
    a_layer = (e * c["mamba_d_state"] * state_bytes
               + (c["mamba_d_conv"] - 1) * e * tail_bytes)
    return 2.0 * live_slots * layer_counts(c)[1] * a_layer


def decode_bytes_per_step(config: dict, contexts: list[int],
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step must move: every matrix once (the mixers',
    the attention layers', the gated units') and every vector of the
    mixers; of the tied embedding its live rows (one a sequence) and the
    whole of it once as the head; each live sequence's cached keys and
    values in the attention layers (``2 · kv_heads · hd`` values a token
    a layer); and the Mamba layers' state of each live sequence both
    ways (:func:`state_bytes_per_step`: read, advanced, written)."""
    c = config
    d = c["hidden_size"]
    attn, mamba = layer_counts(c)
    live = len(contexts)
    weights = (attn * attention_params(c) + mamba * mamba_mixer_params(c)
               + (attn + mamba) * unit_params(c)
               + live * d + d * c["vocab_size"]) * weight_bytes
    hd = d // c["num_attention_heads"]
    rows = sum(contexts) * attn * 2 * c["num_key_value_heads"] * hd * kv_bytes
    return float(weights + rows + state_bytes_per_step(c, live))
