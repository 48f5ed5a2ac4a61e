"""The plain reference: the block's forward pass, loss and gradients in
straightforward ``jax.numpy``, float32, matrix products at
``highest`` precision, with no kernel, no cache, no batching tricks and
no recomputation. ``correct`` is decided against this, so it imports
nothing from the program: the parameter tree is data (the program's
``embed``/``pos``/``blocks``/``final_norm`` layout), the equations are
written out here.

The block is OPT's decoder layer (Zhang et al., arXiv:2205.01068;
``transformers`` ``OPTDecoderLayer`` with ``do_layer_norm_before``):
pre-norm attention and pre-norm ReLU FFN of width ``ffn_dim``, learned
absolute positions, the output head tied to the embedding. The repo's
block departs from OPT in three places, which change no matrix shape
and are reproduced here because the reference has to compute what the
system claims to compute:

* RMSNorm (scale only, epsilon 1e-6 inside the root) where OPT has
  LayerNorm with mean subtraction and a bias;
* no bias on any projection (OPT has one on q, k, v, out, fc1, fc2);
* position ``i`` reads row ``i`` of the table (OPT reads row ``i + 2``
  of a table two rows longer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NORM_EPS = 1e-6


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + _NORM_EPS) * scale


def _block(x, blk, num_heads: int):
    """One decoder layer on one sequence ``x`` [S, d]."""
    s, d = x.shape
    hd = d // num_heads
    h = _rms_norm(x, blk["ln1"]["scale"])
    # wqkv is [d, 3, d]: the three projections side by side
    q, k, v = (h @ blk["wqkv"][:, i, :] for i in range(3))
    q, k, v = (t.reshape(s, num_heads, hd).transpose(1, 0, 2)
               for t in (q, k, v))                      # [H, S, hd]
    scores = q @ k.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = (probs @ v).transpose(1, 0, 2).reshape(s, d)
    x = x + o @ blk["wo"]
    h = _rms_norm(x, blk["ln2"]["scale"])
    return x + jax.nn.relu(h @ blk["w1"]) @ blk["w2"]


def hidden(params, tokens, num_heads: int):
    """Final-normed hidden states [S, d] of one sequence [S]."""
    x = params["embed"][tokens] + params["pos"][:tokens.shape[0]]
    for blk in params["blocks"]:
        x = _block(x, blk, num_heads)
    return _rms_norm(x, params["final_norm"]["scale"])


def logits(params, tokens, num_heads: int, last: int | None = None):
    """Logits [B, S or last, V] of ``tokens`` [B, S] through the tied
    head; ``last`` keeps only the last so many positions (each still
    attends to the whole context before it)."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        out = []
        for seq in tokens:
            h = hidden(params, seq, num_heads)
            if last is not None:
                h = h[-last:]
            out.append(h @ params["embed"].T)
        return jnp.stack(out)


def loss(params, tokens, num_heads: int):
    """Mean next-token cross-entropy over every position but the last
    of every sequence (the training loss)."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        total = jnp.float32(0.0)
        for seq in tokens:
            lg = hidden(params, seq, num_heads)[:-1] @ params["embed"].T
            logp = jax.nn.log_softmax(lg, axis=-1)
            total = total - jnp.sum(
                jnp.take_along_axis(logp, seq[1:, None], axis=-1))
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def loss_and_grads(params, tokens, num_heads: int):
    """The loss and its gradient with respect to every parameter, by
    plain automatic differentiation of :func:`loss`."""
    return jax.value_and_grad(
        lambda p: loss(p, tokens, num_heads))(_f32(params))
