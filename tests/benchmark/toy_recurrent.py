"""An architecture file whose served state is a SEQUENCE's and not a
token's: a stack of linear recurrences over the embeddings, no
attention, so nothing to page and no block table. It stands for what a
later PR brings with a state-space or linear-attention model, as
``toy_routed.py`` stood for a routed one; its stand-in program is
``toy_recurrent_model.py``, which it does not import.

A layer: ``h_t = sigmoid(decay) * h_{t-1} + x_t @ w_in`` over a state of
``d``, ``x_t <- x_t + tanh(h_t) @ w_out``; the head is the embedding,
tied. Plain float32 at ``highest`` matrix precision, position by
position."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def toy_config(dim: int = 32, layers: int = 2, vocab: int = 64) -> dict:
    return {"arch": "toy_recurrent", "hidden_size": dim,
            "num_hidden_layers": layers, "vocab_size": vocab}


def init(key, config: dict) -> dict:
    d, v = config["hidden_size"], config["vocab_size"]
    keys = jax.random.split(key, 1 + 3 * config["num_hidden_layers"])
    mat = lambda k: jax.random.normal(k, (d, d)) / d ** 0.5  # noqa: E731
    return {"embed": jax.random.normal(keys[0], (v, d)),
            "layers": [{"decay": 2.0 + jax.random.normal(keys[3 * i + 1],
                                                         (d,)),
                        "w_in": mat(keys[3 * i + 2]),
                        "w_out": mat(keys[3 * i + 3])}
                       for i in range(config["num_hidden_layers"])]}


def logits(params, tokens, config, last=None):
    """``[batch, seq]`` tokens to ``[batch, last or seq, vocab]``."""
    del config
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = f32(params["embed"])[tokens]
    for layer in params["layers"]:
        keep = jax.nn.sigmoid(f32(layer["decay"]))
        u = jnp.matmul(x, f32(layer["w_in"]), precision=HIGHEST)
        h, hs = jnp.zeros_like(u[:, 0]), []
        for t in range(u.shape[1]):
            h = keep * h + u[:, t]
            hs.append(h)
        x = x + jnp.matmul(jnp.tanh(jnp.stack(hs, axis=1)),
                           f32(layer["w_out"]), precision=HIGHEST)
    if last is not None:
        x = x[:, -last:]
    return jnp.matmul(x, f32(params["embed"]).T, precision=HIGHEST)
