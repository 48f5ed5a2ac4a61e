"""Operations and bytes the model's algorithm needs, from its shapes.

These are MODEL counts: what the forward and backward passes require,
whatever the program does to get there. Recomputed operations
(``model.remat``), padding, casts and copies never count, so a
utilisation built on these cannot be raised by doing more work.

A multiply-add is two operations. ``shapes`` is the configuration
file's source keys (``hidden_size``, ``ffn_dim``, ``num_attention_heads``,
``num_hidden_layers``, ``vocab_size``, ``max_position_embeddings``)."""

from __future__ import annotations


def block_matmul_params(shapes: dict) -> int:
    """Weights of one block that enter a matrix multiplication: q, k, v
    and the output projection (4·d²) and the two FFN matrices
    (2·d·ffn). Norm scales multiply elementwise and are left out."""
    d, ffn = shapes["hidden_size"], shapes["ffn_dim"]
    return 4 * d * d + 2 * d * ffn


def param_count(shapes: dict) -> int:
    """Every stored parameter of the repo's block at these shapes: the
    tied embedding, the learned positions, per block the matrices and
    two norm scales, and the final norm."""
    d = shapes["hidden_size"]
    return ((shapes["vocab_size"] + shapes["max_position_embeddings"]) * d
            + shapes["num_hidden_layers"] * (block_matmul_params(shapes)
                                             + 2 * d)
            + d)


def attention_flops_per_token(shapes: dict, context: float) -> float:
    """QKᵀ and PV of one layer for one query token that attends to
    ``context`` keys: 2·context·d each, heads summed."""
    return 4.0 * context * shapes["hidden_size"]


def forward_flops_per_token(shapes: dict, context: float) -> float:
    """One token through every block and the tied head, attending to
    ``context`` keys in each layer. For a causal pass over a whole
    sequence of S tokens the mean context is (S+1)/2."""
    layers = shapes["num_hidden_layers"]
    return (layers * (2.0 * block_matmul_params(shapes)
                      + attention_flops_per_token(shapes, context))
            + 2.0 * shapes["hidden_size"] * shapes["vocab_size"])


def train_flops_per_token(shapes: dict, seq_len: int) -> float:
    """Forward plus backward (twice the forward: one product for the
    input gradient, one for the weight gradient) for a causal sequence
    of ``seq_len`` tokens, per token. The optimizer's elementwise update
    is not a matrix operation and is left out, as is conventional."""
    return 3.0 * forward_flops_per_token(shapes, (seq_len + 1) / 2.0)


def attention_train_flops_per_token(shapes: dict, seq_len: int) -> float:
    """Attention's own part of :func:`train_flops_per_token`: QK^T and
    PV of every layer, forward plus backward, causal."""
    return (3.0 * shapes["num_hidden_layers"]
            * attention_flops_per_token(shapes, (seq_len + 1) / 2.0))


def decode_bytes_per_step(shapes: dict, contexts: list[int],
                          weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one decode step must move: every matrix weight once, and
    each live sequence's keys and values (2·layers·d per cached token).
    What the step is bound by on a chip whose decode is bandwidth-bound."""
    d, layers = shapes["hidden_size"], shapes["num_hidden_layers"]
    weights = (layers * block_matmul_params(shapes)
               + shapes["vocab_size"] * d) * weight_bytes
    kv = sum(contexts) * 2 * layers * d * kv_bytes
    return float(weights + kv)
