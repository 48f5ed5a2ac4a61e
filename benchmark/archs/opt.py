"""Architecture ``opt``: OPT's decoder (Zhang et al., arXiv:2205.01068)
through the block of ``models/transformer.py``. The configuration's
keys are those of OPT's ``config.json`` (``hidden_size``, ``ffn_dim``,
``num_attention_heads``, ``num_hidden_layers``, ``vocab_size``,
``max_position_embeddings``, ``word_embed_proj_dim``).

The equations are ``lib/reference.py``'s and the counts
``lib/flops.py``'s, which read those keys: this file hands them the
configuration and holds nothing twice. The departures of the repo's
block from OPT are listed in ``lib/reference.py`` and in each
configuration file."""

from __future__ import annotations

from benchmark.lib import flops, reference
from benchmark.lib.cell import BenchmarkError

train_flops_per_token = flops.train_flops_per_token
attention_train_flops_per_token = flops.attention_train_flops_per_token
decode_bytes_per_step = flops.decode_bytes_per_step


def model_section(config: dict) -> dict:
    """The program's ``model`` section from the configuration's source
    keys. Only sizes: the choice of attention implementation, dtype and
    recomputation policy stay at the program's defaults."""
    d, ffn = config["hidden_size"], config["ffn_dim"]
    if ffn != 4 * d:
        raise BenchmarkError(
            f"ffn_dim {ffn} is not 4 x hidden_size {d}: the repo's block "
            "fixes the FFN width at 4·d and cannot run this shape")
    if config.get("word_embed_proj_dim", d) != d:
        raise BenchmarkError("word_embed_proj_dim differs from hidden_size: "
                             "the repo's block has no embedding projection")
    return {"name": "transformer", "model_dim": d,
            "num_heads": config["num_attention_heads"],
            "num_layers": config["num_hidden_layers"],
            "seq_len": config["max_position_embeddings"],
            "vocab_size": config["vocab_size"],
            **config.get("model_assumed", {})}


def logits(params, tokens, config: dict, last: int | None = None):
    return reference.logits(params, tokens, config["num_attention_heads"],
                            last=last)


def loss(params, tokens, config: dict):
    return reference.loss(params, tokens, config["num_attention_heads"])
