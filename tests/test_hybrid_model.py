"""A hybrid of state-space and attention layers against its plain
reference (``benchmark/archs/jamba.py``) on seeded weights, 1 attention
layer in 4 with 1 key-value head for 4 queries: ``apply``, the flash
prefill, prefill then decoding through the model record's decode session
on both arms; and the paged kernel and the gather with fewer key-value
heads than queries against the dense oracle, interpreted."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import cell as cell_lib
from distributedmnist_tpu.core.config import DecodeConfig, ModelConfig
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.ops.pallas_paged_attention import (
    _scattered, paged_attention, paged_attention_dense,
    paged_attention_write)

CONFIG = {
    "arch": "jamba", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 1, "num_hidden_layers": 4, "vocab_size": 97,
    "intermediate_size": 96, "mamba_d_state": 8, "mamba_expand": 2,
    "mamba_d_conv": 4, "mamba_dt_rank": 6, "attn_layer_period": 4,
    "attn_layer_offset": 1, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "tie_word_embeddings": True, "num_experts": 1, "mamba_proj_bias": False,
    "mamba_conv_bias": True, "sliding_window": None,
    "assumed": {"seq_len": 64},
    "model_assumed": {"attention_impl": "dense", "compute_dtype": "float32"}}
DECODE = DecodeConfig(decode_slots=3, block_size=8, num_blocks=20,
                      max_prompt_len=16, max_new_tokens=16)


@pytest.fixture(scope="module")
def arch():
    return cell_lib.load_arch(CONFIG)


@pytest.fixture(scope="module")
def model_and_params(arch):
    model = get_model(ModelConfig(**arch.model_section(CONFIG)))
    return model, model.init(jax.random.PRNGKey(1))


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def test_the_pattern_is_read_from_the_model_section(arch, model_and_params):
    model, params = model_and_params
    kinds = ["wqkv" in blk for blk in params["blocks"]]
    assert kinds == [arch.attends(CONFIG, i) for i in range(4)]
    assert kinds == [False, True, False, False]
    assert model.decode_cache_shape == (1, 1, 16)
    assert model.decode_state_shape == (3, 8, 128, 3)
    assert sum(a.size for a in jax.tree.leaves(params)) == arch.param_count(
        CONFIG)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_apply_is_the_reference(arch, model_and_params, impl):
    _, params = model_and_params
    section = {**arch.model_section(CONFIG), "attention_impl": impl}
    model = get_model(ModelConfig(**section))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 23), 0, 97)
    assert _rel(model.apply(params, tokens),
                arch.logits(params, tokens, CONFIG)) < 1e-5
    assert float(jnp.abs(
        arch.loss(params, tokens, CONFIG)
        - model.loss(model.apply(params, tokens), tokens)
    )) < 1e-4


@pytest.mark.parametrize("kernel", ["dense", "paged"])
def test_prefill_then_decoding_through_the_session_is_the_reference(
        arch, model_and_params, kernel):
    model, params = model_and_params
    session = model.decode_session(
        params, dataclasses.replace(DECODE, attention_kernel=kernel),
        jnp.float32)
    assert session.said["attention_arm"] == (
        "paged" if kernel == "paged" else "gather")
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (19,), 0, 97))
    rows = [session.prefill(seq[:11])]        # padded to a bucket of 16
    for pos in range(11, 19):
        rows.append(session.step(int(seq[pos]), pos))
    want = arch.logits(params, jnp.asarray(seq[None]), CONFIG, last=9)[0]
    assert _rel(jnp.stack(rows), want) < 1e-5
    # a step asked again at the position it has just stepped leaves what
    # the first left: the same logits now, and the same at the next
    again = session.step(int(seq[18]), 18)
    assert float(jnp.abs(again - rows[-1]).max()) == 0.0
    # a second prompt in the same slot starts from nothing of the first
    other = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (7,), 0, 97))
    fresh = model.decode_session(params, DECODE, jnp.float32)
    assert _rel(session.prefill(other), fresh.prefill(other)) < 1e-6


def _paged_inputs(heads, kv_heads, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    slots, hd, block, blocks, width = 4, 16, 8, 12, 3
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    k, v = f(2, blocks, block, kv_heads, hd), f(2, blocks, block, kv_heads, hd)
    q = f(slots, heads, hd)
    lengths = jnp.asarray([17, 0, 8, 24], jnp.int32)
    tables = jnp.asarray([[3, 5, 7], [0, 0, 0], [2, 0, 0], [9, 4, 11]],
                         jnp.int32)
    return q, f(slots, kv_heads, hd), f(slots, kv_heads, hd), k, v, tables, lengths


@pytest.mark.parametrize("heads, kv_heads", [(4, 1), (4, 2), (20, 1)])
def test_the_paged_kernel_with_fewer_key_value_heads(heads, kv_heads):
    """Reading, and writing the token's rows first (through a tile of
    the cache where a token's rows fill none): against the gather over a
    cache the rows were scattered into."""
    q, k_new, v_new, k, v, tables, lengths = _paged_inputs(heads, kv_heads)
    live = np.asarray(lengths) > 0
    for layer in (0, 1):
        want_k = _scattered(k, k_new, tables, lengths, layer)
        want_v = _scattered(v, v_new, tables, lengths, layer)
        want = paged_attention_dense(q, want_k[layer], want_v[layer],
                                     tables, lengths)
        read = paged_attention(q, want_k, want_v, tables, lengths,
                               layer=layer, interpret=True)
        np.testing.assert_allclose(np.asarray(read)[live],
                                   np.asarray(want)[live], rtol=1e-5,
                                   atol=1e-5)
        got, k2, v2 = paged_attention_write(q, k_new, v_new, k, v, tables,
                                            lengths, layer=layer,
                                            interpret=True)
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(k2), np.asarray(want_k))
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(want_v))
        assert float(jnp.abs(got[1]).max()) == 0.0     # the idle slot


def test_the_gather_with_one_key_value_head_is_the_dense_oracle(
        model_and_params):
    """``_decode_attn``'s gather arm for grouped heads against attention
    written out over the same rows."""
    from distributedmnist_tpu.models import transformer
    model, params = model_and_params
    blk = params["blocks"][1]
    slots, block, width = 3, 8, 2
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((slots, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 9, block, 1, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 9, block, 1, 16)), jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 0], [0, 0]], jnp.int32)
    lengths = jnp.asarray([13, 4, 0], jnp.int32)
    positions = jnp.maximum(lengths - 1, 0)
    live = jnp.arange(width * block)[None] < lengths[:, None]
    out = {}
    for arm in ("gather", "paged"):
        out[arm], k2, v2 = transformer._decode_attn(
            x, blk, 0, k, v, tables, lengths,
            jnp.take_along_axis(tables, (positions // block)[:, None], 1)[:, 0],
            positions % block, live, num_heads=4, scale=0.25, arm=arm,
            kv_heads=1)
    np.testing.assert_allclose(np.asarray(out["paged"][:2]),
                               np.asarray(out["gather"][:2]), rtol=1e-5,
                               atol=1e-5)
    # slot 0 by hand: 4 queries against the one head's 13 rows
    h = transformer._rms_norm(x, blk["ln1"])
    q, kk, vv = jnp.split(h @ blk["wqkv"], [64, 80], axis=-1)
    rows_k = jnp.concatenate([k2[0, 1], k2[0, 2]])[:13, 0]
    rows_v = jnp.concatenate([v2[0, 1], v2[0, 2]])[:13, 0]
    np.testing.assert_allclose(np.asarray(rows_k[12]), np.asarray(kk[0]),
                               rtol=1e-6)
    w = jax.nn.softmax(q[0].reshape(4, 16) @ rows_k.T * 0.25, axis=-1)
    want = x[0] + (w @ rows_v).reshape(64) @ blk["wo"]
    np.testing.assert_allclose(np.asarray(out["gather"][0]),
                               np.asarray(want), rtol=1e-4, atol=1e-5)


def test_grouped_heads_alone_and_mixtures_are_refused():
    with pytest.raises(ValueError, match="state-space layers"):
        get_model(ModelConfig(name="transformer", ssm_state_dim=4,
                              kv_latent_dim=8))
    grouped = get_model(ModelConfig(name="transformer", model_dim=32,
                                    num_heads=4, kv_heads=2, ffn_dim=48,
                                    attention_impl="dense"))
    assert grouped.decode_step is None
    assert not hasattr(grouped, "decode_session")
    with pytest.raises(ValueError, match="key-value heads"):
        get_model(ModelConfig(name="transformer", model_dim=32, num_heads=4,
                              kv_heads=3, ffn_dim=48))


def test_a_hybrid_with_a_key_value_head_a_query_head_decodes_its_forward():
    """No grouped heads, another epsilon, 1 attention layer in 2: the
    attention layers go through the plain block's cache-side attention
    with the block's norm."""
    model = get_model(ModelConfig(
        name="transformer", model_dim=64, num_heads=4, num_layers=4,
        vocab_size=97, seq_len=64, ffn_dim=96, ssm_state_dim=8,
        ssm_dt_rank=6, attn_layer_period=2, attn_layer_offset=1,
        attention_impl="dense", compute_dtype="float32", norm_eps=1e-5))
    params = model.init(jax.random.PRNGKey(1))
    assert model.decode_cache_shape == (2, 4, 16)
    assert params["blocks"][1]["wqkv"].shape == (64, 3, 64)
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (19,), 0, 97))
    full = model.apply(params, jnp.asarray(seq[None]))[0]
    session = model.decode_session(params, DECODE, jnp.float32)
    rows = [session.prefill(seq[:11])]
    for pos in range(11, 19):
        rows.append(session.step(int(seq[pos]), pos))
    assert _rel(jnp.stack(rows), full[10:]) < 1e-5
