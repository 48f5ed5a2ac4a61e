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


# -- delta-rule layers beside latent attention and routed experts (PR 45) ----

LING = {
    "arch": "bailing_hybrid", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 7,
    "layer_group_size": 3, "vocab_size": 97, "intermediate_size": 96,
    "first_k_dense_replace": 1, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "q_lora_rank": None,
    "rope_theta": 6000000, "rope_scaling": None, "rms_norm_eps": 1e-6,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
    "num_experts": 4, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "n_group": 8, "topk_group": 4, "routed_scaling_factor": 2.5,
    "score_function": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "moe_router_enable_expert_bias": True,
    "num_nextn_predict_layers": 0, "num_kv_heads_for_linear_attn": 0,
    "group_norm_size": 1, "linear_silu": True, "use_qk_norm": True,
    "kda_safe_gate": True, "kda_lower_bound": -5, "no_kda_lora": True,
    "use_kda_lora": False, "short_conv_kernel_size": 4,
    "gated_attention_proj_granularity_type": "head_wise",
    "use_mla_nope": False, "use_bias": False, "use_qkv_bias": False,
    "use_nGPT": False, "value_norm": False, "up_proj_norm": False,
    "scale_router_input": False,
    "expert_swiglu_limit_list": [0] * 7,
    "share_expert_swiglu_limit_list": [0] * 7,
    "published": {"num_experts": 32},
    "assumed": {"seq_len": 64, "first_held_expert": 0,
                "router_bias_rate": 0.001},
    "model_assumed": {"attention_impl": "dense", "compute_dtype": "float32"}}


@pytest.fixture(scope="module")
def ling():
    arch = cell_lib.load_arch(LING)
    model = get_model(ModelConfig(**arch.model_section(LING)))
    params = model.init(jax.random.PRNGKey(1))
    # norm scales away from one: a norm left out shows; matrices at the
    # inverse root of this toy's width, not the published one's
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(path)), a.shape)
        if getattr(path[-1], "key", None) == "scale" else a, params)
    params["blocks"] = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 4 if a.ndim >= 2 and getattr(
            path[-1], "key", None) != "conv_w" else a, params["blocks"])
    return arch, model, params


def test_the_delta_rule_pattern_is_read_from_the_model_section(ling):
    arch, model, params = ling
    kinds = ["w_qkv" in blk for blk in params["blocks"]]
    assert kinds == [not arch.attends(LING, i) for i in range(7)]
    assert kinds == [True, True, False, True, True, False, True]
    assert ["router" in blk for blk in params["blocks"]] == [False] + [True] * 6
    assert model.decode_cache_shape == (2, 1, (32, 8))
    assert model.decode_state_shape == (5, (4, 16), 16, 3, 192)
    assert "wq" in params["blocks"][2] and "wq_a" not in params["blocks"][2]
    assert params["blocks"][2]["w_hgate"].shape == (64, 4)
    assert "head" in params and "pos" not in params
    assert sum(a.size for a in jax.tree.leaves(params)) == arch.param_count(
        LING)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_the_delta_rule_models_apply_is_the_reference(ling, impl):
    arch, _, params = ling
    model = get_model(ModelConfig(**{**arch.model_section(LING),
                                     "attention_impl": impl}))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 23), 0, 97)
    with jax.default_matmul_precision("highest"):
        got, aux = model.apply(params, tokens, return_aux=True)
        want = arch.logits(params, tokens, LING, routing=aux["routing"])
        slack = arch.routing_slack(params, tokens, LING, aux["routing"])
    assert aux["routing"].shape == (6, 2, 23, 4)
    assert _rel(got, want) < 2e-5
    # the program's choices are the reference's own, group limit and all
    assert float(slack.max()) == 0.0
    free = arch.logits(params, tokens, LING)
    assert _rel(got, free) < 2e-5


@pytest.mark.parametrize("asked", [False, True],
                         ids=["as_the_loop_calls_it", "asked_for_routing"])
def test_delta_rule_prefill_then_steps_through_the_session_is_the_reference(
        ling, asked):
    """Prefill of 11 in a bucket of 16, then eight teacher-forced steps,
    logits against the reference's full forward under the program's own
    choices; asked for its routing the session returns the same logits to
    the bit and leaves the state as the unasked step left it."""
    arch, model, params = ling
    session = model.decode_session(params, DECODE, jnp.float32)
    assert session.said["state_arrays"] == [[3, 4, 16, 16], [3, 3, 192]]
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (19,), 0, 97))
    with jax.default_matmul_precision("highest"):
        rows = [session.prefill(seq[:11])]
        again, routing = session.prefill(seq[:11], return_routing=True)
        chosen, diffs = [routing[:, None]], [jnp.abs(again - rows[0]).max()]
        assert routing.shape == (6, 11, 4)
        for pos in range(11, 19):
            rows.append(session.step(int(seq[pos]), pos))
            if asked:
                again, picked = session.step(int(seq[pos]), pos,
                                             return_routing=True)
                diffs.append(jnp.abs(again - rows[-1]).max())
            else:
                _, picked = session.step(int(seq[pos]), pos,
                                         return_routing=True)
            assert picked.shape == (6, 4)
            chosen.append(picked[:, None, None])
        forced = jnp.concatenate(chosen, axis=2)
        want = arch.logits(params, jnp.asarray(seq[None]), LING, last=9,
                           routing=forced)[0]
        slack = arch.routing_slack(params, jnp.asarray(seq[None]), LING,
                                   forced)
    assert _rel(jnp.stack(rows), want) < 2e-5
    assert float(max(diffs)) == 0.0               # routing_flag_diff
    assert float(slack.max()) == 0.0
    # a second prompt in the same slot starts from nothing of the first
    other = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (7,), 0, 97))
    fresh = model.decode_session(params, DECODE, jnp.float32)
    assert _rel(session.prefill(other), fresh.prefill(other)) < 1e-6


@pytest.fixture(scope="module")
def cache_free(ling):
    """A sequence of 27 tokens and the cache-free forward's logits at
    its last 17 positions: what prefill of 11 then 16 steps answer."""
    _, model, params = ling
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (27,), 0, 97))
    with jax.default_matmul_precision("highest"):
        return seq, model.apply(params, jnp.asarray(seq[None]))[0, 10:]


@pytest.mark.parametrize("arm", ["xla", "kernel"])
def test_sixteen_delta_rule_steps_are_the_cache_free_forward_on_either_arm(
        ling, cache_free, arm, monkeypatch):
    """Prefill of 11, then 16 teacher-forced steps whose matrix states
    are advanced by the XLA step, or (the asking function patched) by the
    kernel that holds them in VMEM, interpreted here: the logits of the
    cache-free forward, to the same tolerance."""
    from distributedmnist_tpu.ops import kda
    _, model, params = ling
    seq, want = cache_free
    asked = []
    monkeypatch.setattr(kda, "state_arm", lambda *a: asked.append(a) or arm)
    session = model.decode_session(params, DECODE, jnp.float32)
    with jax.default_matmul_precision("highest"):
        rows = [session.prefill(seq[:11])] + [
            session.step(int(seq[pos]), pos) for pos in range(11, 27)]
    assert ((3, 4, 16, 16), jnp.float32) in asked
    assert _rel(jnp.stack(rows), want) < 2e-5


def test_sixteen_shares_add_up_to_the_uncut_layer_of_the_reference():
    """What ties the share to the model: the routed parts of the sixteen
    chips' shares (2 experts each of 32 under the group limit) plus the
    shared expert once are the reference's uncut layer."""
    from distributedmnist_tpu.models import transformer
    from distributedmnist_tpu.ops import moe
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    z = transformer.Sizes(routed_experts=32, held=(0, 32), shared_experts=1,
                          expert_ffn_dim=32)
    blk = transformer._init_sized_block(keys[0], 64, 4, z, routed=True)
    blk["router"] = blk["router"] * 10     # scores wide enough apart
    h = jax.random.normal(keys[1], (2, 48, 64))
    uncut = {**LING, "num_experts": 32}
    arch = cell_lib.load_arch(uncut)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([arch._routed(seq, blk, uncut, None)[0]
                          for seq in h])
        shared = moe.gated_unit(h, **blk["shared"])
        total, pairs = shared, 0
        for first in range(0, 32, 2):
            held = jax.tree.map(lambda w: w[first:first + 2], blk["experts"])
            out, ids, counts, _ = moe.routed_ffn(
                h, blk["router"], blk["router_bias"], held, blk["shared"],
                total=32, held=(first, 2), top_k=4, scaling=2.5, n_group=8,
                topk_group=4)
            # the shared expert is in every share: counted once
            total, pairs = total + (out - shared), pairs + int(counts.sum())
            # each share is the reference's share of the same layer
            share = jnp.stack([arch._routed(
                seq, {**blk, "experts": held},
                {**uncut, "num_experts": 2,
                 "assumed": {**uncut["assumed"], "first_held_expert": first}},
                None)[0] for seq in h])
            assert _rel(out, share) < 2e-5
    assert pairs == 2 * 48 * 4                      # every pair, once
    assert _rel(total, want) < 2e-5
    # a token's experts lie in at most four groups of four
    assert int(jnp.max(jnp.sum(jnp.any(
        (ids // 4)[..., None] == jnp.arange(8), axis=-2), axis=-1))) <= 4


def test_route_tokens_with_one_group_is_todays_to_the_bit():
    from distributedmnist_tpu.ops import moe
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(keys[0], (64, 48))
    w = jax.random.normal(keys[1], (48, 32))
    bias = jax.random.normal(keys[2], (32,)) * 0.03

    def today(x, router_w, bias, top_k, scaling):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, ids = jax.lax.top_k(scores + jax.lax.stop_gradient(
            bias.astype(jnp.float32)), top_k)
        chosen = jnp.take_along_axis(scores, ids, axis=-1)
        gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
        return ids.astype(jnp.int32), gates * scaling

    for groups in ({}, {"n_group": 1, "topk_group": 1}):
        ids, gates = moe.route_tokens(x, w, bias, 4, 2.5, **groups)
        want_ids, want_gates = today(x, w, bias, 4, 2.5)
        assert bool((ids == want_ids).all())
        assert float(jnp.abs(gates - want_gates).max()) == 0.0
    # and one program: the jaxprs are the same, equation for equation
    mine = jax.make_jaxpr(lambda *a: moe.route_tokens(*a, 4, 2.5))(x, w, bias)
    theirs = jax.make_jaxpr(lambda *a: today(*a, 4, 2.5))(x, w, bias)
    assert str(mine) == str(theirs)


def test_route_tokens_under_the_group_limit_is_the_references_selection():
    from distributedmnist_tpu.ops import moe
    arch = cell_lib.load_arch(LING)
    keys = jax.random.split(jax.random.PRNGKey(12), 3)
    x = jax.random.normal(keys[0], (96, 64))
    blk = {"router": jax.random.normal(keys[1], (64, 32)) * 0.3,
           "router_bias": jax.random.normal(keys[2], (32,)) * 0.03,
           "experts": {k: jnp.zeros((4, *s)) for k, s in (
               ("w_gate", (64, 8)), ("w_up", (64, 8)), ("w_down", (8, 64)))}}
    with jax.default_matmul_precision("highest"):
        ids, gates = moe.route_tokens(x, blk["router"], blk["router_bias"],
                                      4, 2.5, n_group=8, topk_group=4)
        _, own, slack = arch._routed(x, blk, LING, None)
        _, _, forced_slack = arch._routed(x, blk, LING, ids)
        loose, _ = moe.route_tokens(x, blk["router"], blk["router_bias"],
                                    4, 2.5)
        _, _, loose_slack = arch._routed(x, blk, LING, loose)
    assert bool((jnp.sort(ids, -1) == jnp.sort(own, -1)).all())
    assert float(slack.max()) == 0.0 and float(forced_slack.max()) == 0.0
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-5)
    # without the limit the best 4 of all 32 leave the 4 best groups at
    # most positions, and the slack that knows the limit says by how much
    differs = (jnp.sort(loose, -1) != jnp.sort(own, -1)).any(-1)
    assert 0.3 < float(differs.mean())
    assert float(loose_slack.max()) > 0.25
    assert bool(((loose_slack > 0) == differs).all())


def test_what_is_still_refused_says_so():
    with pytest.raises(ValueError, match="two mixers"):
        get_model(ModelConfig(name="transformer", ssm_state_dim=4,
                              kda_head_dim=8))
    with pytest.raises(ValueError, match="delta-rule layers"):
        get_model(ModelConfig(name="transformer", kda_head_dim=8,
                              sandwich_norm=True))
    with pytest.raises(ValueError, match="delta-rule layers"):
        get_model(ModelConfig(name="transformer", kda_head_dim=8,
                              residual_streams=4))
    with pytest.raises(ValueError, match="attn_head_gate"):
        get_model(ModelConfig(name="transformer", attn_head_gate=True))
    with pytest.raises(ValueError, match="not a group limit"):
        get_model(ModelConfig(name="transformer", routed_experts=30,
                              held_experts=30, experts_per_token=2,
                              expert_ffn_dim=8, router_groups=4,
                              router_topk_groups=2))
    model = get_model(ModelConfig(**cell_lib.load_arch(LING).model_section(
        LING)))
    with pytest.raises(NotImplementedError, match="delta-rule"):
        model.sharded_apply_factory(None, "model")
