"""A latent, per-token routed, sandwich-normed block through the decode
path: the absorbed step against the expanded forward in float32, the
cache of one latent and one rotated key a token, the routing outputs,
the grouped product's tile, and the decode replica on such a model
(restore into shapes, counts in the heartbeat, what ``decode_start``
says of the cache, the refusal for a model with no decode export)."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedmnist_tpu.core.config import (ConfigError, ExperimentConfig,
                                              ModelConfig, ServeConfig)
from distributedmnist_tpu.models import transformer
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.ops import moe
from distributedmnist_tpu.servesvc import kv_cache
from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

#: 4 heads of 8 + 8 (value 8) over a 16-wide latent, 8 of 32 experts held
#: from the 8th on, 4 a token, one leading dense layer of three
LATENT = dict(
    name="transformer", model_dim=64, num_heads=4, num_layers=3, seq_len=64,
    vocab_size=96, q_latent_dim=24, kv_latent_dim=16, qk_nope_dim=8,
    qk_rope_dim=8, v_head_dim=8, rope_theta=25.6e6, ffn_dim=96,
    routed_experts=32, held_experts=8, first_held_expert=8,
    experts_per_token=4, shared_experts=1, expert_ffn_dim=32,
    routed_scaling=2.5, dense_layers=1, sandwich_norm=True, norm_eps=1e-5,
    compute_dtype="float32", attention_impl="dense")
F32_TOL = 2e-5


def build(**over):
    model = get_model(ModelConfig(**{**LATENT, **over}))
    params = model.init(jax.random.PRNGKey(0))
    # norm scales away from one, so that a norm left out shows
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(1), a.shape)
        if a.ndim == 1 and a.shape[0] != 32 else a, params)
    return model, params


@pytest.fixture(scope="module")
def toy():
    model, params = build()
    seq = np.random.default_rng(0).integers(0, 96, (1, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = model.apply(params, jnp.asarray(seq))
    return model, params, seq, want


def _decode(model, params, seq, prompt, **asked):
    """Prefill ``prompt`` tokens, then the rest one at a time in slot 1
    of 3 through a paged cache. Returns the prefill's outputs and each
    step's."""
    layers, heads, widths = model.decode_cache_shape
    cache = PagedKVCache(layers, 16, 4, heads, widths, 8, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        first = model.decode_prefill(params, jnp.asarray(seq[:, :prompt]))
        table = cache.alloc_sequence(seq.shape[1])
        cache.write_prompt(table, first[1][:, 0], first[2][:, 0], prompt)
        step = jax.jit(functools.partial(model.decode_step, block_size=4,
                                         **asked))
        outs = []
        for pos in range(prompt, seq.shape[1]):
            tokens, positions, lengths = (np.zeros(3, np.int32)
                                          for _ in range(3))
            tables = np.zeros((3, 8), np.int32)
            tokens[1], positions[1], lengths[1] = seq[0, pos], pos, pos + 1
            tables[1] = table
            out = step(params, jnp.asarray(tokens), jnp.asarray(positions),
                       cache.k, cache.v, jnp.asarray(tables),
                       jnp.asarray(lengths))
            cache.k, cache.v = out[1], out[2]
            outs.append(out)
    return first, outs, cache


def test_the_absorbed_step_is_the_expanded_forward_in_float32(toy):
    model, params, seq, want = toy
    first, outs, cache = _decode(model, params, seq, prompt=16)
    np.testing.assert_array_equal(np.asarray(first[0]),
                                  np.asarray(want[:, :16]))
    peak = float(jnp.max(jnp.abs(want)))
    for i, out in enumerate(outs):
        err = float(jnp.max(jnp.abs(out[0][1] - want[0, 16 + i]))) / peak
        assert err <= F32_TOL, (i, err)
    # one row a token for all heads: a latent and a rotated key
    assert model.decode_cache_shape == (3, 1, (16, 8))
    assert cache.k.shape == (3, 16, 4, 16) and cache.v.shape == (3, 16, 4, 8)
    assert first[1].shape == (3, 1, 16, 16) and first[2].shape == (3, 1, 16, 8)


def test_a_row_stored_wider_reads_the_same(toy):
    model, params, seq, want = toy
    layers, heads, _ = model.decode_cache_shape
    _, narrow, _ = _decode(model, params, seq, prompt=20)
    # the replica stores the rotated key as wide as the device's lanes
    cache = PagedKVCache(layers, 16, 4, heads, (16, 128), 8,
                         dtype=jnp.float32)
    assert cache.v.shape == (3, 16, 4, 128)
    with jax.default_matmul_precision("highest"):
        first = model.decode_prefill(params, jnp.asarray(seq[:, :20]))
        table = cache.alloc_sequence(24)
        cache.write_prompt(table, first[1][:, 0], first[2][:, 0], 20)
        vec = lambda v: jnp.zeros((3,), jnp.int32).at[1].set(v)  # noqa: E731
        tables = jnp.zeros((3, 8), jnp.int32).at[1].set(jnp.asarray(table))
        out = model.decode_step(params, vec(int(seq[0, 20])), vec(20),
                                cache.k, cache.v, tables, vec(21),
                                block_size=4)
    # (one side jitted, the other not: float32 rounding apart)
    np.testing.assert_allclose(np.asarray(out[0][1]),
                               np.asarray(narrow[0][0][1]), atol=1e-6)
    assert not np.asarray(out[2][..., 8:]).any()    # the rest untouched


def test_sandwich_norms_on_and_off_differ(toy):
    model, params, seq, want = toy
    plain, _ = build(sandwich_norm=False)
    without = plain.apply(params, jnp.asarray(seq))
    assert float(jnp.max(jnp.abs(without - want))) > 1e-2
    # and the tree of a block without them has no such leaf
    assert "ln1_out" not in plain.init(jax.random.PRNGKey(0))["blocks"][0]
    # the epsilon is the configuration's too
    other, _ = build(norm_eps=1e-2)
    assert float(jnp.max(jnp.abs(other.apply(params, jnp.asarray(seq))
                                 - want))) > 1e-4


def test_output_norms_start_depth_scaled_and_the_residual_is_float32():
    model, _ = build()
    blk = model.init(jax.random.PRNGKey(0))["blocks"][2]
    # c / sqrt(L), L the tree's own depth: the scales a cut of a deeper
    # model starts from are the cut's
    np.testing.assert_allclose(np.asarray(blk["ln1_out"]["scale"]),
                               0.283 / np.sqrt(3), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(blk["ln2_out"]["scale"]),
                               0.432 / np.sqrt(3), rtol=1e-6)
    assert not np.asarray(blk["ln1"]["scale"] != 1).any()
    # bfloat16 products over a float32 residual: what a sublayer adds is
    # rounded once
    block = transformer.make_block(num_heads=4, residual=transformer.FLOAT32)
    x = block.residual.start(jnp.ones((1, 2, 8), jnp.bfloat16))
    assert x.dtype == jnp.float32
    assert block.residual.write(x, jnp.ones((1, 2, 8), jnp.bfloat16)
                                ).dtype == jnp.float32
    assert transformer.PLAIN.start(jnp.ones((1,), jnp.bfloat16)
                                   ).dtype == jnp.bfloat16


def test_routing_outputs_are_valid_and_change_nothing(toy):
    model, params, seq, want = toy
    plain, steps, _ = _decode(model, params, seq, prompt=16)
    asked, asked_steps, _ = _decode(model, params, seq, prompt=16,
                                    return_routing=True, return_counts=True)
    assert len(plain) == 3 and len(steps[0]) == 3
    # the prefill takes the flag on the call
    logits, _, _, routing = model.decode_prefill(
        params, jnp.asarray(seq[:, :16]), return_routing=True)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(plain[0]))
    assert routing.shape == (2, 1, 16, 4) and routing.dtype == jnp.int32
    for bare, full in zip(steps, asked_steps):
        np.testing.assert_array_equal(np.asarray(bare[0]),
                                      np.asarray(full[0]))
        ids, counts = np.asarray(full[3]), np.asarray(full[4])
        assert ids.shape == (2, 3, 4) and counts.shape == (2, 8)
        assert ((ids >= 0) & (ids < 32)).all()
        assert all(len(set(row)) == 4 for layer in ids for row in layer)
        # the pairs of all three slots (idle ones route token 0 too)
        held = ((ids >= 8) & (ids < 16)).sum(axis=(1, 2))
        np.testing.assert_array_equal(counts.sum(axis=1), held)
    # a router without a bias in its source: the leaf is zeros
    assert not np.asarray(params["blocks"][1]["router_bias"]).any()
    assert model.decode_counts
    assert not get_model(ModelConfig(
        name="transformer", attention_impl="dense")).decode_counts


@pytest.mark.parametrize("pairs, total, tile", [
    (8192 * 4, 64, 512),      # a training batch: the largest
    (64 * 8, 256, 16),        # a decode step of 64 slots: the smallest
    (2048 * 8, 256, 128), (512 * 8, 256, 32), (1, 8, 16)])
def test_the_tile_follows_the_pairs_an_expert_takes(pairs, total, tile):
    assert moe.tile_rows(pairs, total) == tile


def test_a_small_tile_drops_no_pair():
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    z = transformer.Sizes(routed_experts=16, held=(4, 4), shared_experts=0,
                          expert_ffn_dim=32, router_bias_init=0.0)
    blk = transformer._init_sized_block(keys[0], 64, 4, z, routed=True)
    # expert 5 takes every token's first place: 40 pairs on one expert,
    # more than two tiles of 16
    blk["router"] = blk["router"].at[:, 5].set(0.0)
    bias = jnp.zeros(16).at[5].set(10.0)
    h = jax.random.normal(keys[1], (1, 40, 64))
    with jax.default_matmul_precision("highest"):
        out, ids, counts, _ = moe.routed_ffn(
            h, blk["router"], bias, blk["experts"], None, total=16,
            held=(4, 4), top_k=2, scaling=1.0)
        assert moe.tile_rows(80, 16) == 16 and int(counts[1]) == 40
        flat = h.reshape(-1, 64)
        _, gates = moe.route_tokens(flat, blk["router"], bias, 2, 1.0)
        want = sum(
            jnp.sum(jnp.where(ids.reshape(-1, 2) == 4 + e, gates, 0.0),
                    axis=-1, keepdims=True)
            * moe.gated_unit(flat, *(blk["experts"][k][e] for k in
                                     ("w_gate", "w_up", "w_down")))
            for e in range(4))
    assert float(jnp.max(jnp.abs(out.reshape(-1, 64) - want))) <= 1e-5


def test_the_paged_step_is_the_dense_step_in_float32(toy):
    """``attention_kernel="paged"`` no longer refuses a latent cache:
    the whole step through the latent kernel (interpreted here; rows
    written inside its call) against the same steps through the gather
    and against the expanded forward, within the file's tolerance; the
    two caches equal to the bit outside the null block."""
    model, params, seq, want = toy
    _, dense, dense_cache = _decode(model, params, seq, prompt=16,
                                    attention_kernel="dense")
    _, paged, paged_cache = _decode(model, params, seq, prompt=16,
                                    attention_kernel="paged")
    peak = float(jnp.max(jnp.abs(want)))
    for i, (d, p) in enumerate(zip(dense, paged)):
        assert float(jnp.max(jnp.abs(p[0][1] - d[0][1]))) / peak <= F32_TOL
        err = float(jnp.max(jnp.abs(p[0][1] - want[0, 16 + i]))) / peak
        assert err <= F32_TOL, (i, err)
    for got, held in ((paged_cache.k, dense_cache.k),
                      (paged_cache.v, dense_cache.v)):
        np.testing.assert_allclose(np.asarray(got[:, 1:]),
                                   np.asarray(held[:, 1:]), atol=1e-6)
    # an idle slot's row went to the null block by the scatter alone
    assert not np.asarray(paged_cache.k[:, 0]).any()


@pytest.mark.parametrize("block_size, heads", [(16, 128), (128, 32)])
def test_a_bfloat16_step_takes_either_arm(block_size, heads):
    """One whole ``decode_step`` of a latent toy in bfloat16 at the two
    cells' pages and heads, three slots at ragged lengths (one idle, one
    at a page's last row): the paged arm's logits within the kernels'
    tolerance of the gather arm's, the row it wrote the scatter's to the
    bit, nothing else of either array touched."""
    model = get_model(ModelConfig(**{
        **LATENT, "num_heads": heads, "model_dim": 2 * heads,
        "num_layers": 2, "routed_experts": 0, "held_experts": 0,
        "experts_per_token": 0, "shared_experts": 0, "dense_layers": 0,
        "seq_len": 4 * block_size, "compute_dtype": "bfloat16"}))
    params = model.init(jax.random.PRNGKey(0))
    layers, one, widths = model.decode_cache_shape
    rng = np.random.default_rng(0)
    shapes = kv_cache.cache_shapes(layers, 9, block_size, one, (16, 128))
    k, v = (jnp.zeros(shape, jnp.bfloat16).at[:, 1:, :, :width].set(
        jnp.asarray(rng.standard_normal((*shape[:3], width)) * 0.5,
                    jnp.bfloat16)[:, 1:])
            for shape, width in zip(shapes, widths))
    lengths = jnp.asarray([block_size + 3, 0, 2 * block_size], jnp.int32)
    tables = jnp.asarray([[1, 2, 0, 0], [0, 0, 0, 0], [3, 4, 0, 0]],
                         jnp.int32)
    tokens = jnp.asarray([5, 0, 7], jnp.int32)

    def step(kernel):
        return jax.jit(functools.partial(
            model.decode_step, block_size=block_size,
            attention_kernel=kernel))(
                params, tokens, jnp.maximum(lengths - 1, 0), k, v, tables,
                lengths)

    dense, paged = step("dense"), step("paged")
    live = np.asarray(lengths) > 0
    want, got = (np.asarray(out[0], np.float32)[live]
                 for out in (dense, paged))
    assert np.abs(got - want).max() <= 2e-2 * np.abs(want).max()
    for at in (1, 2):
        held, wrote = (np.asarray(out[at][:, 1:], np.float32)
                       for out in (dense, paged))
        np.testing.assert_array_equal(wrote[0], held[0])   # layer 0's rows
        # deeper rows are computed from what the layer before read
        np.testing.assert_allclose(wrote, held, atol=3e-2)
        before = np.asarray((k, v)[at - 1][:, 1:], np.float32)
        assert (wrote != before).any(axis=-1).sum() == 2 * layers


def test_cache_shapes_of_a_pair_put_the_positions_second_minor():
    assert kv_cache.cache_shapes(5, 9, 16, 1, (512, 64)) == (
        (5, 9, 16, 512), (5, 9, 16, 64))
    assert kv_cache.cache_shapes(2, 9, 16, 4, 8) == ((2, 9, 16, 4, 8),) * 2
    # on this backend the last dimension is minor: stored as it is
    assert kv_cache.stored_head_dim((5, 9, 16, 64), jnp.bfloat16) == 64


# -- the replica -----------------------------------------------------------

def _experiment(tmp_path, model: dict, **decode) -> ExperimentConfig:
    return ExperimentConfig.from_dict({
        "model": model,
        "decode": {"decode_slots": 3, "block_size": 4, "num_blocks": 33,
                   "max_prompt_len": 16, "max_new_tokens": 8, **decode},
        "train": {"train_dir": str(tmp_path / "publish"), "seed": 0}})


def _published(cfg):
    from distributedmnist_tpu.core.config import effective_model_config
    from distributedmnist_tpu.parallel.api import init_train_state
    from distributedmnist_tpu.train.checkpoint import save_checkpoint
    model = get_model(effective_model_config(cfg, serving=True))
    state = init_train_state(model, cfg)
    save_checkpoint(cfg.train.train_dir, state, 0,
                    extra={"config": cfg.to_dict()})
    return model, state


def test_the_replica_serves_a_latent_routed_model(tmp_path):
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    cfg = _experiment(tmp_path, dict(LATENT))
    model, state = _published(cfg)
    # the served experiment's optimizer has no slots: weights held once
    assert state.momentum is None
    rep = DecodeReplica(cfg.train.train_dir, serve_dir=tmp_path / "serve",
                        scfg=ServeConfig(poll_secs=0.05), dcfg=cfg.decode,
                        cfg=cfg)
    # restored into shapes: the replica keeps no second copy of the tree
    assert all(isinstance(leaf, jax.ShapeDtypeStruct)
               for leaf in jax.tree.leaves(rep.template.params))
    rep.start()
    try:
        client = ServeClient([("127.0.0.1", rep.bound_port)],
                             deadline_s=60.0)
        prompt = [5, 17, 3, 80, 41, 2, 9]
        out = client.generate(prompt, request_id=1, max_tokens=6)
        assert out["status"] == "ok" and len(out["tokens"]) == 6
        # greedy through the cache is the full forward's argmax
        seq = list(prompt)
        with jax.default_matmul_precision("highest"):
            for tok in out["tokens"]:
                logits = model.apply(state.params, jnp.asarray([seq]))
                assert int(jnp.argmax(logits[0, -1])) == tok
                seq.append(tok)
        # the counts stay on the device until a heartbeat reads them
        assert isinstance(rep._expert_pairs, jax.Array)
        assert rep._routing_fields()["expert_pairs_held"] >= 0
    finally:
        rep.stop()
    records = [json.loads(line) for line in
               (tmp_path / "serve" / "serve_log.jsonl").read_text()
               .splitlines()]
    start = next(r for r in records if r.get("action") == "decode_start")
    assert start["cache_arrays"] == [[3, 33, 4, 16], [3, 33, 4, 8]]
    # one row a token for all heads: the block's own gather, no kernel
    assert set(start["attention_arm"]) == {"gather"}
    assert set(start["paged_calls"]) == {0}
    # float32 here: 3 layers x (16 + 8) x 4 bytes a cached token
    assert start["cache_row_bytes"] == 3 * 24 * 4
    beats = [json.loads(line) for line in
             (tmp_path / "serve" / "train_log.jsonl").read_text()
             .splitlines()]
    routed = [b for b in beats if "experts_touched" in b]
    assert routed and all(
        0 <= b["experts_touched"] <= 2 * 8
        and b["experts_touched"] <= b["expert_pairs_held"] <= 2 * 3 * 4
        for b in routed)


def test_the_plain_replica_says_nothing_of_experts(tmp_path):
    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    cfg = _experiment(tmp_path, {
        "name": "transformer", "seq_len": 64, "model_dim": 32,
        "num_heads": 4, "num_layers": 1, "vocab_size": 32,
        "compute_dtype": "float32", "attention_impl": "dense"})
    _published(cfg)
    rep = DecodeReplica(cfg.train.train_dir, serve_dir=tmp_path / "serve",
                        dcfg=cfg.decode, cfg=cfg)
    assert "expert_pairs_held" not in rep._pressure_fields()
    assert rep.cache.k.shape == rep.cache.v.shape == (1, 33, 4, 4, 8)


@pytest.mark.parametrize("model, missing", [
    ({"num_experts": 4}, "capacity routing"),
    ({"residual_streams": 2, "kv_latent_dim": 16, "q_latent_dim": 24,
      "qk_nope_dim": 8, "qk_rope_dim": 8, "v_head_dim": 8},
     "more than one residual stream"),
    ({"ffn_dim": 96}, "does not attend through a latent")])
def test_the_refusal_says_what_has_no_decode_export(tmp_path, model, missing):
    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    cfg = _experiment(tmp_path, {
        "name": "transformer", "seq_len": 64, "model_dim": 64,
        "num_heads": 4, "num_layers": 1, "vocab_size": 32,
        "compute_dtype": "float32", "attention_impl": "dense", **model})
    with pytest.raises(ConfigError, match=missing) as refused:
        DecodeReplica(cfg.train.train_dir, serve_dir=tmp_path / "serve",
                      dcfg=cfg.decode, cfg=cfg)
    assert "MoE and" not in str(refused.value)
