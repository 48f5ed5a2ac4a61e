"""Model-family numerics tests (≙ SURVEY §2.1 "Model" row parity)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedmnist_tpu.core.config import ModelConfig
from distributedmnist_tpu.models import available, get_model
from distributedmnist_tpu.models import cnn, transformer


def test_registry_lists_families():
    assert {"mnist_cnn", "resnet20", "transformer"} <= set(available())


def test_all_registered_models_buildable():
    """Every advertised family must init+apply (regression: registry
    used to list families whose modules didn't exist)."""
    for name in available():
        cfg = ModelConfig(name=name, compute_dtype="float32",
                          num_channels=3 if name == "resnet20" else 1,
                          image_size=32 if name == "resnet20" else 28,
                          seq_len=32, model_dim=32, num_heads=2, num_layers=1)
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        x = jnp.zeros((2,) + model.input_shape, model.input_dtype)
        logits = model.apply(params, x, train=False)
        assert logits.shape[0] == 2
        assert jnp.all(jnp.isfinite(logits))


def test_every_model_exports_predictions():
    """The serving tier's model-agnostic contract: EVERY registered
    family carries a ``predictions`` export producing a per-example
    probability distribution (softmax class probs for classifiers,
    next-token distribution for the LM) — the same registry-driven
    genericity the trainer has."""
    for name in available():
        cfg = ModelConfig(name=name, compute_dtype="float32",
                          num_channels=3 if name == "resnet20" else 1,
                          image_size=32 if name == "resnet20" else 28,
                          seq_len=32, model_dim=32, num_heads=2, num_layers=1)
        model = get_model(cfg)
        assert callable(model.predictions), name
        params = model.init(jax.random.PRNGKey(0))
        x = jnp.zeros((2,) + model.input_shape, model.input_dtype)
        probs = model.predictions(model.apply(params, x, train=False))
        # one distribution per example, regardless of family
        assert probs.ndim == 2 and probs.shape[0] == 2, (name, probs.shape)
        expected_classes = (cfg.vocab_size if name == "transformer"
                            else cfg.num_classes)
        assert probs.shape[1] == expected_classes, name
        np.testing.assert_allclose(np.asarray(probs).sum(axis=-1),
                                   np.ones(2), rtol=1e-5)
        assert np.all(np.asarray(probs) >= 0), name


def test_cnn_param_shapes_and_init_constants():
    """Parity with reference init (src/mnist.py:81-101): conv1 bias 0,
    conv2/fc biases 0.1, truncated-normal weights with stddev 0.1."""
    params = cnn.init(jax.random.PRNGKey(66478))
    assert params["conv1"]["w"].shape == (5, 5, 1, 32)
    assert params["conv2"]["w"].shape == (5, 5, 32, 64)
    assert params["fc1"]["w"].shape == (7 * 7 * 64, 512)
    assert params["fc2"]["w"].shape == (512, 10)
    np.testing.assert_array_equal(np.asarray(params["conv1"]["b"]), 0.0)
    np.testing.assert_allclose(np.asarray(params["conv2"]["b"]), 0.1, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(params["fc1"]["b"]), 0.1, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(params["fc2"]["b"]), 0.1, rtol=1e-6)
    # truncated at ±2σ = ±0.2
    w = np.asarray(params["fc1"]["w"])
    assert np.abs(w).max() <= 0.2 + 1e-6
    assert 0.05 < w.std() < 0.15


def test_cnn_loss_matches_manual_xent():
    logits = jnp.array([[2.0, 1.0, 0.1], [0.5, 2.5, 0.2]])
    labels = jnp.array([0, 1])
    got = float(cnn.loss_fn(logits, labels))
    p = jax.nn.log_softmax(logits)
    want = float(-(p[0, 0] + p[1, 1]) / 2)
    assert got == pytest.approx(want, rel=1e-6)


def test_cnn_predictions_softmax_parity():
    """≙ tf.nn.softmax export (src/mnist.py:166-167): rows are proper
    distributions and exp-normalized logits."""
    logits = jnp.array([[2.0, 1.0, 0.1], [0.5, 2.5, 0.2]])
    probs = np.asarray(cnn.predictions(logits))
    np.testing.assert_allclose(probs.sum(axis=-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        probs, np.exp(logits) / np.exp(logits).sum(-1, keepdims=True),
        rtol=1e-6)


def test_cnn_accuracy():
    logits = jnp.array([[2.0, 1.0], [0.1, 3.0], [5.0, 0.0], [0.0, 1.0]])
    labels = jnp.array([0, 1, 1, 1])
    assert float(cnn.accuracy(logits, labels)) == pytest.approx(0.75)


def test_cnn_dropout_train_vs_eval():
    params = cnn.init(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 28, 28, 1))
    eval_logits = cnn.apply(params, x, train=False, compute_dtype=jnp.float32)
    k = jax.random.PRNGKey(3)
    train_logits = cnn.apply(params, x, train=True, dropout_key=k,
                             compute_dtype=jnp.float32)
    assert not np.allclose(np.asarray(eval_logits), np.asarray(train_logits))
    # dropout requires a key
    with pytest.raises(ValueError):
        cnn.apply(params, x, train=True, compute_dtype=jnp.float32)
    # deterministic given the key
    again = cnn.apply(params, x, train=True, dropout_key=k,
                      compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(train_logits), np.asarray(again))


def test_resnet20_learns_a_step():
    from distributedmnist_tpu.models import resnet
    params = resnet.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3)) * 0.3
    y = jnp.array([0, 1, 2, 3])

    def loss(p):
        return cnn.loss_fn(resnet.apply(p, x, compute_dtype=jnp.float32), y)

    l0 = float(loss(params))
    g = jax.grad(loss)(params)
    params2 = jax.tree.map(lambda p_, g_: p_ - 0.1 * g_, params, g)
    assert float(loss(params2)) < l0


def test_transformer_next_token_loss_decreases():
    params = transformer.init(jax.random.PRNGKey(0), vocab_size=17,
                              model_dim=32, num_heads=2, num_layers=1,
                              max_seq_len=16)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 17)

    def loss(p):
        logits = transformer.apply(p, toks,
                                   block=transformer.make_block(num_heads=2),
                                   compute_dtype=jnp.float32)
        return transformer.loss_fn(logits, toks)

    l0 = float(loss(params))
    g = jax.grad(loss)(params)
    params2 = jax.tree.map(lambda p_, g_: p_ - 0.5 * g_, params, g)
    assert float(loss(params2)) < l0


# ---------------------------------------------------------------------------
# the seam: a layer's kind is handed to make_block, and no forward knows it
# ---------------------------------------------------------------------------

def _gated_unit(h, blk):
    """A feed-forward no model here has: w1's columns split into a value
    and a SiLU gate, over the dense block's own w1/w2."""
    value, gate = jnp.split(h @ blk["w1"], 2, axis=-1)
    mlp = (jax.nn.silu(gate) * value) @ blk["w2"][:value.shape[-1]]
    return mlp, jnp.zeros((), jnp.float32)


def _through_the_cache(params, block, seq, want):
    """Prefill seven tokens, then the rest one by one over a paged
    cache, teacher-forced: every step's logits against the full
    forward's at that position."""
    import functools

    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    plen, total = 7, seq.shape[1]
    cache = PagedKVCache(4, 16, 4, 4, 8, max_blocks_per_seq=4,
                         dtype=jnp.float32)
    logits, ks, vs = transformer.prefill_with_kv(
        params, seq, block=block, compute_dtype=jnp.float32)
    # causal: the prompt's rows do not see what is padded after them
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    table = cache.alloc_sequence(total)
    cache.write_prompt(table, ks[:, 0], vs[:, 0], plen)
    step = jax.jit(functools.partial(
        transformer.decode_step, ffn=block.ffn, num_heads=4, block_size=4,
        compute_dtype=jnp.float32))
    slot, slots = 1, 3
    for pos in range(plen, total):
        tokens, positions, lengths = (np.zeros(slots, np.int32)
                                      for _ in range(3))
        tables = np.zeros((slots, 4), np.int32)
        tokens[slot], positions[slot], lengths[slot] = (seq[0, pos], pos,
                                                        pos + 1)
        tables[slot] = table
        got, cache.k, cache.v = step(
            params, jnp.asarray(tokens), jnp.asarray(positions), cache.k,
            cache.v, jnp.asarray(tables), jnp.asarray(lengths))
        np.testing.assert_allclose(np.asarray(got[slot]),
                                   np.asarray(want[0, pos]),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("forward", ["prefill_decode", "apply_pp",
                                     "apply_pp_1f1b", "grads_pp_1f1b"])
def test_a_new_feed_forward_goes_through_every_forward(forward):
    """The constructor is the only place that knows the layer: a gated
    unit written HERE runs through ``apply`` and through each other
    forward, and they agree (tolerances of test_decode.py and
    test_pipeline_parallel.py)."""
    from jax.sharding import PartitionSpec as P

    from conftest import LOSS_TOL, assert_update_parity
    from distributedmnist_tpu.core.config import MeshConfig
    from distributedmnist_tpu.core.mesh import make_topology

    params = transformer.init(jax.random.PRNGKey(0), vocab_size=37,
                              model_dim=32, num_heads=4, num_layers=4,
                              max_seq_len=16)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 37)
    block = transformer.make_block(num_heads=4, feed_forward=_gated_unit)
    f32 = dict(block=block, compute_dtype=jnp.float32)
    want = transformer.apply(params, toks, **f32)
    dense = transformer.apply(params, toks, compute_dtype=jnp.float32,
                              block=transformer.make_block(num_heads=4))
    assert float(jnp.max(jnp.abs(want - dense))) > 1e-3   # it is another layer

    if forward == "prefill_decode":
        _through_the_cache(params, block, toks[:1],
                           transformer.apply(params, toks[:1], **f32))
        return
    stages, chunks = (4, 1) if forward == "apply_pp" else (2, 2)
    topo = make_topology(MeshConfig(num_replicas=1,
                                    pipeline_parallelism=stages))
    staged = dict(f32, stage_axis=topo.stage_axis, num_microbatches=2)
    specs = transformer.pp_param_partition_specs(topo.stage_axis)
    if forward == "apply_pp":
        stacked = transformer.stack_block_params(params)
        fn = lambda p, t: transformer.apply_pp(p, t, **staged)
        out_specs = P()
    else:
        stacked = transformer.stack_block_params_chunked(params, stages,
                                                         chunks)
        if forward == "apply_pp_1f1b":
            fn = lambda p, t: transformer.apply_pp_1f1b(
                p, t, num_chunks=chunks, **staged)
            out_specs = P()
        else:
            fn = lambda p, t: transformer.grads_pp_1f1b(
                p, t, t, num_chunks=chunks, **staged)
            out_specs = (P(), P(), specs)
    got = jax.jit(jax.shard_map(fn, mesh=topo.mesh, in_specs=(specs, P()),
                                out_specs=out_specs))(
        topo.device_put_state(stacked, specs), toks)
    if forward != "grads_pp_1f1b":
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        return
    want_loss, want_grads = jax.value_and_grad(
        lambda p: transformer.loss_fn(transformer.apply(p, toks, **f32),
                                      toks))(params)
    loss, _, grads = got
    np.testing.assert_allclose(float(loss), float(want_loss), **LOSS_TOL)
    assert_update_parity(jax.device_get(grads),
                         transformer.stack_block_params_chunked(
                             want_grads, stages, chunks))
