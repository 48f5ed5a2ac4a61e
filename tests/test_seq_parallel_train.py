"""DP×SP training correctness: the sequence-sharded train step (ring
or ulysses attention + cross-shard token-shift loss + seq-axis gradient
psum) must produce EXACTLY the update a dense single-device step would.
This is the long-context path the reference lacks entirely
(SURVEY §5.7) wired through the real product train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import base_config
from distributedmnist_tpu.core.mesh import make_topology
from distributedmnist_tpu.core.config import MeshConfig
from distributedmnist_tpu.models import transformer
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.parallel.api import (build_train_step,
                                               init_train_state)
from distributedmnist_tpu.train.lr_schedule import constant

LR = 0.1


def _cfg(sp_attention, n_replicas, n_seq, heads=4):
    return base_config(
        data={"dataset": "synthetic_lm", "batch_size": 4 * n_replicas},
        model={"name": "transformer", "compute_dtype": "float32",
               "seq_len": 32, "model_dim": 32, "num_heads": heads,
               "num_layers": 2, "vocab_size": 37,
               "attention_impl": "dense", "sp_attention": sp_attention},
        sync={"mode": "sync", "straggler_profile": "none"},
    )


def _tokens(cfg, key=0):
    b, s = cfg.data.batch_size, cfg.model.seq_len
    toks = jax.random.randint(jax.random.PRNGKey(key), (b, s), 0,
                              cfg.model.vocab_size)
    return {"image": toks, "label": toks}


def _dense_reference_update(cfg, batch):
    """Single-device: params - lr * grad(mean-over-batch dense loss)."""
    model = get_model(cfg.model)
    params = model.init(jax.random.PRNGKey(cfg.model.init_seed))

    def loss_fn(p):
        logits = transformer.apply(
            p, batch["image"],
            block=transformer.make_block(num_heads=cfg.model.num_heads),
            compute_dtype=jnp.float32)
        return transformer.loss_fn(logits, batch["label"])

    loss, grads = jax.value_and_grad(loss_fn)(params)
    new = jax.tree.map(lambda p, g: p - LR * g, params, grads)
    return loss, new


def _sp_update(cfg, batch, n_replicas, n_seq):
    topo = make_topology(MeshConfig(num_replicas=n_replicas,
                                    seq_parallelism=n_seq))
    model = get_model(cfg.model)
    state = topo.device_put_replicated(init_train_state(model, cfg))
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    gbatch = topo.device_put_batch(batch, seq_sharded=True)
    state, metrics = step_fn(state, gbatch)
    return metrics, state.params


@pytest.mark.parametrize("sp_attention,n_replicas,n_seq", [
    ("ring", 2, 4),
    ("ulysses", 2, 4),   # heads=4 divisible by n_seq=4
    ("ring", 1, 8),
])
def test_sp_step_matches_dense_update(sp_attention, n_replicas, n_seq):
    cfg = _cfg(sp_attention, n_replicas, n_seq)
    batch = _tokens(cfg)
    want_loss, want_params = _dense_reference_update(cfg, batch)
    metrics, got_params = _sp_update(cfg, batch, n_replicas, n_seq)

    # loss: mean over replicas of per-replica dense losses == global
    # dense loss (identical row counts)
    np.testing.assert_allclose(float(metrics["loss"]), float(want_loss),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.tree.leaves(got_params), jax.tree.leaves(want_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_sp_requires_capable_model():
    cfg = _cfg("ring", 2, 4)
    cfg = cfg.override({"model.name": "mnist_cnn", "model.compute_dtype":
                        "float32"})
    topo = make_topology(MeshConfig(num_replicas=2, seq_parallelism=4))
    model = get_model(cfg.model)
    with pytest.raises(ValueError, match="seq_parallelism"):
        build_train_step(model, cfg, topo, constant(LR))


def test_trainer_end_to_end_seq_parallel(tmp_train_dir):
    """Full Trainer on a (replica=2, seq=4) mesh: runs, learns, and the
    quorum discipline still applies on the replica axis."""
    from distributedmnist_tpu.train.loop import Trainer

    cfg = _cfg("ring", 2, 4)
    cfg = cfg.override({
        "mesh.num_replicas": 2, "mesh.seq_parallelism": 4,
        "sync.mode": "quorum", "sync.num_replicas_to_aggregate": 1,
        "sync.straggler_profile": "lognormal",
        "data.use_native_pipeline": True,
        "train.max_steps": 20, "train.train_dir": tmp_train_dir,
        "train.log_every_steps": 10,
    })
    tr = Trainer(cfg)
    summary = tr.run()
    assert summary["final_step"] == 20
    assert summary["last_metrics"]["num_contributors"] == 1.0
    # loss must drop from roughly ln(vocab) chance level
    assert summary["last_metrics"]["loss"] < 3.4
    ev = tr.evaluate("test")
    assert ev["num_examples"] == 256


def test_sharded_paths_refuse_dropout_models():
    """A model that consumes a dropout key must not silently train
    without dropout on the SP path (which does not thread one)."""
    import dataclasses

    cfg = _cfg("ring", 2, 4)
    topo = make_topology(MeshConfig(num_replicas=2, seq_parallelism=4))
    model = dataclasses.replace(get_model(cfg.model), uses_dropout=True)
    with pytest.raises(ValueError, match="dropout"):
        build_train_step(model, cfg, topo, constant(LR))
