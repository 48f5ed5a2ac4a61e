"""Tensor parallelism correctness: Megatron-style sharded transformer
(column-parallel qkv/MLP-in, row-parallel wo/MLP-out) must match the
dense single-device model exactly — forward, one-step update, and in
composition with data and sequence parallelism (DP×TP×SP)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import (LOSS_TOL, assert_update_parity,
                      base_config)
from distributedmnist_tpu.core.config import MeshConfig
from distributedmnist_tpu.core.mesh import make_topology
from distributedmnist_tpu.models import transformer
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.parallel.api import (build_eval_step,
                                               build_train_step,
                                               init_train_state,
                                               state_partition_specs)
from distributedmnist_tpu.train.lr_schedule import constant

LR = 0.1


def _cfg(n_replicas=1, heads=4, sp_attention="ring"):
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


def test_tp_forward_matches_dense():
    cfg = _cfg()
    model = get_model(cfg.model)
    params = model.init(jax.random.PRNGKey(0))
    toks = _tokens(cfg)["image"]
    want = transformer.apply(params, toks,
                             block=transformer.make_block(num_heads=4),
                             compute_dtype=jnp.float32)

    topo = make_topology(MeshConfig(num_replicas=1, model_parallelism=4))
    specs = transformer.param_partition_specs(2, topo.model_axis)
    sharded_params = topo.device_put_state(params, specs)
    tp_apply = model.sharded_apply_factory(None, topo.model_axis)

    fn = jax.jit(jax.shard_map(
        lambda p, t: tp_apply(p, t, None),
        mesh=topo.mesh, in_specs=(specs, P()), out_specs=P()))
    got = fn(sharded_params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _dense_update(cfg, batch):
    model = get_model(cfg.model)
    params = model.init(jax.random.PRNGKey(cfg.model.init_seed))

    def loss_fn(p):
        logits = transformer.apply(
            p, batch["image"],
            block=transformer.make_block(num_heads=cfg.model.num_heads),
            compute_dtype=jnp.float32)
        return transformer.loss_fn(logits, batch["label"])

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return loss, jax.tree.map(lambda p, g: p - LR * g, params, grads)


@pytest.mark.parametrize("n_replicas,n_model,n_seq", [
    (1, 4, 1),   # pure TP
    (2, 2, 1),   # DP × TP
    (2, 2, 2),   # DP × TP × SP — the full 3D mesh
])
def test_tp_step_matches_dense_update(n_replicas, n_model, n_seq):
    cfg = _cfg(n_replicas=n_replicas)
    batch = _tokens(cfg)
    want_loss, want_params = _dense_update(cfg, batch)

    topo = make_topology(MeshConfig(num_replicas=n_replicas,
                                    model_parallelism=n_model,
                                    seq_parallelism=n_seq))
    model = get_model(cfg.model)
    specs = state_partition_specs(model, cfg, topo)
    state = topo.device_put_state(init_train_state(model, cfg), specs)
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    gbatch = topo.device_put_batch(batch, seq_sharded=True)
    state, metrics = step_fn(state, gbatch)

    np.testing.assert_allclose(float(metrics["loss"]), float(want_loss),
                               **LOSS_TOL)  # 2e-4 under the check_rep shim
    got_full = jax.device_get(state.params)  # gathers shards
    assert_update_parity(got_full, want_params)


def test_tp_eval_step_matches_dense():
    cfg = _cfg(n_replicas=2)
    topo = make_topology(MeshConfig(num_replicas=2, model_parallelism=2))
    model = get_model(cfg.model)
    specs = state_partition_specs(model, cfg, topo)
    state = topo.device_put_state(init_train_state(model, cfg), specs)
    eval_fn = build_eval_step(model, cfg, topo)

    toks = _tokens(cfg)["image"]
    weight = np.ones(toks.shape[0], np.float32)
    correct, loss_sum, wsum = eval_fn(
        state.params, {"image": toks, "label": toks, "weight": weight})
    # dense reference
    params = model.init(jax.random.PRNGKey(cfg.model.init_seed))
    logits = model.apply(params, toks, train=False)
    c_ref, l_ref, w_ref = model.eval_metrics(logits, toks, jnp.asarray(weight))
    np.testing.assert_allclose(float(correct), float(c_ref), rtol=1e-5)
    np.testing.assert_allclose(float(loss_sum), float(l_ref), rtol=1e-4)
    np.testing.assert_allclose(float(wsum), float(w_ref), rtol=1e-6)


def test_tp_rejects_indivisible_heads():
    cfg = _cfg(heads=2)  # 2 heads cannot split over 4 TP ranks
    topo = make_topology(MeshConfig(num_replicas=1, model_parallelism=4))
    model = get_model(cfg.model)
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    specs = state_partition_specs(model, cfg, topo)
    with pytest.raises(Exception, match="divisible"):
        state = topo.device_put_state(init_train_state(model, cfg), specs)
        step_fn(state, topo.device_put_batch(_tokens(cfg), seq_sharded=True))


def test_trainer_end_to_end_3d_mesh(tmp_train_dir):
    """Full Trainer on a (replica=2, model=2, seq=2) mesh with quorum
    masks on the replica axis, checkpoint save + TP-sharded restore."""
    from distributedmnist_tpu.train.loop import Trainer

    cfg = _cfg(n_replicas=2)
    cfg = cfg.override({
        "mesh.num_replicas": 2, "mesh.model_parallelism": 2,
        "mesh.seq_parallelism": 2,
        "sync.mode": "quorum", "sync.num_replicas_to_aggregate": 1,
        "sync.straggler_profile": "lognormal",
        "train.max_steps": 12, "train.train_dir": tmp_train_dir,
        "train.log_every_steps": 6, "train.save_interval_secs": 0,
        "train.save_interval_steps": 6,
    })
    tr = Trainer(cfg)
    summary = tr.run()
    assert summary["final_step"] == 12
    assert summary["last_metrics"]["num_contributors"] == 1.0
    ev = tr.evaluate("test")
    assert np.isfinite(ev["loss"])

    tr2 = Trainer(cfg.override({"train.resume": True, "train.max_steps": 14}))
    assert tr2._start_step == 12
    assert tr2.run()["final_step"] == 14
