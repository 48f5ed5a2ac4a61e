"""Pipeline parallelism correctness: the GPipe-style microbatch
pipeline over the stage axis must match the dense single-device
transformer exactly — forward and one-step update — and compose with
data parallelism through the real Trainer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import (LOSS_TOL, assert_update_parity,
                      base_config)
from distributedmnist_tpu.core.config import MeshConfig
from distributedmnist_tpu.core.mesh import make_topology
from distributedmnist_tpu.models import transformer
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.ops.pipeline import pipeline_apply
from distributedmnist_tpu.parallel.api import (build_train_step,
                                               init_train_state,
                                               state_partition_specs)
from distributedmnist_tpu.train.lr_schedule import constant

LR = 0.1


def test_pipeline_apply_identity_stages():
    """A pipeline of elementwise stage functions == composing them."""
    topo = make_topology(MeshConfig(num_replicas=1, pipeline_parallelism=8))
    axis = topo.stage_axis
    micro = jnp.arange(4 * 2 * 3, dtype=jnp.float32).reshape(4, 2, 3)

    def fn(mb):
        return pipeline_apply(lambda x: x * 2.0 + 1.0, mb, axis)

    out = jax.jit(jax.shard_map(fn, mesh=topo.mesh,
                                in_specs=P(), out_specs=P()))(micro)
    want = micro
    for _ in range(8):
        want = want * 2.0 + 1.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


def _cfg(n_replicas=1, layers=4):
    return base_config(
        data={"dataset": "synthetic_lm", "batch_size": 8 * n_replicas},
        model={"name": "transformer", "compute_dtype": "float32",
               "seq_len": 16, "model_dim": 32, "num_heads": 4,
               "num_layers": layers, "vocab_size": 37,
               "attention_impl": "dense"},
        sync={"mode": "sync", "straggler_profile": "none"},
    )


def _tokens(cfg, key=0):
    b, s = cfg.data.batch_size, cfg.model.seq_len
    toks = jax.random.randint(jax.random.PRNGKey(key), (b, s), 0,
                              cfg.model.vocab_size)
    return {"image": toks, "label": toks}


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


@pytest.mark.parametrize("n_replicas,n_stage,n_model,microbatches", [
    (1, 4, 1, 4),
    (2, 4, 1, 2),   # DP × PP
    (1, 2, 1, 1),   # single microbatch (pure layer split)
    (2, 2, 2, 2),   # DP × PP × TP: stage outermost, Megatron inside
    (1, 2, 4, 2),   # PP × wide TP
])
def test_pp_step_matches_dense_update(n_replicas, n_stage, n_model,
                                      microbatches):
    cfg = _cfg(n_replicas=n_replicas)
    cfg = cfg.override({"mesh.num_replicas": n_replicas,
                        "mesh.pipeline_parallelism": n_stage,
                        "mesh.model_parallelism": n_model,
                        "mesh.pipeline_microbatches": microbatches})
    batch = _tokens(cfg)
    want_loss, want_params = _dense_update(cfg, batch)

    topo = make_topology(cfg.mesh)
    model = get_model(cfg.model)
    specs = state_partition_specs(model, cfg, topo)
    state = topo.device_put_state(init_train_state(model, cfg, topo), specs)
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    state, metrics = step_fn(state, topo.device_put_batch(batch))

    np.testing.assert_allclose(float(metrics["loss"]), float(want_loss),
                               **LOSS_TOL)  # 2e-4 under the check_rep shim
    got = jax.device_get(state.params)
    want_stacked = transformer.stack_block_params(want_params)
    assert_update_parity(got, want_stacked)


@pytest.mark.parametrize("n_replicas,n_stage,n_seq,microbatches", [
    (2, 2, 2, 2),   # DP × PP × SP (ring attention inside the pipeline)
    (1, 2, 4, 2),   # PP × wide SP
])
def test_pp_sp_step_matches_dense_update(n_replicas, n_stage, n_seq,
                                         microbatches):
    """PP×SP: the seq axis shards tokens through the pipeline stages
    (ring attention collectives run lockstep inside the pipeline scan)
    and the partial SP loss psums back to the dense loss exactly."""
    cfg = _cfg(n_replicas=n_replicas)
    cfg = cfg.override({"mesh.num_replicas": n_replicas,
                        "mesh.pipeline_parallelism": n_stage,
                        "mesh.seq_parallelism": n_seq,
                        "mesh.pipeline_microbatches": microbatches})
    batch = _tokens(cfg)
    want_loss, want_params = _dense_update(cfg, batch)

    topo = make_topology(cfg.mesh)
    model = get_model(cfg.model)
    specs = state_partition_specs(model, cfg, topo)
    state = topo.device_put_state(init_train_state(model, cfg, topo), specs)
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    state, metrics = step_fn(state, topo.device_put_batch(batch,
                                                          seq_sharded=True))

    np.testing.assert_allclose(float(metrics["loss"]), float(want_loss),
                               **LOSS_TOL)  # 2e-4 under the check_rep shim
    got = jax.device_get(state.params)
    want_stacked = transformer.stack_block_params(want_params)
    assert_update_parity(got, want_stacked)


def test_trainer_end_to_end_dp_pp(tmp_train_dir):
    """Full Trainer on (replica=2, stage=4): quorum on the replica
    axis, async checkpointing, resume with stacked params."""
    from distributedmnist_tpu.train.loop import Trainer

    cfg = _cfg(n_replicas=2)
    cfg = cfg.override({
        "mesh.num_replicas": 2, "mesh.pipeline_parallelism": 4,
        "mesh.pipeline_microbatches": 2,
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


# ---------------------------------------------------------------------------
# Interleaved 1F1B schedule
# ---------------------------------------------------------------------------

def test_1f1b_schedule_valid_and_fewer_idle_ticks():
    """The measured bubble comparison: at M ≥ 2S with v ≥ 2 virtual
    chunks, the fused 1F1B schedule must have FEWER idle chunk-slots
    than GPipe's 2·S·(S−1)·v (GPipe's 2(S−1) stage-work bubble, spread
    over v chunk-works per stage-work)."""
    from distributedmnist_tpu.ops.pipeline import make_1f1b_schedule

    for S, v, M in [(2, 2, 4), (2, 2, 8), (4, 2, 8), (4, 2, 16),
                    (2, 3, 12)]:
        tbl = make_1f1b_schedule(S, v, M)
        gpipe_idle = 2 * S * (S - 1) * v
        assert tbl["idle_slots"] < gpipe_idle, (S, v, M, tbl["idle_slots"])
        # wall comparison in chunk-works: T single-work ticks vs
        # GPipe's 2(M+S-1) stage-ticks of v chunk-works each
        assert tbl["ticks"] < 2 * (M + S - 1) * v, (S, v, M)
        # validity: every (mb, chunk) forwarded + backwarded exactly once
        kind, slot, mb = tbl["kind"], tbl["slot"], tbl["mb"]
        f_seen, b_seen = set(), set()
        for t in range(tbl["ticks"]):
            for d in range(S):
                c = slot[t, d] * S + d
                if kind[t, d] in (1, 2):
                    f_seen.add((mb[t, d], c))
                elif kind[t, d] == 3:
                    assert (mb[t, d], c) in f_seen  # B after own F
                    b_seen.add((mb[t, d], c))
        assert len(f_seen) == len(b_seen) == M * S * v
    # v=1 (non-interleaved): no worse than GPipe
    tbl = make_1f1b_schedule(4, 1, 8)
    assert tbl["idle_slots"] <= 2 * 4 * 3 * 1


@pytest.mark.parametrize("n_replicas,n_stage,chunks,microbatches,layers", [
    (1, 2, 2, 4, 4),    # S=2, v=2: the canonical interleaved shape
    (2, 2, 2, 2, 4),    # DP × interleaved 1F1B
    (1, 4, 1, 4, 4),    # v=1: plain (non-interleaved) 1F1B
])
def test_1f1b_step_matches_dense_update(n_replicas, n_stage, chunks,
                                        microbatches, layers):
    """Gold parity: the fused-schedule training step — explicit
    recompute-vjp backward, interleaved chunk placement, banked
    embedding cotangents, tied-head gradient assembly — must reproduce
    the dense single-device update exactly (same bar as the GPipe
    tests above)."""
    cfg = _cfg(n_replicas=n_replicas, layers=layers)
    cfg = cfg.override({"mesh.num_replicas": n_replicas,
                        "mesh.pipeline_parallelism": n_stage,
                        "mesh.pipeline_microbatches": microbatches,
                        "mesh.pipeline_schedule": "1f1b",
                        "mesh.pipeline_chunks": chunks})
    batch = _tokens(cfg)
    want_loss, want_params = _dense_update(cfg, batch)

    topo = make_topology(cfg.mesh)
    model = get_model(cfg.model)
    specs = state_partition_specs(model, cfg, topo)
    state = topo.device_put_state(init_train_state(model, cfg, topo), specs)
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    state, metrics = step_fn(state, topo.device_put_batch(batch))

    np.testing.assert_allclose(float(metrics["loss"]), float(want_loss),
                               **LOSS_TOL)  # 2e-4 under the check_rep shim
    assert 0.0 <= float(metrics["train_acc"]) <= 1.0
    got = jax.device_get(state.params)
    want_stacked = transformer.stack_block_params_chunked(
        want_params, n_stage, chunks)
    assert_update_parity(got, want_stacked)


def test_resume_refuses_cross_schedule_layout(tmp_train_dir):
    """A gpipe checkpoint must not restore into a 1f1b run: the two
    stacked layouts shape-match but order layers differently, so a
    silent restore would permute the model."""
    from distributedmnist_tpu.train.loop import Trainer

    base = _cfg(n_replicas=2).override({
        "mesh.num_replicas": 2, "mesh.pipeline_parallelism": 2,
        "mesh.pipeline_microbatches": 2,
        "train.max_steps": 2, "train.train_dir": tmp_train_dir,
        "train.log_every_steps": 2, "train.save_interval_secs": 0,
        "train.save_interval_steps": 2,
    })
    Trainer(base).run()
    with pytest.raises(ValueError, match="pipeline layout"):
        Trainer(base.override({"mesh.pipeline_schedule": "1f1b",
                               "mesh.pipeline_chunks": 2,
                               "train.max_steps": 4}))


@pytest.mark.parametrize("n_replicas,n_stage,n_model,chunks,microbatches", [
    (2, 2, 2, 2, 2),    # DP × 1F1B × TP
    (1, 2, 4, 2, 4),    # 1F1B × wide TP
])
def test_1f1b_tp_step_matches_dense_update(n_replicas, n_stage, n_model,
                                           chunks, microbatches):
    """Gold parity for 1F1B × tensor parallelism: the Megatron
    row-parallel psums (and the AD-inserted psums for TP-replicated
    leaves) execute inside the engine's stage-varying switch branches —
    legal because every model-axis peer group shares one stage
    coordinate and so takes the same branch each tick."""
    cfg = _cfg(n_replicas=n_replicas)
    cfg = cfg.override({"mesh.num_replicas": n_replicas,
                        "mesh.pipeline_parallelism": n_stage,
                        "mesh.model_parallelism": n_model,
                        "mesh.pipeline_microbatches": microbatches,
                        "mesh.pipeline_schedule": "1f1b",
                        "mesh.pipeline_chunks": chunks})
    batch = _tokens(cfg)
    want_loss, want_params = _dense_update(cfg, batch)

    topo = make_topology(cfg.mesh)
    model = get_model(cfg.model)
    specs = state_partition_specs(model, cfg, topo)
    state = topo.device_put_state(init_train_state(model, cfg, topo), specs)
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    state, metrics = step_fn(state, topo.device_put_batch(batch))

    np.testing.assert_allclose(float(metrics["loss"]), float(want_loss),
                               **LOSS_TOL)  # 2e-4 under the check_rep shim
    got = jax.device_get(state.params)
    want_stacked = transformer.stack_block_params_chunked(
        want_params, n_stage, chunks)
    assert_update_parity(got, want_stacked)


@pytest.mark.parametrize("n_replicas,n_stage,n_seq,chunks,microbatches", [
    (2, 2, 2, 2, 2),    # DP × 1F1B × SP (Ulysses attention in the chunks)
    (1, 2, 4, 2, 4),    # 1F1B × wide SP
])
def test_1f1b_sp_step_matches_dense_update(n_replicas, n_stage, n_seq,
                                           chunks, microbatches):
    """Gold parity for 1F1B × sequence parallelism: Ulysses all-to-alls
    run inside the switch branches (group-local rendezvous over seq
    peers that share the stage coordinate — ring's global-rendezvous
    ppermute cannot, see the refusal test), the seed branch computes
    the cross-shard partial loss against targets shifted OUTSIDE the
    engine, and the outer psum over the seq axis reassembles the dense
    update exactly."""
    cfg = _cfg(n_replicas=n_replicas)
    cfg = cfg.override({"model.sp_attention": "ulysses",
                        "mesh.num_replicas": n_replicas,
                        "mesh.pipeline_parallelism": n_stage,
                        "mesh.seq_parallelism": n_seq,
                        "mesh.pipeline_microbatches": microbatches,
                        "mesh.pipeline_schedule": "1f1b",
                        "mesh.pipeline_chunks": chunks})
    batch = _tokens(cfg)
    want_loss, want_params = _dense_update(cfg, batch)

    topo = make_topology(cfg.mesh)
    model = get_model(cfg.model)
    specs = state_partition_specs(model, cfg, topo)
    state = topo.device_put_state(init_train_state(model, cfg, topo), specs)
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    state, metrics = step_fn(state, topo.device_put_batch(batch,
                                                          seq_sharded=True))

    np.testing.assert_allclose(float(metrics["loss"]), float(want_loss),
                               **LOSS_TOL)  # 2e-4 under the check_rep shim
    got = jax.device_get(state.params)
    want_stacked = transformer.stack_block_params_chunked(
        want_params, n_stage, chunks)
    assert_update_parity(got, want_stacked)


def test_1f1b_sp_refuses_ring_attention():
    """Ring attention's ppermute rendezvouses globally — inside the
    fused engine's stage-varying branches it would deadlock, so the
    registry refuses the combination up front (Ulysses composes)."""
    cfg = _cfg().override({"model.sp_attention": "ring",
                           "mesh.num_replicas": 1,
                           "mesh.pipeline_parallelism": 2,
                           "mesh.seq_parallelism": 2,
                           "mesh.pipeline_microbatches": 2,
                           "mesh.pipeline_schedule": "1f1b",
                           "mesh.pipeline_chunks": 2})
    with pytest.raises(ValueError, match="ulysses"):
        build_train_step(get_model(cfg.model), cfg, make_topology(cfg.mesh),
                         constant(LR))


def test_trainer_end_to_end_1f1b(tmp_train_dir):
    """Full Trainer on (replica=2, stage=2, model=2): training,
    checkpoint/resume with the chunk-interleaved TP-sharded layout, and
    eval through the chunked-ring forward with Megatron shards."""
    from distributedmnist_tpu.train.loop import Trainer

    cfg = _cfg(n_replicas=2)
    cfg = cfg.override({
        "mesh.num_replicas": 2, "mesh.pipeline_parallelism": 2,
        "mesh.model_parallelism": 2, "mesh.pipeline_microbatches": 2,
        "mesh.pipeline_schedule": "1f1b", "mesh.pipeline_chunks": 2,
        "train.max_steps": 10, "train.train_dir": tmp_train_dir,
        "train.log_every_steps": 5, "train.save_interval_secs": 0,
        "train.save_interval_steps": 5,
    })
    tr = Trainer(cfg)
    summary = tr.run()
    assert summary["final_step"] == 10
    ev = tr.evaluate("test")
    assert np.isfinite(ev["loss"])

    tr2 = Trainer(cfg.override({"train.resume": True, "train.max_steps": 12}))
    assert tr2._start_step == 10
    assert tr2.run()["final_step"] == 12
