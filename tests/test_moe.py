"""Mixture-of-experts + expert parallelism: routing math vs a manual
per-token loop, EP-sharded execution vs the dense-MoE oracle (forward
and one-step update), capacity-overflow behavior, and Trainer e2e."""

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
from distributedmnist_tpu.ops.moe import moe_ffn
from distributedmnist_tpu.parallel.api import (build_train_step,
                                               init_train_state,
                                               state_partition_specs)
from distributedmnist_tpu.train.lr_schedule import constant

LR = 0.1
E, D, FF = 4, 8, 16


def _moe_weights(key):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (D, E)) * 0.5,
            jax.random.normal(ks[1], (E, D, FF)) * 0.1,
            jax.random.normal(ks[2], (E, FF, D)) * 0.1)


def test_moe_ffn_matches_per_token_loop():
    router, w1, w2 = _moe_weights(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, D))
    out, aux = moe_ffn(x, router, w1, w2, num_experts=E,
                       capacity_factor=8.0)  # capacity: nothing dropped
    xf = np.asarray(x).reshape(-1, D)
    probs = jax.nn.softmax(xf @ np.asarray(router), axis=-1)
    want = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        e = int(np.argmax(probs[t]))
        h = np.maximum(xf[t] @ np.asarray(w1)[e], 0.0)
        want[t] = float(probs[t, e]) * (h @ np.asarray(w2)[e])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, D), want,
                               rtol=1e-4, atol=1e-5)
    assert float(aux) > 0.0 and np.isfinite(float(aux))


def test_capacity_overflow_drops_tokens():
    _, w1, w2 = _moe_weights(jax.random.PRNGKey(2))
    # positive inputs + positive router column 0 → every token routes
    # to expert 0 → capacity ceil(cf*t/E) overflows
    router = jnp.zeros((D, E)).at[:, 0].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (1, 8, D))) + 0.1
    out, _ = moe_ffn(x, router, w1, w2, num_experts=E, capacity_factor=1.0)
    # capacity = ceil(1.0 * 8 / 4) = 2 → tokens 2..7 dropped (zero output)
    norms = np.linalg.norm(np.asarray(out)[0], axis=-1)
    assert (norms[:2] > 1e-6).all()
    assert np.allclose(norms[2:], 0.0, atol=1e-6)


def test_ep_capacity_is_shard_local():
    """Under EP the capacity budget is per token GROUP (ops/moe.py):
    with every token routed to expert 0 and cf=1.0, each of the 4
    groups keeps ceil(t_g/E)=1 token — its first — where the dense
    oracle keeps the first ceil(t/E)=4 tokens overall. The documented
    GShard shard-local-capacity trade, asserted."""
    _, w1, w2 = _moe_weights(jax.random.PRNGKey(2))
    router = jnp.zeros((D, E)).at[:, 0].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (1, 16, D))) + 0.1

    topo = make_topology(MeshConfig(num_replicas=1, expert_parallelism=4))
    axis = topo.expert_axis

    def fn(x, router, w1, w2):
        return moe_ffn(x, router, w1, w2, num_experts=E,
                       capacity_factor=1.0, expert_axis=axis)

    out, _ = jax.jit(jax.shard_map(
        fn, mesh=topo.mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=(P(), P())))(x, router, w1, w2)
    norms = np.linalg.norm(np.asarray(out)[0], axis=-1)
    kept = norms > 1e-6
    # groups are contiguous 4-token slices; each keeps exactly its first
    assert kept.tolist() == [True, False, False, False] * 4


def test_ep_matches_unsharded():
    # With an EXPLICIT num_groups the routing math is mesh-invariant
    # (ops/moe.py): the EP-sharded dispatch must equal the dense oracle
    # EXACTLY — output AND aux — including with BINDING capacity, since
    # both paths route the same fixed per-row groups.
    router, w1, w2 = _moe_weights(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, D))

    topo = make_topology(MeshConfig(num_replicas=1, expert_parallelism=4))
    axis = topo.expert_axis

    for cf in (4.0, 1.0):  # loose AND binding capacity
        want, want_aux = moe_ffn(x, router, w1, w2, num_experts=E,
                                 capacity_factor=cf, num_groups=4)

        def fn(x, router, w1, w2):
            return moe_ffn(x, router, w1, w2, num_experts=E,
                           capacity_factor=cf, num_groups=4,
                           expert_axis=axis)

        got, got_aux = jax.jit(jax.shard_map(
            fn, mesh=topo.mesh,
            in_specs=(P(), P(), P(axis), P(axis)),
            out_specs=(P(), P())))(x, router, w1, w2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(got_aux), float(want_aux),
                                   rtol=1e-6)


def test_ep_tp_matches_unsharded():
    """EP×TP: experts over the expert axis AND every expert's hidden
    dim Megatron-sharded over the model axis; one fused psum over both
    reassembles the unsharded result."""
    router, w1, w2 = _moe_weights(jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 8, D))
    want, want_aux = moe_ffn(x, router, w1, w2, num_experts=E,
                             capacity_factor=4.0, num_groups=2)

    topo = make_topology(MeshConfig(num_replicas=1, model_parallelism=2,
                                    expert_parallelism=2))
    e_ax, m_ax = topo.expert_axis, topo.model_axis

    def fn(x, router, w1, w2):
        return moe_ffn(x, router, w1, w2, num_experts=E,
                       capacity_factor=4.0, num_groups=2,
                       expert_axis=e_ax, tp_axis=m_ax)

    got, got_aux = jax.jit(jax.shard_map(
        fn, mesh=topo.mesh,
        in_specs=(P(), P(), P(e_ax, None, m_ax), P(e_ax, m_ax, None)),
        out_specs=(P(), P())))(x, router, w1, w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(got_aux), float(want_aux), rtol=1e-6)


def test_bf16_compute_dtype():
    """MoE FFN runs in the compute dtype (routing stays f32)."""
    router, w1, w2 = (w.astype(jnp.bfloat16)
                      for w in _moe_weights(jax.random.PRNGKey(6)))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, D), jnp.bfloat16)
    out, aux = moe_ffn(x, router, w1, w2, num_experts=E, capacity_factor=4.0)
    assert out.dtype == jnp.bfloat16
    assert aux.dtype == jnp.float32
    ref, _ = moe_ffn(*(v.astype(jnp.float32) for v in (x, router, w1, w2)),
                     num_experts=E, capacity_factor=4.0)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=0.15, rtol=0.15)


def _cfg(n_replicas=1):
    return base_config(
        data={"dataset": "synthetic_lm", "batch_size": 4 * n_replicas},
        model={"name": "transformer", "compute_dtype": "float32",
               "seq_len": 16, "model_dim": 16, "num_heads": 2,
               "num_layers": 2, "vocab_size": 31, "attention_impl": "dense",
               # moe_num_groups EXPLICIT → identical routing math on
               # every mesh in the parametrize grid (and in the dense
               # oracle), drops included; cf=4 keeps capacity loose so
               # update parity is about dispatch, not drop patterns
               "num_experts": 4, "expert_capacity_factor": 4.0,
               "moe_num_groups": 4},
        sync={"mode": "sync", "straggler_profile": "none"},
    )


def _tokens(cfg, key=0):
    b, s = cfg.data.batch_size, cfg.model.seq_len
    toks = jax.random.randint(jax.random.PRNGKey(key), (b, s), 0,
                              cfg.model.vocab_size)
    return {"image": toks, "label": toks}


def _dense_moe_update(cfg, batch):
    model = get_model(cfg.model)
    params = model.init(jax.random.PRNGKey(cfg.model.init_seed))

    def loss_fn(p):
        block = transformer.make_block(
            num_heads=cfg.model.num_heads,
            feed_forward=transformer.moe_feed_forward(
                num_experts=cfg.model.num_experts,
                capacity_factor=cfg.model.expert_capacity_factor,
                num_groups=cfg.model.moe_num_groups,
                router_top_k=cfg.model.moe_router_top_k))
        logits, aux = transformer.apply(
            p, batch["image"], block=block, compute_dtype=jnp.float32,
            return_aux=True)
        return (transformer.loss_fn(logits, batch["label"])
                + cfg.model.moe_aux_weight * aux["loss"])

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return loss, jax.tree.map(lambda p, g: p - LR * g, params, grads)


@pytest.mark.parametrize("n_replicas,n_expert,n_model,n_seq", [
    (1, 4, 1, 1),   # pure EP
    (2, 2, 1, 1),   # DP×EP
    (1, 2, 2, 1),   # EP×TP: experts AND their hidden dims sharded
    (2, 1, 2, 1),   # DP×TP on a MoE model (all experts on every rank)
    (1, 2, 1, 2),   # SP×EP: seq-sharded tokens through grouped dispatch
    (1, 2, 2, 2),   # SP×EP×TP: all three model-side axes at once
])
def test_ep_step_matches_dense_update(n_replicas, n_expert, n_model, n_seq):
    cfg = _cfg(n_replicas=n_replicas)
    batch = _tokens(cfg)
    want_loss, want_params = _dense_moe_update(cfg, batch)

    topo = make_topology(MeshConfig(num_replicas=n_replicas,
                                    model_parallelism=n_model,
                                    expert_parallelism=n_expert,
                                    seq_parallelism=n_seq))
    model = get_model(cfg.model)
    specs = state_partition_specs(model, cfg, topo)
    state = topo.device_put_state(init_train_state(model, cfg, topo), specs)
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    state, metrics = step_fn(state, topo.device_put_batch(batch,
                                                          seq_sharded=True))
    np.testing.assert_allclose(float(metrics["loss"]), float(want_loss),
                               **LOSS_TOL)  # 2e-4 under the check_rep shim
    got = jax.device_get(state.params)
    assert_update_parity(got, want_params)


@pytest.mark.parametrize("n_replicas,n_stage,n_expert,n_model,microbatches", [
    (1, 2, 2, 1, 2),   # PP×EP: experts sharded inside pipeline stages
    (1, 2, 2, 1, 4),   # more microbatches → smaller microbatch-local groups
    (2, 2, 1, 1, 2),   # DP×PP on the MoE model (all experts on every stage)
    (1, 2, 2, 2, 2),   # PP×EP×TP: layer × expert × hidden-slice sharding
])
def test_pp_ep_step_matches_dense_update(n_replicas, n_stage, n_expert,
                                         n_model, microbatches):
    """MoE through the pipeline: per-tick grouped dispatch over fixed
    per-row groups (microbatch-split-invariant), per-tick aux
    accumulated across the real ticks (bubbles excluded) — must equal
    the dense single-device update exactly."""
    cfg = _cfg(n_replicas=n_replicas)
    batch = _tokens(cfg)
    want_loss, want_params = _dense_moe_update(cfg, batch)

    topo = make_topology(MeshConfig(num_replicas=n_replicas,
                                    pipeline_parallelism=n_stage,
                                    pipeline_microbatches=microbatches,
                                    expert_parallelism=n_expert,
                                    model_parallelism=n_model))
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


@pytest.mark.slow  # PP*EP Trainer e2e; superset coverage stays via test_trainer_end_to_end_pp_sp_ep
def test_trainer_end_to_end_pp_ep(tmp_train_dir):
    """Full Trainer on (replica=2, stage=2, expert=2): MoE pipeline
    training with quorum on the replica axis, eval through the M=1
    pipeline apply, resume with stacked expert-sharded params."""
    from distributedmnist_tpu.train.loop import Trainer

    cfg = _cfg(n_replicas=2).override({
        "mesh.num_replicas": 2, "mesh.pipeline_parallelism": 2,
        "mesh.pipeline_microbatches": 2, "mesh.expert_parallelism": 2,
        "sync.mode": "quorum", "sync.num_replicas_to_aggregate": 1,
        "sync.straggler_profile": "lognormal",
        "train.max_steps": 8, "train.train_dir": tmp_train_dir,
        "train.log_every_steps": 4, "train.save_interval_secs": 0,
        "train.save_interval_steps": 4,
    })
    tr = Trainer(cfg)
    assert tr.run()["final_step"] == 8
    ev = tr.evaluate("test")
    assert np.isfinite(ev["loss"])
    tr2 = Trainer(cfg.override({"train.max_steps": 10}))
    assert tr2._start_step == 8
    assert tr2.run()["final_step"] == 10


def test_trainer_end_to_end_pp_sp_ep(tmp_train_dir):
    """Full Trainer at (stage=2, seq=2, expert=2): seq-sharded batches
    through the MoE pipeline, eval, and checkpoint/resume."""
    from distributedmnist_tpu.train.loop import Trainer

    cfg = _cfg(n_replicas=1).override({
        "mesh.num_replicas": 1, "mesh.pipeline_parallelism": 2,
        "mesh.pipeline_microbatches": 2, "mesh.seq_parallelism": 2,
        "mesh.expert_parallelism": 2,
        "train.max_steps": 6, "train.train_dir": tmp_train_dir,
        "train.log_every_steps": 3, "train.save_interval_secs": 0,
        "train.save_interval_steps": 3,
    })
    tr = Trainer(cfg)
    assert tr.run()["final_step"] == 6
    ev = tr.evaluate("test")
    assert np.isfinite(ev["loss"])
    tr2 = Trainer(cfg.override({"train.max_steps": 8}))
    assert tr2._start_step == 6
    assert tr2.run()["final_step"] == 8


def test_pp_sp_ep_step_matches_dense_update():
    """The full stack at once — PP (layer stages) × SP (seq-sharded
    tokens, ring attention lockstep in the pipeline scan) × EP (grouped
    expert dispatch): per-tick routing stats pmean over (expert, seq)
    and accumulate over real ticks, the SP partial loss pre-divides the
    replicated aux — everything must still reproduce the dense
    single-device update exactly."""
    cfg = _cfg(n_replicas=1)
    batch = _tokens(cfg)
    want_loss, want_params = _dense_moe_update(cfg, batch)

    topo = make_topology(MeshConfig(num_replicas=1, pipeline_parallelism=2,
                                    pipeline_microbatches=2,
                                    seq_parallelism=2, expert_parallelism=2))
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


def test_top2_matches_two_expert_oracle():
    """GShard top-2 routing vs a manual per-token two-expert loop:
    renormalized gates g_i/(g1+g2), capacity non-binding."""
    router, w1, w2 = _moe_weights(jax.random.PRNGKey(10))
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 6, D))
    out, aux = moe_ffn(x, router, w1, w2, num_experts=E,
                       capacity_factor=8.0, router_top_k=2)
    xf = np.asarray(x).reshape(-1, D)
    probs = np.asarray(jax.nn.softmax(xf @ np.asarray(router), axis=-1))
    want = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        order = np.argsort(-probs[t])
        e1, e2 = int(order[0]), int(order[1])
        g1, g2 = probs[t, e1], probs[t, e2]
        for ei, gi in ((e1, g1), (e2, g2)):
            h = np.maximum(xf[t] @ np.asarray(w1)[ei], 0.0)
            want[t] += (gi / (g1 + g2)) * (h @ np.asarray(w2)[ei])
    np.testing.assert_allclose(np.asarray(out).reshape(-1, D), want,
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(aux))


def _top2_oracle(x2d, router, w1, w2, e, cap):
    """Independent numpy implementation of the documented GShard top-2
    semantics: sequential queue filling (round-2 positions offset by
    ALL round-1 claims, kept or dropped), renormalized gates."""
    probs = np.asarray(jax.nn.softmax(x2d @ np.asarray(router), axis=-1))
    t = x2d.shape[0]
    order = np.argsort(-probs, axis=-1)
    e1, e2 = order[:, 0], order[:, 1]
    claims = np.zeros(e, int)
    kept1 = np.zeros(t, bool)
    for i in range(t):            # round 1 arrival order
        kept1[i] = claims[e1[i]] < cap
        claims[e1[i]] += 1
    pos2_base = claims.copy()     # round 2 starts after ALL round-1 claims
    kept2 = np.zeros(t, bool)
    for i in range(t):
        kept2[i] = pos2_base[e2[i]] < cap
        pos2_base[e2[i]] += 1
    want = np.zeros_like(x2d)
    for i in range(t):
        g1, g2 = probs[i, e1[i]], probs[i, e2[i]]
        denom = g1 + g2
        if kept1[i]:
            h = np.maximum(x2d[i] @ np.asarray(w1)[e1[i]], 0.0)
            want[i] += (g1 / denom) * (h @ np.asarray(w2)[e1[i]])
        if kept2[i]:
            h = np.maximum(x2d[i] @ np.asarray(w1)[e2[i]], 0.0)
            want[i] += (g2 / denom) * (h @ np.asarray(w2)[e2[i]])
    return want, kept1, kept2


def test_top2_overflow_to_second_choice():
    """A token whose first choice overflows still flows through its
    second choice, and round-2 queue positions start after round-1's
    claims — pinned against an independent numpy implementation of the
    GShard semantics on a construction where both effects bind."""
    _, w1, w2 = _moe_weights(jax.random.PRNGKey(12))
    # build inputs whose router logits we control exactly: three token
    # kinds via directions u, v, w in the first 3 coords
    router = jnp.zeros((D, E))
    router = router.at[0, :].set(jnp.asarray([2.0, 1.0, 0.0, -9.0]))
    router = router.at[1, :].set(jnp.asarray([2.0, 0.0, 1.0, -9.0]))
    router = router.at[2, :].set(jnp.asarray([0.0, 2.0, 1.0, -9.0]))
    rows = ([[1.0, 0, 0] + [0.0] * (D - 3)] * 4      # first e0, second e1
            + [[0, 1.0, 0] + [0.0] * (D - 3)] * 4    # first e0, second e2
            + [[0, 0, 1.0] + [0.0] * (D - 3)] * 2)   # first e1, second e2
    x = jnp.asarray([rows])                          # [1, 10, D]
    # gs=10, top-2 cap = ceil(1.0·2·10/4) = 5:
    # e0 round-1 claims 8 → tokens 5-7 overflow their FIRST choice but
    #   keep their second (e2, offset 0) — overflow-to-second-choice;
    # e1 round-1 claims 2 (tokens 8,9) → u-tokens' round-2 queue on e1
    #   starts at position 2 → token 3's pos 5 ≥ cap — the round-2
    #   offset binding.
    out, _ = moe_ffn(x, router, w1, w2, num_experts=E,
                     capacity_factor=1.0, router_top_k=2)
    want, kept1, kept2 = _top2_oracle(np.asarray(x)[0], router, w1, w2,
                                      E, cap=5)
    # the construction really exercises both effects:
    assert not kept1[5:8].any() and kept2[5:8].all()   # overflow → 2nd
    assert kept2[:3].all() and not kept2[3]            # offset binds at t=3
    np.testing.assert_allclose(np.asarray(out)[0], want,
                               rtol=1e-4, atol=1e-5)


def test_top2_ep_matches_unsharded():
    """Top-2 routing through the expert-parallel all-to-all dispatch ==
    the dense top-2 oracle, output and aux (explicit num_groups)."""
    router, w1, w2 = _moe_weights(jax.random.PRNGKey(14))
    x = jax.random.normal(jax.random.PRNGKey(15), (2, 8, D))
    want, want_aux = moe_ffn(x, router, w1, w2, num_experts=E,
                             capacity_factor=2.0, router_top_k=2,
                             num_groups=4)

    topo = make_topology(MeshConfig(num_replicas=1, expert_parallelism=4))
    axis = topo.expert_axis

    def fn(x, router, w1, w2):
        return moe_ffn(x, router, w1, w2, num_experts=E,
                       capacity_factor=2.0, router_top_k=2, num_groups=4,
                       expert_axis=axis)

    got, got_aux = jax.jit(jax.shard_map(
        fn, mesh=topo.mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=(P(), P())))(x, router, w1, w2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(got_aux), float(want_aux), rtol=1e-6)


@pytest.mark.parametrize("top_k", [1, 2])
def test_top_k_train_step_matches_dense(top_k):
    """The full train step with top-k routing on a DP×EP mesh equals
    the dense oracle update (the top-2 path through value_and_grad)."""
    cfg = _cfg(n_replicas=2).override({"model.moe_router_top_k": top_k})
    batch = _tokens(cfg)
    want_loss, want_params = _dense_moe_update(cfg, batch)

    topo = make_topology(MeshConfig(num_replicas=2, expert_parallelism=2))
    model = get_model(cfg.model)
    specs = state_partition_specs(model, cfg, topo)
    state = topo.device_put_state(init_train_state(model, cfg, topo), specs)
    step_fn = build_train_step(model, cfg, topo, constant(LR))
    state, metrics = step_fn(state, topo.device_put_batch(batch))
    np.testing.assert_allclose(float(metrics["loss"]), float(want_loss),
                               **LOSS_TOL)  # 2e-4 under the check_rep shim
    got = jax.device_get(state.params)
    assert_update_parity(got, want_params)


def test_pp_moe_eval_invariant_to_microbatch_count():
    """Eval metrics through the pipelined MoE apply must be IDENTICAL
    at every microbatch count — token groups nest inside rows, so the
    microbatch split cannot change routing (the round-4 M=1 force is
    gone)."""
    from distributedmnist_tpu.parallel.api import build_eval_step

    results = {}
    for m in (1, 4):
        cfg = _cfg(n_replicas=1).override({
            "mesh.num_replicas": 1, "mesh.pipeline_parallelism": 2,
            "mesh.expert_parallelism": 2, "mesh.pipeline_microbatches": m})
        topo = make_topology(cfg.mesh)
        model = get_model(cfg.model)
        state = init_train_state(model, cfg, topo)
        specs = state_partition_specs(model, cfg, topo)
        state = topo.device_put_state(state, specs)
        eval_fn = build_eval_step(model, cfg, topo)
        batch = _tokens(cfg)
        eb = {"image": batch["image"], "label": batch["label"],
              "weight": jnp.ones((cfg.data.batch_size,), jnp.float32)}
        correct, loss_sum, weight = eval_fn(state.params, topo.device_put_batch(eb))
        results[m] = (float(correct), float(loss_sum), float(weight))
    np.testing.assert_allclose(results[1], results[4], rtol=1e-6)


@pytest.mark.parametrize(
    "n_replicas,n_stage,n_expert,n_model,n_seq,chunks,microbatches", [
        (1, 2, 2, 1, 1, 2, 2),   # 1F1B × EP
        (2, 2, 2, 1, 1, 2, 2),   # DP × 1F1B × EP
        (1, 2, 2, 2, 1, 2, 2),   # 1F1B × EP × TP
        (1, 2, 2, 1, 2, 2, 2),   # 1F1B × SP × EP (Ulysses)
    ])
def test_1f1b_ep_step_matches_dense_update(n_replicas, n_stage, n_expert,
                                           n_model, n_seq, chunks,
                                           microbatches):
    """MoE through the fused interleaved-1F1B engine: the per-row-group
    aux is linear across chunks/microbatches, so each chunk's aux
    accumulates on forward works and every backward chunk seeds its aux
    output with the constant weight (ops/pipeline.py with_aux) — the
    whole thing must reproduce the dense single-device update exactly,
    completing the composition matrix."""
    cfg = _cfg(n_replicas=n_replicas).override({
        "model.num_layers": 4,
        "model.sp_attention": "ulysses",
        "mesh.num_replicas": n_replicas,
        "mesh.pipeline_parallelism": n_stage,
        "mesh.expert_parallelism": n_expert,
        "mesh.model_parallelism": n_model,
        "mesh.seq_parallelism": n_seq,
        "mesh.pipeline_microbatches": microbatches,
        "mesh.pipeline_schedule": "1f1b",
        "mesh.pipeline_chunks": chunks})
    batch = _tokens(cfg)
    want_loss, want_params = _dense_moe_update(cfg, batch)

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


def test_1f1b_moe_eval_matches_gpipe_eval():
    """Eval through the chunked forward ring with expert sharding must
    equal the gpipe pipeline eval on the same (re-ordered) params."""
    from distributedmnist_tpu.parallel.api import build_eval_step

    results = {}
    for schedule in ("gpipe", "1f1b"):
        cfg = _cfg(n_replicas=1).override({
            "model.num_layers": 4,
            "mesh.num_replicas": 1, "mesh.pipeline_parallelism": 2,
            "mesh.expert_parallelism": 2, "mesh.pipeline_microbatches": 2,
            "mesh.pipeline_schedule": schedule,
            "mesh.pipeline_chunks": 2 if schedule == "1f1b" else 1})
        topo = make_topology(cfg.mesh)
        model = get_model(cfg.model)
        state = init_train_state(model, cfg, topo)
        specs = state_partition_specs(model, cfg, topo)
        state = topo.device_put_state(state, specs)
        eval_fn = build_eval_step(model, cfg, topo)
        batch = _tokens(cfg)
        eb = {"image": batch["image"], "label": batch["label"],
              "weight": jnp.ones((cfg.data.batch_size,), jnp.float32)}
        correct, loss_sum, weight = eval_fn(state.params,
                                            topo.device_put_batch(eb))
        results[schedule] = (float(correct), float(loss_sum), float(weight))
    np.testing.assert_allclose(results["gpipe"], results["1f1b"], rtol=1e-6)


def test_ep_on_dense_model_rejected():
    """expert_parallelism on a model without experts must refuse, not
    silently waste the axis."""
    cfg = _cfg().override({"model.num_experts": 0})
    topo = make_topology(MeshConfig(num_replicas=1, expert_parallelism=2))
    with pytest.raises(ValueError, match="expert"):
        build_train_step(get_model(cfg.model), cfg, topo, constant(LR))


def test_trainer_end_to_end_ep(tmp_train_dir):
    from distributedmnist_tpu.train.loop import Trainer

    cfg = _cfg(n_replicas=2)
    cfg = cfg.override({
        "mesh.num_replicas": 2, "mesh.expert_parallelism": 4,
        "sync.mode": "quorum", "sync.num_replicas_to_aggregate": 1,
        "sync.straggler_profile": "lognormal",
        "train.max_steps": 10, "train.train_dir": tmp_train_dir,
        "train.log_every_steps": 5, "train.save_interval_secs": 0,
        "train.save_interval_steps": 5,
    })
    tr = Trainer(cfg)
    summary = tr.run()
    assert summary["final_step"] == 10
    ev = tr.evaluate("test")
    assert np.isfinite(ev["loss"])
