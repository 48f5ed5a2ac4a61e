"""Live multi-host execution: two real ``jax.distributed`` processes
(4 virtual CPU devices each → one 8-device global mesh) training and
evaluating through the full product stack, asserted for loss/param
parity against the single-process 8-device run.

This is the one reference capability — an actually-running
multi-process cluster (reference src/mnist_distributed_train.py:25-35)
— that unit tests cannot cover in-process: ``jax.distributed``
bring-up (core/mesh.initialize_distributed), per-process batch
assembly (``make_array_from_process_local_data`` in
Topology.device_put_batch), host-sharded ingest (data/pipeline
``shard_mode="sharded"``) and the striped multi-host eval with its
process allgather (train/evaluation.run_full_eval).

Parity argument: the dataset equals the global batch (full-batch
steps), so the multiset of rows per step is identical however the
hosts shard it; with equal per-replica row counts the replica-mean of
means equals the global mean, making losses and SGD updates equal up
to float reassociation.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from conftest import base_config

_CHILD = """
import json, os, sys
from distributedmnist_tpu.core.mesh import initialize_distributed, simulate_devices
simulate_devices(4)           # per-process local devices
initialize_distributed()      # before any backend touch
import jax
import numpy as np
from distributedmnist_tpu.core.config import ExperimentConfig
from distributedmnist_tpu.train.loop import Trainer

cfg = ExperimentConfig.from_dict(json.loads(os.environ["DML_CFG"]))
t = Trainer(cfg)
sleep_ms = float(os.environ.get("DML_SLEEP_MS", "0"))
if sleep_ms:
    # a REAL slowdown of this process's step loop (not a configured
    # delay constant): every batch fetch stalls the host, exactly like
    # slow ingest or CPU contention would — the measured-timing path
    # must observe it and the policies must act on it
    import time as _time
    _base_iter = t.train_iter
    def _slow(it, secs):
        while True:
            _time.sleep(secs)
            yield next(it)
    t.train_iter = _slow(_base_iter, sleep_ms / 1000.0)
start_step = t._start_step
summary = t.run()
ev = t.evaluate()
leaves = jax.tree.leaves(jax.device_get(t.state.params))
times = t.collector.matrix()
print("RESULT " + json.dumps({
    "process_count": jax.process_count(),
    "local_devices": jax.local_device_count(),
    "global_devices": len(jax.devices()),
    "start_step": start_step,
    "final_step": summary["final_step"],
    "loss": summary["last_metrics"]["loss"],
    "param_l1": float(sum(np.abs(np.asarray(x), dtype=np.float64).sum()
                          for x in leaves)),
    "eval_accuracy": ev["accuracy"],
    "eval_loss": ev["loss"],
    "eval_num_examples": ev["num_examples"],
    # the multi-host-safety claim under test: every process holds the
    # full replicated [n] timing vector and contribution flags
    # (parallel/api._gather_replicated's one-hot psum)
    "flags": summary["last_metrics"]["flags"],
    "num_contributors": summary["last_metrics"]["num_contributors"],
    "last_step_times": times[-1].tolist() if times.size else [],
}))
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cfg_dict(train_dir: str) -> dict:
    # Full-batch (dataset == global batch) for the parity argument
    # above; dropout off because dropout masks are keyed by replica
    # and rows land on different replicas across launch shapes.
    return {
        "data": {"dataset": "synthetic", "batch_size": 128,
                 "synthetic_train_size": 128, "synthetic_test_size": 96,
                 "use_native_pipeline": False},
        "model": {"compute_dtype": "float32", "dropout_rate": 0.0},
        "optim": {"learning_rate_decay_factor": 1.0},
        "sync": {"mode": "sync", "straggler_profile": "none"},
        "eval": {"eval_batch_size": 32},
        "train": {"max_steps": 4, "log_every_steps": 2,
                  "save_interval_steps": 0, "save_results_period": 0,
                  "train_dir": train_dir},
    }


def _launch(tmp_path, cfg_dicts=None, sleep_ms=(0.0, 0.0),
            child=None, local_devices=4):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{local_devices}")
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = "2"
        env["JAX_PROCESS_ID"] = str(pid)
        env["DML_SLEEP_MS"] = str(sleep_ms[pid])
        env["DML_LOCAL_DEVICES"] = str(local_devices)
        env["DML_CFG"] = json.dumps(
            cfg_dicts[pid] if cfg_dicts is not None
            else _cfg_dict(str(tmp_path / f"multihost_p{pid}")))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", child or _CHILD], env=env, cwd=os.getcwd(),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=600)
            assert p.returncode == 0, f"child failed:\n{err[-4000:]}"
            line = [ln for ln in out.splitlines()
                    if ln.startswith("RESULT ")]
            assert line, f"no RESULT line:\n{out[-2000:]}\n{err[-2000:]}"
            results.append(json.loads(line[-1][len("RESULT "):]))
    finally:
        for q in procs:  # a failed sibling must not orphan the other
            if q.poll() is None:
                q.kill()
    return results


@pytest.mark.slow  # boots 2 real gloo worker processes
def test_two_process_training_matches_single_process(tmp_path):
    r0, r1 = _launch(tmp_path)
    for r in (r0, r1):
        assert r["process_count"] == 2
        assert r["local_devices"] == 4
        assert r["global_devices"] == 8
        assert r["final_step"] == 4
    # both processes observe the same global state
    np.testing.assert_allclose(r0["loss"], r1["loss"], rtol=1e-6)
    np.testing.assert_allclose(r0["param_l1"], r1["param_l1"], rtol=1e-6)
    assert r0["eval_num_examples"] == r1["eval_num_examples"] == 96

    # single-process 8-device reference run, identical config
    from distributedmnist_tpu.train.loop import Trainer
    import jax
    cfg = base_config(**_cfg_dict(str(tmp_path / "single")))
    t = Trainer(cfg)
    summary = t.run()
    ev = t.evaluate()
    leaves = jax.tree.leaves(jax.device_get(t.state.params))
    param_l1 = float(sum(np.abs(np.asarray(x), dtype=np.float64).sum()
                         for x in leaves))

    np.testing.assert_allclose(r0["loss"], summary["last_metrics"]["loss"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r0["param_l1"], param_l1, rtol=1e-6)
    np.testing.assert_allclose(r0["eval_loss"], ev["loss"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r0["eval_accuracy"], ev["accuracy"],
                               rtol=1e-5, atol=1e-6)
    assert ev["num_examples"] == 96


@pytest.mark.slow  # boots 2 real gloo worker processes
def test_two_process_quorum_gathers_on_every_host(tmp_path):
    """Quorum mode across two live processes: the k-of-n mask, the
    replicated [n] timing vector and the flags gather — the exact paths
    `_gather_replicated` exists for (parallel/api.py: a one-hot psum is
    statically replicated, so non-addressable processes can materialize
    it; an all_gather could not leave shard_map replicated) — must
    produce identical values on BOTH hosts, and match the seeded
    single-process run."""
    def qcfg(train_dir):
        d = _cfg_dict(train_dir)
        d["sync"] = {"mode": "quorum", "num_replicas_to_aggregate": 6,
                     "straggler_profile": "lognormal"}
        d["train"]["max_steps"] = 3
        return d

    r0, r1 = _launch(tmp_path, [qcfg(str(tmp_path / "q_p0")),
                                qcfg(str(tmp_path / "q_p1"))])
    for r in (r0, r1):
        assert r["global_devices"] == 8
        assert r["num_contributors"] == 6.0
        assert sum(r["flags"]) == 6
        assert len(r["last_step_times"]) == 8
    # every host holds the same replicated vectors
    assert r0["flags"] == r1["flags"]
    np.testing.assert_allclose(r0["last_step_times"], r1["last_step_times"],
                               rtol=1e-6)
    np.testing.assert_allclose(r0["loss"], r1["loss"], rtol=1e-6)

    # the straggler model is keyed by (seed, step, replica) — a
    # single-process run with the same config selects the same quorum.
    # (No loss parity here, deliberately: masking replica r drops
    # whichever ROWS replica r holds, and the host-sharded ingest
    # assigns different rows per replica across launch shapes — only
    # the selection itself is layout-invariant.)
    from distributedmnist_tpu.train.loop import Trainer
    records = []
    cfg = base_config(**qcfg(str(tmp_path / "q_single")))
    t = Trainer(cfg)
    t.run(step_callback=lambda s, rec: records.append(rec))
    assert records[-1]["flags"] == r0["flags"]


@pytest.mark.slow  # boots 2 real gloo worker processes. Open on jax 0.9.0
# (8-core box): runs to the end, but the sleeping process's measured
# step time reads 9.1 ms against 7.4 ms, so the 10x ratio assertion
# fails — the stall is not reaching the measured vector
def test_slow_process_loses_quorum_by_measured_time(tmp_path):
    """A REALLY slow process — its host loop stalled by an actual
    sleep, not a configured delay — must lose quorum membership through
    the measured-timing path: each process feeds its own measured step
    time into its replicas' rows of the [n] vector
    (Topology.device_put_measured), and the quorum policy ranks on it
    (≙ measured per-worker times driving aggregation,
    src/timeout_manager.py:48-61). k=4 of 8 with process 1 sleeping
    250 ms per step ⇒ steady-state contributors are exactly process 0's
    replicas 0–3."""
    def qcfg(train_dir):
        d = _cfg_dict(train_dir)
        # straggler_profile "none" → the REAL measured host step time
        # drives the policies (train/loop.py inject_measured)
        d["sync"] = {"mode": "quorum", "num_replicas_to_aggregate": 4,
                     "straggler_profile": "none"}
        d["train"]["max_steps"] = 6
        return d

    r0, r1 = _launch(tmp_path, [qcfg(str(tmp_path / "s_p0")),
                                qcfg(str(tmp_path / "s_p1"))],
                     sleep_ms=(0.0, 250.0))
    for r in (r0, r1):
        assert r["num_contributors"] == 4.0
        # process 1's measured times dwarf process 0's
        times = r["last_step_times"]
        assert min(times[4:]) > 10 * max(times[0], 1e-3), times
        # ... and exactly its replicas are evicted from the quorum
        assert r["flags"] == [1, 1, 1, 1, 0, 0, 0, 0]
    assert r0["flags"] == r1["flags"]


@pytest.mark.slow  # boots real worker processes twice (save, kill, resume); ~40 s
def test_two_process_save_kill_resume(tmp_path):
    """Checkpoint/resume across process death on a live two-process
    cluster: phase 1 trains 4 steps into a SHARED train_dir (process 0
    is the writer, ≙ the chief's NFS checkpoints,
    tools/tf_ec2.py:61-68) and the cluster dies; phase 2's fresh
    processes must both restore step 4 and finish at 8 with exactly the
    params a never-killed single-process 8-step run produces."""
    shared = str(tmp_path / "mh_shared")

    def pcfg(max_steps):
        d = _cfg_dict(shared)
        d["train"]["max_steps"] = max_steps
        return d

    r0, r1 = _launch(tmp_path, [pcfg(4), pcfg(4)])
    assert r0["start_step"] == r1["start_step"] == 0
    assert r0["final_step"] == r1["final_step"] == 4

    s0, s1 = _launch(tmp_path, [pcfg(8), pcfg(8)])
    for s in (s0, s1):
        assert s["start_step"] == 4, "resume must pick up the checkpoint"
        assert s["final_step"] == 8
    np.testing.assert_allclose(s0["param_l1"], s1["param_l1"], rtol=1e-6)

    # exact-resume oracle: one uninterrupted 8-step run
    from distributedmnist_tpu.train.loop import Trainer
    import jax
    cfg = base_config(**_cfg_dict(str(tmp_path / "oracle")))
    cfg = cfg.override({"train.max_steps": 8})
    t = Trainer(cfg)
    t.run()
    leaves = jax.tree.leaves(jax.device_get(t.state.params))
    param_l1 = float(sum(np.abs(np.asarray(x), dtype=np.float64).sum()
                         for x in leaves))
    np.testing.assert_allclose(s0["param_l1"], param_l1, rtol=1e-6)


# Child for the cross-process TENSOR-PARALLEL cluster: params are
# Megatron-sharded over the model axis of a (replica=2, model=2) mesh
# spanning both processes, so no process can materialize the full
# arrays — the per-host sharded checkpoint format (train/checkpoint.py)
# is the only way to save. param_l1 is computed IN-PROGRAM (a jitted
# global reduction comes out replicated), since jax.device_get of
# non-addressable shards is exactly what multi-host TP forbids.
_CHILD_TP = """
import glob, json, os, sys
from distributedmnist_tpu.core.mesh import initialize_distributed, simulate_devices
simulate_devices(int(os.environ.get("DML_LOCAL_DEVICES", "2")))
initialize_distributed()
import jax
import jax.numpy as jnp
import numpy as np
from distributedmnist_tpu.core.config import ExperimentConfig
from distributedmnist_tpu.train.loop import Trainer

cfg = ExperimentConfig.from_dict(json.loads(os.environ["DML_CFG"]))
t = Trainer(cfg)
start_step = t._start_step
summary = t.run()
ev = t.evaluate()
l1 = jax.jit(lambda p: sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
                           for l in jax.tree.leaves(p)))(t.state.params)
shards = sorted(os.path.basename(f) for f in
                glob.glob(os.path.join(cfg.train.train_dir, "ckpt-*")))
print("RESULT " + json.dumps({
    "process_count": jax.process_count(),
    "start_step": start_step,
    "final_step": summary["final_step"],
    "loss": summary["last_metrics"]["loss"],
    "param_l1": float(l1),
    "eval_accuracy": ev["accuracy"],
    "eval_loss": ev["loss"],
    "ckpt_files": shards,
}))
"""


def _tp_cfg_dict(train_dir: str, max_steps: int) -> dict:
    return {
        "data": {"dataset": "synthetic_lm", "batch_size": 8,
                 "synthetic_train_size": 8, "synthetic_test_size": 8,
                 "use_native_pipeline": False},
        "model": {"name": "transformer", "compute_dtype": "float32",
                  "seq_len": 16, "model_dim": 32, "num_heads": 4,
                  "num_layers": 2, "vocab_size": 37,
                  "attention_impl": "dense", "dropout_rate": 0.0},
        "mesh": {"num_replicas": 2, "model_parallelism": 2},
        "optim": {"learning_rate_decay_factor": 1.0},
        "sync": {"mode": "sync", "straggler_profile": "none"},
        "eval": {"eval_batch_size": 8},
        "train": {"max_steps": max_steps, "log_every_steps": 2,
                  "save_interval_steps": 0, "save_results_period": 0,
                  "train_dir": train_dir},
    }


@pytest.mark.slow  # boots real gloo worker processes
def test_two_process_tp_sharded_save_kill_resume_and_eval(tmp_path):
    """The round-5 per-host checkpoint proof (SURVEY §2.3 'per-host
    array serialization'): a live 2-process cluster with params
    TENSOR-SHARDED across it trains, writes the sharded checkpoint
    (one shard file per process + manifest), dies, resumes exactly,
    and the checkpoint is then evaluated LIVE by the standalone
    evaluator on its own single-process mesh — the reassembly path a
    DP-only format cannot provide."""
    shared = str(tmp_path / "mh_tp_shared")

    r0, r1 = _launch(tmp_path,
                     [_tp_cfg_dict(shared, 4), _tp_cfg_dict(shared, 4)],
                     child=_CHILD_TP, local_devices=2)
    assert r0["start_step"] == r1["start_step"] == 0
    assert r0["final_step"] == r1["final_step"] == 4
    np.testing.assert_allclose(r0["loss"], r1["loss"], rtol=1e-6)
    # the sharded layout really engaged: one shard per process + manifest
    assert any("shard000-of-002" in f for f in r0["ckpt_files"]), r0["ckpt_files"]
    assert any("shard001-of-002" in f for f in r0["ckpt_files"])
    assert any("manifest" in f for f in r0["ckpt_files"])
    assert not any(f.endswith("ckpt-00000004.msgpack") for f in r0["ckpt_files"])

    s0, s1 = _launch(tmp_path,
                     [_tp_cfg_dict(shared, 8), _tp_cfg_dict(shared, 8)],
                     child=_CHILD_TP, local_devices=2)
    for s in (s0, s1):
        assert s["start_step"] == 4, "resume must reassemble the shards"
        assert s["final_step"] == 8
    np.testing.assert_allclose(s0["param_l1"], s1["param_l1"], rtol=1e-6)

    # exact-resume oracle: one uninterrupted single-process run on the
    # SAME logical mesh (4 of this process's devices)
    import jax
    import jax.numpy as jnp
    from distributedmnist_tpu.train.loop import Trainer
    cfg = base_config(**_tp_cfg_dict(str(tmp_path / "tp_oracle"), 8))
    t = Trainer(cfg)
    t.run()
    ev = t.evaluate()
    l1 = float(jax.jit(lambda p: sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
                                     for l in jax.tree.leaves(p)))(t.state.params))
    np.testing.assert_allclose(s0["param_l1"], l1, rtol=1e-6)
    np.testing.assert_allclose(s0["eval_loss"], ev["loss"], rtol=1e-5,
                               atol=1e-6)

    # LIVE evaluation of the sharded checkpoint by the standalone
    # evaluator service (full-mesh mode, config bootstrapped from the
    # checkpoint manifest itself)
    from distributedmnist_tpu.core.config import EvalConfig
    from distributedmnist_tpu.evalsvc import Evaluator
    evs = Evaluator(shared, EvalConfig(eval_dir=str(tmp_path / "tp_eval"),
                                       run_once=True))
    rec = evs.evaluate_checkpoint()
    assert rec is not None and rec["step"] == 8
    np.testing.assert_allclose(rec["loss"], ev["loss"], rtol=1e-5,
                               atol=1e-6)
