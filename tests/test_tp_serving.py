"""Tensor-parallel serving groups (servesvc/tp_group.py + the
ServingReplica TP topology branch).

The supervision contract under test is die-as-a-unit: a TP replica is
one process group holding one sharded weight set, so ANY rank dying
must take the whole group down (journaled ``rank_exit`` →
``group_down``) before a unit restart (``group_restart`` →
``group_start``) — a half-dead group must never serve.  The group
journal chain is replayed by the ``serve_group`` invariant, checked
here both ways (conforming and violating histories).

The supervisor is exercised with stub rank processes (``sleep``
children via an injected spawn_fn) — the lifecycle logic owes nothing
to jax.  The sharded-boot test drives the real DecodeReplica with
``tp_ranks=2`` on the conftest-simulated device mesh.
"""

import json
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

LM_MODEL = {"name": "transformer", "seq_len": 64, "model_dim": 64,
            "num_heads": 4, "num_layers": 2, "vocab_size": 32,
            "compute_dtype": "float32", "attention_impl": "dense"}


def _stub_spawn(rank, attempt):
    return subprocess.Popen([sys.executable, "-c",
                             "import time; time.sleep(30)"])


def _group_records(serve_dir) -> list[dict]:
    p = Path(serve_dir) / "group_log.jsonl"
    return [json.loads(l) for l in p.read_text().splitlines() if l.strip()]


def _actions(recs):
    return [r["action"] for r in recs]


# ---------------------------------------------------------------------------
# supervisor lifecycle
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_group_die_as_a_unit_and_restart(tmp_path):
    from distributedmnist_tpu.servesvc.tp_group import ServeGroup

    g = ServeGroup(tmp_path / "g", 2, _stub_spawn, max_restarts=2,
                   poll_secs=0.01)
    g.start()
    first = dict(g.procs)
    assert all(p.poll() is None for p in first.values())
    roster = json.loads((tmp_path / "g" / "group.json").read_text())
    assert roster["ranks"] == 2 and roster["attempt"] == 0
    assert set(roster["pids"]) == {"0", "1"}

    first[1].kill()                      # murder one rank
    first[1].wait()
    assert g.step()                      # detect → teardown → restart
    # die-as-a-unit: the SURVIVING rank of attempt 0 was killed too
    assert first[0].poll() is not None
    # and a whole fresh group is up
    assert g.attempt == 1
    assert all(p.poll() is None for p in g.procs.values())
    acts = _actions(_group_records(tmp_path / "g"))
    i_exit = acts.index("rank_exit")
    assert acts[:2] == ["group_start", "rank_spawn"]
    assert acts[i_exit:i_exit + 2] == ["rank_exit", "group_down"]
    assert "group_restart" in acts[i_exit:]
    assert acts.count("group_start") == 2

    g.stop()
    assert all(p.poll() is not None for p in g.procs.values())
    acts = _actions(_group_records(tmp_path / "g"))
    assert acts[-1] == "group_stop"


@pytest.mark.tier1
def test_group_restart_budget_exhausted(tmp_path):
    from distributedmnist_tpu.servesvc.tp_group import ServeGroup

    g = ServeGroup(tmp_path / "g", 2, _stub_spawn, max_restarts=0,
                   poll_secs=0.01)
    g.start()
    g.procs[0].kill()
    g.procs[0].wait()
    assert not g.step()                  # budget 0: over, no respawn
    acts = _actions(_group_records(tmp_path / "g"))
    assert acts[-3:] == ["rank_exit", "group_down", "group_stop"]
    assert "group_restart" not in acts
    assert all(p.poll() is not None for p in g.procs.values())


@pytest.mark.tier1
def test_group_restart_on_rank0_socket_reset_via_proxy(tmp_path):
    """ISSUE 19 crossover: a rank whose WIRE dies (chaos-proxy RST
    mid-stream, not a signal) exits like any other crash — the
    supervisor must still journal the full die-as-a-unit chain
    ``rank_exit`` → ``group_down`` → ``group_restart``."""
    import socket
    import threading

    from distributedmnist_tpu.launch.netchaos import ChaosProxy
    from distributedmnist_tpu.servesvc.tp_group import ServeGroup

    # upstream: a tiny streamer the proxied rank reads from — accepts
    # serially (attempt 0's rank 0, then attempt 1's) and drips bytes
    # so the proxy's downstream pump crosses the reset threshold
    lsock = socket.create_server(("127.0.0.1", 0))
    lsock.settimeout(0.2)
    up_port = lsock.getsockname()[1]
    stop = threading.Event()

    def streamer():
        while not stop.is_set():
            try:
                conn, _ = lsock.accept()
            except TimeoutError:
                continue
            with conn:
                try:
                    while not stop.is_set():
                        conn.sendall(b"x" * 16)
                        time.sleep(0.01)
                except OSError:
                    pass

    t = threading.Thread(target=streamer, daemon=True)
    t.start()

    proxy = ChaosProxy(("127.0.0.1", up_port),
                       [{"kind": "reset", "after_bytes": 64}], worker=0)
    proxy_port = proxy.start()

    # rank 0 is a real socket reader through the proxy: it exits(1)
    # the moment its connection dies; rank 1 is the inert stub
    reader = ("import socket, sys\n"
              f"s = socket.create_connection(('127.0.0.1', {proxy_port}),"
              " timeout=10)\n"
              "s.settimeout(10)\n"
              "try:\n"
              "    while True:\n"
              "        if not s.recv(4096):\n"
              "            sys.exit(1)\n"
              "except OSError:\n"
              "    sys.exit(1)\n")

    def spawn(rank, attempt):
        if rank == 0:
            return subprocess.Popen([sys.executable, "-c", reader])
        return _stub_spawn(rank, attempt)

    g = ServeGroup(tmp_path / "g", 2, spawn, max_restarts=2,
                   poll_secs=0.01)
    try:
        g.start()
        # the one-shot reset fires after ~4 drip chunks; poll until
        # the supervisor has seen the exit and restarted the unit
        deadline = time.time() + 10.0
        while g.attempt == 0 and time.time() < deadline:
            g.step()
            time.sleep(0.02)
        assert g.attempt == 1, "proxy reset never took rank 0 down"
        assert all(p.poll() is None for p in g.procs.values())
        acts = _actions(_group_records(tmp_path / "g"))
        i_exit = acts.index("rank_exit")
        assert acts[i_exit:i_exit + 2] == ["rank_exit", "group_down"]
        assert "group_restart" in acts[i_exit:]
        recs = _group_records(tmp_path / "g")
        assert recs[i_exit]["rank"] == 0
    finally:
        g.stop()
        proxy.stop()
        stop.set()
        t.join(timeout=5)
        lsock.close()


@pytest.mark.tier1
def test_default_spawn_fn_rewrites_rank_argv(tmp_path, monkeypatch):
    """The supervisor re-invokes the SAME serve command per rank, with
    only serve-dir/rank identity rewritten (and any stale --tp-rank*
    flags stripped, including the two-token form)."""
    from distributedmnist_tpu.servesvc import tp_group

    captured = []

    class FakePopen:
        pid = 4242

        def __init__(self, cmd, **kw):
            captured.append((cmd, kw))

    monkeypatch.setattr(tp_group.subprocess, "Popen", FakePopen)
    base = ["serve", "--train_dir", "/pub", "--serve-dir", "old",
            "--tp-ranks", "2", "--decode", "--port", "0"]
    spawn = tp_group.default_spawn_fn(base, tmp_path / "w1", 2)
    spawn(0, 0)
    spawn(1, 0)
    for rank, (cmd, _kw) in enumerate(captured):
        args = cmd[cmd.index("serve"):]
        assert args.count("--serve-dir") == 1
        assert "old" not in args
        assert args[args.index("--tp-rank") + 1] == str(rank)
        assert args[args.index("--tp-ranks") + 1] == "2"
        assert "--decode" in args and "--train_dir" in args
    assert (captured[0][0][captured[0][0].index("--serve-dir") + 1]
            == str(tmp_path / "w1"))
    assert (captured[1][0][captured[1][0].index("--serve-dir") + 1]
            == str(tmp_path / "w1" / "rank1"))


# ---------------------------------------------------------------------------
# serve_group invariant replay
# ---------------------------------------------------------------------------

def _write_group_log(d: Path, actions: list[dict]) -> None:
    d.mkdir(parents=True, exist_ok=True)
    with open(d / "group_log.jsonl", "w") as f:
        for a in actions:
            f.write(json.dumps({"event": "serve", "time": time.time(),
                                **a}) + "\n")


@pytest.mark.tier1
def test_serve_group_invariant_passes_on_unit_restart(tmp_path):
    from distributedmnist_tpu.obsv.invariants import check_serve_group

    _write_group_log(tmp_path / "worker1", [
        {"action": "group_start", "ranks": 2, "attempt": 0},
        {"action": "rank_spawn", "rank": 0, "pid": 1},
        {"action": "rank_spawn", "rank": 1, "pid": 2},
        {"action": "rank_exit", "rank": 1, "pid": 2, "rc": -9},
        {"action": "group_down", "reason": "rank 1 exited (rc=-9)",
         "ranks": 2, "rank": 1},
        {"action": "group_restart", "attempt": 1, "backoff_s": 0.25},
        {"action": "group_start", "ranks": 2, "attempt": 1},
        {"action": "group_stop", "ranks": 2},
    ])
    violations, applicable = check_serve_group(tmp_path)
    assert applicable and not violations


@pytest.mark.tier1
def test_serve_group_invariant_catches_half_dead_group(tmp_path):
    from distributedmnist_tpu.obsv.invariants import check_serve_group

    # restart WITHOUT a group_down: the surviving rank was never killed
    _write_group_log(tmp_path / "worker1", [
        {"action": "group_start", "ranks": 2, "attempt": 0},
        {"action": "rank_exit", "rank": 1, "pid": 2, "rc": -9},
        {"action": "group_start", "ranks": 2, "attempt": 1},
    ])
    violations, applicable = check_serve_group(tmp_path)
    assert applicable
    assert any("no group_down" in v.detail for v in violations)

    # trailing unanswered rank_exit: the group may still be half-alive
    _write_group_log(tmp_path / "worker2", [
        {"action": "group_start", "ranks": 2, "attempt": 0},
        {"action": "rank_exit", "rank": 0, "pid": 1, "rc": 1},
    ])
    violations, _ = check_serve_group(tmp_path)
    assert any(v.worker == 2 for v in violations)


@pytest.mark.tier1
def test_check_run_skips_serve_group_without_group_log(tmp_path):
    from distributedmnist_tpu.obsv.invariants import check_run

    (tmp_path / "worker0").mkdir()
    res = check_run(tmp_path, outcome={})
    assert res["verdicts"]["serve_group"] == "skipped"


# ---------------------------------------------------------------------------
# shard digests
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_rank_shard_digest_distinct_per_rank_and_deterministic():
    import jax

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.servesvc.tp_group import rank_shard_digest

    model = get_model(ModelConfig(**LM_MODEL))
    params = jax.device_get(model.init(jax.random.PRNGKey(0)))
    specs = model.tp_param_specs("model")
    d0 = rank_shard_digest(params, specs, 0, 2)
    d1 = rank_shard_digest(params, specs, 1, 2)
    assert d0 != d1                      # ranks hold different shards
    assert d0 == rank_shard_digest(params, specs, 0, 2)
    # no specs → whole-tree digest, identical across ranks (the
    # documented degraded mode, still a digest)
    w0 = rank_shard_digest(params, None, 0, 2)
    assert w0 == rank_shard_digest(params, None, 1, 2)


# ---------------------------------------------------------------------------
# real TP replica boot (simulated mesh)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_staging(tmp_path_factory):
    """One published 10-step checkpoint of the toy transformer."""
    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.train.loop import Trainer

    staging = tmp_path_factory.mktemp("tp_staging")
    cfg = ExperimentConfig.from_dict({
        "data": {"dataset": "synthetic_lm", "batch_size": 32,
                 "synthetic_train_size": 256, "synthetic_test_size": 64,
                 "use_native_pipeline": False},
        "model": dict(LM_MODEL),
        "train": {"max_steps": 10, "log_every_steps": 10,
                  "train_dir": str(staging),
                  "save_interval_steps": 10, "save_results_period": 0,
                  "async_checkpoint": False},
    })
    Trainer(cfg).run()
    return staging, cfg


@pytest.mark.parametrize("rank", [0, 1])
def test_decode_replica_boots_tensor_parallel(lm_staging, tmp_path,
                                              monkeypatch, rank):
    """Rank 0: tp_ranks=2 builds a replica=1 × model=2 serving mesh, and
    the mesh-portable restore actually SHARDS the followed checkpoint —
    at least the attention/FFN weights carry the model axis. Rank 1, a
    follower: it restores the same publish through the same digest
    check and journals ``shard_verify`` with the sha256 of the bytes
    ITS half of the model axis holds."""
    import jax

    from distributedmnist_tpu.core.config import (DecodeConfig,
                                                  ExperimentConfig,
                                                  ServeConfig)

    staging, cfg = lm_staging
    if rank == 1:
        from distributedmnist_tpu.models.registry import get_model
        from distributedmnist_tpu.obsv.report import load_jsonl
        from distributedmnist_tpu.servesvc import tp_group
        from distributedmnist_tpu.train import checkpoint as ckpt

        # the follower runs until its supervisor kills it: here its
        # first park ends it, and it installs no signal handler in the
        # test's process
        park, real_sleep = 0.123, time.sleep

        class Parked(Exception):
            pass

        def sleep(secs):
            if secs == park:
                raise Parked
            real_sleep(secs)

        monkeypatch.setattr(tp_group.signal, "signal", lambda *a: None)
        monkeypatch.setattr(tp_group.time, "sleep", sleep)
        with pytest.raises(Parked):
            tp_group.run_rank_follower(staging, tmp_path / "rank1", 1, 2,
                                       poll_secs=park)
        verified = [r for r in load_jsonl(tmp_path / "rank1"
                                          / "serve_log.jsonl", "serve")
                    if r["action"] == "shard_verify"]
        assert [(r["rank"], r["step"]) for r in verified] == [(1, 10)]
        params = ckpt._checkpoint_state_dict(staging, 10)[0]["params"]
        specs = get_model(cfg.model).tp_param_specs("model")
        assert verified[0]["digest"] == tp_group.rank_shard_digest(
            params, specs, 1, 2)
        assert verified[0]["digest"] != tp_group.rank_shard_digest(
            params, specs, 0, 2)
        assert verified[0]["source_digest"] == ckpt.artifact_digest(
            staging, 10)
        beats = load_jsonl(tmp_path / "rank1" / "train_log.jsonl",
                           "heartbeat")
        assert beats[-1]["step"] == 1 and beats[-1]["tp_rank"] == 1
        return

    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    rep = DecodeReplica(
        staging, serve_dir=tmp_path / "replica",
        scfg=ServeConfig(poll_secs=0.05, tp_ranks=2),
        dcfg=DecodeConfig(decode_slots=2, block_size=8, num_blocks=32,
                          max_prompt_len=16, max_new_tokens=4),
        cfg=cfg)
    assert rep.topo.mesh.shape["model"] == 2
    rep._load_initial(timeout_s=120)
    tp_leaves = [
        l for l in jax.tree.leaves(rep._params)
        if "model" in (ax for spec in [getattr(l.sharding, "spec", ())]
                       for entry in (spec or ())
                       for ax in (entry if isinstance(entry, tuple)
                                  else (entry,)) if ax)]
    assert tp_leaves, "no param leaf is sharded over the model axis"

    # a classification replica (MLP, no TP specs) refuses tp_ranks>1
    # with a config error instead of serving replicated silently
    from distributedmnist_tpu.core.config import ConfigError
    from distributedmnist_tpu.servesvc.server import ServingReplica
    mnist_cfg = ExperimentConfig.from_dict(
        {"data": {"dataset": "synthetic", "batch_size": 8}})
    with pytest.raises(ConfigError, match="tp_ranks"):
        ServingReplica(tmp_path / "nope", serve_dir=tmp_path / "nope2",
                       scfg=ServeConfig(tp_ranks=2), cfg=mnist_cfg)
