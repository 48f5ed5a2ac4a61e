"""Tensor-parallel serving process groups.

``serve.tp_ranks > 1`` turns one serving replica into a small process
group behind the UNCHANGED socket/failover/hot-swap/heartbeat
contract:

* **rank 0** is the real replica — it owns the socket, the serving
  mesh (``replica=1 × model=tp_ranks``, built inside
  ``ServingReplica``), ``serve.json`` and ``serve_log.jsonl`` in the
  worker's own dir. Clients, the chaos harness, and the serving
  invariants see exactly the single-chip replica surface.
* **ranks 1..N-1** are follower ranks: each hot-follows the same
  publish dir, digest-verifies every checkpoint through the identical
  ``restore_checkpoint`` machinery, and journals a ``shard_verify``
  record carrying the sha256 of ITS model-axis shard of the new params
  — the shard-wise staging evidence for hot-swap under TP. Followers
  write under ``serve_dir/rank<r>/`` and heartbeat like any worker.
* the **supervisor** (this module) spawns all ranks, journals the
  group lifecycle to ``group_log.jsonl`` (``group_start`` /
  ``rank_spawn`` / ``rank_exit`` / ``group_down`` / ``group_restart``
  / ``group_stop`` — schema-declared in ``obsv/schema.py``), and
  enforces **die-as-a-unit**: any rank exiting outside a graceful stop
  kills every other rank and restarts the whole group (bounded by
  ``serve.tp_group_max_restarts``). A half-dead TP group never serves
  — the ``serve_group`` replay invariant checks exactly this.

On a single CPU host the ranks cannot join one cross-process XLA
collective (the CPU backend has no multiprocess computations), so rank
0 carries the whole sharded mesh on virtual devices and followers
exercise the group-lifecycle + shard-verification contract; on a
multi-host accelerator pod the same layout puts real chips behind each
rank. The supervision, journaling, and invariant surface are identical
either way — that is the point of keeping the replica contract shape-
agnostic (TF-Replicator's resource-shape-agnostic replicas).
"""

from __future__ import annotations

import hashlib
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path
from typing import Callable

from ..core.log import JsonlSink, get_logger

logger = get_logger("tp_group")

_KILL_WAIT_S = 10.0


def _set_pdeathsig():
    """Child preexec hook: die with the supervisor. A SIGKILLed
    supervisor must not orphan half a TP group into exactly the
    half-dead state the group exists to prevent (linux only; a no-op
    fallback elsewhere)."""
    try:
        import ctypes
        libc = ctypes.CDLL(None, use_errno=True)
        PR_SET_PDEATHSIG = 1
        libc.prctl(PR_SET_PDEATHSIG, signal.SIGKILL)
    except Exception:
        pass


class ServeGroup:
    """Spawn + supervise the ranks of one TP serving replica.

    ``spawn_fn(rank, attempt) -> subprocess.Popen`` builds one rank
    process (injectable so the die-as-a-unit logic is testable without
    booting jax); the CLI wires :func:`default_spawn_fn`.
    """

    def __init__(self, serve_dir: str | Path, ranks: int,
                 spawn_fn: Callable[[int, int], subprocess.Popen], *,
                 max_restarts: int = 3, poll_secs: float = 0.25):
        if ranks < 2:
            raise ValueError(f"a TP group needs >= 2 ranks, got {ranks}")
        self.serve_dir = Path(serve_dir)
        self.serve_dir.mkdir(parents=True, exist_ok=True)
        self.ranks = ranks
        self.spawn_fn = spawn_fn
        self.max_restarts = max_restarts
        self.poll_secs = poll_secs
        self.attempt = 0
        self.procs: dict[int, subprocess.Popen] = {}
        self._stopping = False
        self._log = JsonlSink(self.serve_dir / "group_log.jsonl")

    def _journal(self, record: dict) -> None:
        self._log.write({"event": "serve", "time": time.time(), **record})

    def _write_group_json(self) -> None:
        """Atomic group roster (pids per rank) — what the chaos
        side reads to target a specific rank."""
        path = self.serve_dir / "group.json"
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps({
            "ranks": self.ranks, "attempt": self.attempt,
            "supervisor_pid": os.getpid(),
            "pids": {str(r): p.pid for r, p in self.procs.items()}}))
        tmp.replace(path)

    def start(self) -> None:
        self._spawn_all()

    def _spawn_all(self) -> None:
        self._journal({"action": "group_start", "ranks": self.ranks,
                       "attempt": self.attempt})
        self.procs = {}
        for r in range(self.ranks):
            p = self.spawn_fn(r, self.attempt)
            self.procs[r] = p
            self._journal({"action": "rank_spawn", "rank": r,
                           "pid": p.pid})
        self._write_group_json()

    def _kill_all(self, sig=signal.SIGKILL) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                try:
                    p.send_signal(sig)
                except OSError:
                    pass
        deadline = time.time() + _KILL_WAIT_S
        for p in self.procs.values():
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.05)

    def _down(self, dead_rank: int, rc) -> None:
        """Die-as-a-unit: one rank is gone, so the whole group goes —
        a TP replica with a missing shard must never keep serving."""
        self._journal({"action": "rank_exit", "rank": dead_rank,
                       "pid": self.procs[dead_rank].pid, "rc": rc})
        self._kill_all()
        # rank 0's endpoint is dead with the group: drop the stale
        # advertisement so client discovery stops routing here until
        # the restarted group re-publishes it
        try:
            (self.serve_dir / "serve.json").unlink()
        except OSError:
            pass
        self._journal({"action": "group_down",
                       "reason": f"rank {dead_rank} exited (rc={rc})",
                       "ranks": self.ranks, "rank": dead_rank})

    def step(self) -> bool:
        """One supervision tick; returns False when the group is
        permanently over (restart budget exhausted or stopping)."""
        for r, p in self.procs.items():
            rc = p.poll()
            if rc is None or self._stopping:
                continue
            self._down(r, rc)
            if self.attempt >= self.max_restarts:
                self._journal({"action": "group_stop",
                               "ranks": self.ranks})
                return False
            self.attempt += 1
            backoff = min(2.0, 0.25 * self.attempt)
            self._journal({"action": "group_restart",
                           "attempt": self.attempt,
                           "backoff_s": backoff})
            time.sleep(backoff)
            self._spawn_all()
            return True
        return not self._stopping

    def stop(self) -> None:
        """Graceful whole-group stop: SIGTERM rank 0 first so it
        drains in-flight work (its own serve_forever contract), then
        the followers; stragglers are killed."""
        self._stopping = True
        for r in sorted(self.procs):
            p = self.procs[r]
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.time() + _KILL_WAIT_S
        for p in self.procs.values():
            while p.poll() is None and time.time() < deadline:
                time.sleep(0.05)
        self._kill_all()
        self._journal({"action": "group_stop", "ranks": self.ranks})

    def run_forever(self) -> None:
        def _on_term(signum, frame):
            self._stopping = True
        try:
            signal.signal(signal.SIGTERM, _on_term)
            signal.signal(signal.SIGINT, _on_term)
        except ValueError:
            pass  # not the main thread (tests)
        self.start()
        while self.step():
            time.sleep(self.poll_secs)
        if self._stopping:
            self.stop()


def default_spawn_fn(base_argv: list[str], serve_dir: str | Path,
                     ranks: int) -> Callable[[int, int], subprocess.Popen]:
    """Rank-process factory for the CLI: re-invoke ``launch serve``
    with the SAME user flags plus ``--tp-rank r`` (rank 0 becomes the
    real replica, others the followers) and a per-rank serve dir
    (rank 0 keeps the group's dir — the socket contract's surface)."""
    serve_dir = Path(serve_dir)
    argv = []
    skip = False
    for tok in base_argv:
        if skip:
            skip = False
            continue
        if tok in ("--serve-dir", "--tp-ranks", "--tp-rank"):
            skip = True
            continue
        if tok.startswith(("--serve-dir=", "--tp-ranks=", "--tp-rank=")):
            continue
        argv.append(tok)

    def _child_env() -> dict:
        """The supervisor stays off jax: a parent that initialised an
        accelerator backend would hold the chip against rank 0. Where
        the env pins the CPU platform, rank 0 needs its virtual-device
        count forced BEFORE its XLA backend initializes, so the flag
        is planted here; otherwise the ranks see the ambient chips and
        the env passes through (too few chips is refused in rank 0 by
        make_topology, never papered over with a CPU mesh)."""
        from ..core.mesh import (force_device_count_flag,
                                 forced_device_count)
        env = dict(os.environ)
        if env.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
            return env  # e.g. unset, or the chip host's "tpu,cpu"
        flags = env.get("XLA_FLAGS", "")
        if (forced_device_count(flags) or 1) < ranks:
            env["XLA_FLAGS"] = force_device_count_flag(flags, ranks)
        return env

    def spawn(rank: int, attempt: int) -> subprocess.Popen:
        rank_dir = serve_dir if rank == 0 else serve_dir / f"rank{rank}"
        cmd = ([sys.executable, "-m", "distributedmnist_tpu.launch"]
               + argv + ["--serve-dir", str(rank_dir),
                         "--tp-ranks", str(ranks),
                         "--tp-rank", str(rank)])
        return subprocess.Popen(
            cmd, env=_child_env(),
            preexec_fn=_set_pdeathsig if os.name == "posix" else None)

    return spawn


# ---------------------------------------------------------------------------
# Follower ranks: shard-wise digest verification of every publish
# ---------------------------------------------------------------------------

def _model_axis_dim(spec) -> int | None:
    """The dim a PartitionSpec shards over the serving mesh's model
    axis, or None (replicated leaf)."""
    if spec is None:
        return None
    for dim, entry in enumerate(tuple(spec)):
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        if "model" in [n for n in names if n is not None]:
            return dim
    return None


def rank_shard_digest(params, specs, rank: int, ranks: int) -> str:
    """sha256 over THIS rank's model-axis shard of every param leaf —
    leaves in deterministic tree order, sharded dims split exactly the
    way the TP layout splits them (``np.array_split`` matches the even
    split the mesh uses; replicated leaves contribute whole). This is
    the identity of the bytes rank ``rank`` holds after a sharded
    load, so followers verifying it per publish IS the shard-wise half
    of the hot-swap digest discipline."""
    import numpy as np
    import jax

    from jax.sharding import PartitionSpec

    h = hashlib.sha256()
    leaves_p, treedef_p = jax.tree.flatten(params)
    leaves_s, _ = jax.tree.flatten(
        specs, is_leaf=lambda x: x is None or isinstance(x, PartitionSpec))
    if len(leaves_s) != len(leaves_p):
        # spec tree shape drifted from the param tree: hash whole
        # leaves (still a digest, just not shard-scoped) rather than
        # guessing an alignment
        leaves_s = [None] * len(leaves_p)
    for leaf, spec in zip(leaves_p, leaves_s):
        arr = np.asarray(leaf)
        dim = _model_axis_dim(spec)
        if dim is not None and arr.ndim > dim:
            arr = np.array_split(arr, ranks, axis=dim)[rank]
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def run_rank_follower(train_dir: str | Path, serve_dir: str | Path,
                      rank: int, ranks: int, *,
                      poll_secs: float = 0.25) -> None:
    """A non-zero rank of a TP serving group: no socket, no mesh —
    hot-follow the publish dir, digest-verify each checkpoint through
    the same restore machinery as rank 0, journal the sha256 of this
    rank's model-axis param shard (``shard_verify``), heartbeat, park.

    Runs until killed (the supervisor owns this process's lifetime —
    SIGTERM from a graceful group stop just exits)."""
    import jax

    from ..core.config import effective_model_config
    from ..core.mesh import MeshConfig, make_topology
    from ..models.registry import get_model
    from ..parallel.api import init_train_state
    from ..train import checkpoint as ckpt

    train_dir = Path(train_dir)
    serve_dir = Path(serve_dir)
    serve_dir.mkdir(parents=True, exist_ok=True)
    cfg = ckpt.wait_for_run_config(train_dir)
    topo = make_topology(MeshConfig(num_replicas=1),
                         devices=jax.devices()[:1])
    model = get_model(effective_model_config(cfg, serving=True))
    # shapes and dtypes only (as servesvc/server.py): the restore
    # returns the saved arrays
    template = jax.eval_shape(lambda: init_train_state(model, cfg, topo))
    tp_specs = (model.tp_param_specs("model")
                if getattr(model, "tp_param_specs", None) else None)

    log = JsonlSink(serve_dir / "serve_log.jsonl")
    heartbeat = JsonlSink(serve_dir / "train_log.jsonl")
    verified = {"count": 0}

    def journal(rec: dict) -> None:
        log.write({"event": "serve", "time": time.time(), "rank": rank,
                   **rec})

    stop = {"flag": False}

    def _on_term(signum, frame):
        stop["flag"] = True

    try:
        signal.signal(signal.SIGTERM, _on_term)
        signal.signal(signal.SIGINT, _on_term)
    except ValueError:
        pass

    follower = ckpt.CheckpointFollower(train_dir)

    def read(step: int):
        restored = ckpt.restore_checkpoint(
            train_dir, template, None,
            on_event=lambda rec: journal(
                {"action": "follow_" + rec.get("action", "?"),
                 **{k: v for k, v in rec.items()
                    if k not in ("layer", "action")}}))
        if restored is None:
            return None
        state, _, at_step = restored
        digest = rank_shard_digest(state.params, tp_specs, rank, ranks)
        journal({"action": "shard_verify", "rank": rank, "step": at_step,
                 "digest": digest,
                 "source_digest": ckpt.artifact_digest(train_dir,
                                                       at_step)})
        verified["count"] += 1
        return at_step

    last_hb = -1
    while not stop["flag"]:
        follower.poll(read)
        # liveness counter = publishes shard-verified (the heartbeat
        # ``step`` contract is "monotone progress", same as the serving
        # replica's terminal count) — write-on-change only
        if verified["count"] != last_hb:
            last_hb = verified["count"]
            heartbeat.write({"event": "heartbeat", "step": last_hb,
                             "time": time.time(), "tp_rank": rank})
        time.sleep(poll_secs)
