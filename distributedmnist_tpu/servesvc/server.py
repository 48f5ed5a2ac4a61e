"""The serving replica process.

One replica = one socket + one bounded admission queue + one batcher
thread + one checkpoint-follower thread. The robustness contract
(checked post-run by ``obsv/invariants.py``'s serving invariants):

* **Exactly one terminal outcome per admitted request** — a response
  or a TYPED reject (``overloaded`` / ``deadline_exceeded`` /
  ``bad_request`` / ``shutting_down``); a graceful stop drains the
  queue by rejecting, never by dropping.
* **Never serve a checkpoint that failed digest verification** — the
  weight path is ``train/checkpoint.py`` ``restore_checkpoint`` with
  its fallback-to-previous-loadable-step, so a torn or corrupt publish
  is skipped (and journaled) while the replica keeps serving the
  previous weights.
* **Served model step is monotone non-decreasing across swaps** — a
  swap only installs a strictly newer step.

Precision tiers (``serve.precision_tier``): with ``bf16`` or ``int8``
the replica PREFERS the publish-time quantized sidecar
(``ckpt-<step>.quant.msgpack``, written by the ``quant/`` pass behind
``quant.publish_tiers``) — digest-verified through the same machinery
as the checkpoint itself, int8 weights resident on device and
dequantized inside the jitted predict (scale fusion). A sidecar that
is absent, torn, or missing the requested tier journals a
``follow_quant_sidecar_fallback`` and that publish serves from the
full-precision artifact instead — the torn-digest invariant covers
sidecars exactly like checkpoints, and the follower cursor still
advances (no skip-loop wedge). Every ``weight_swap`` records the
``tier`` it installed plus ``source_artifact``/``source_digest``, so
the journals say which representation actually served.

Wire protocol: one JSON line per connection each way (the client shim
opens a connection per request — serving rates here are bounded by
model compute, not connection setup).

  request:  {"id": ..., "inputs": [...], "deadline_ms": ...}
            {"meta": true}   → model metadata, never queued
  response: {"id": ..., "status": "ok", "model_step": N,
             "prediction": k, "probs": [...]}
            {"id": ..., "status": "rejected", "reason": "..."}

Artifacts per replica (in ``serve_dir``):

* ``serve_log.jsonl`` — ``event: "serve"`` records: admit / respond /
  reject per request id, ``weight_swap`` (step, digest, swap_ms),
  follower skip events. What the serving invariants replay.
* ``train_log.jsonl`` — ``event: "heartbeat"`` records whose ``step``
  is the terminal-outcome count: the liveness/progress signal that
  makes the EXISTING supervisor machinery (poll, stall detection,
  measured boot, MTTR) work unchanged for serving payloads.
* ``serve.json`` — the bound endpoint (host, port, pid), written once
  the replica is actually ready to serve; the client shim discovers
  replicas by these.
"""

from __future__ import annotations

import collections
import json
import queue
import socket
import threading
import time
from pathlib import Path
from typing import Any

import jax
import numpy as np

from ..core.config import (SERVING_PRECISION_TIERS, ConfigError,
                           ExperimentConfig, MeshConfig, ServeConfig,
                           effective_model_config)
from ..core.log import JsonlSink, get_logger
from ..core.mesh import Topology, make_topology
from ..models.registry import get_model
from ..parallel.api import init_train_state, state_partition_specs
from ..train import checkpoint as ckpt

logger = get_logger("serve")

_MAX_REQUEST_BYTES = 4 << 20  # a request is one image/sequence, not a shard


# The first-checkpoint config bootstrap lives at the checkpoint layer
# (train/checkpoint.py, next to the CheckpointFollower) — re-exported
# here because the serving CLI reads it off this module.
wait_for_run_config = ckpt.wait_for_run_config


class _Pending:
    """One admitted request waiting in the batch queue."""

    __slots__ = ("req_id", "inputs", "conn", "admitted_at", "deadline_at")

    def __init__(self, req_id, inputs, conn, admitted_at, deadline_at):
        self.req_id = req_id
        self.inputs = inputs
        self.conn = conn
        self.admitted_at = admitted_at
        self.deadline_at = deadline_at


class ServingReplica:
    """Load the latest digest-verified checkpoint and serve it; keep
    following publishes and hot-swap without dropping in-flight work."""

    def __init__(self, train_dir: str | Path, serve_dir: str | Path = ".",
                 scfg: ServeConfig | None = None,
                 cfg: ExperimentConfig | None = None,
                 topo: Topology | None = None):
        self.train_dir = Path(train_dir)
        self.serve_dir = Path(serve_dir)
        self.serve_dir.mkdir(parents=True, exist_ok=True)
        if cfg is None:
            cfg = wait_for_run_config(self.train_dir)
        self.cfg = cfg
        self.scfg = scfg or cfg.serve
        self.tp_ranks = max(1, int(self.scfg.tp_ranks))
        if topo is not None:
            self.topo = topo
        else:
            # Lean 1-device mesh, like the evaluator's --single_device
            # mode: serving shares a host with trainers and must not
            # force an N-device backend or join any collective. Same
            # refusal: pipeline-stacked layouts restore differently.
            if cfg.mesh.pipeline_parallelism > 1:
                raise ValueError(
                    "serving cannot restore pipeline-stacked parameter "
                    "layouts; serve from a non-pipeline checkpoint")
            if self.tp_ranks > 1:
                # TP serving: replica capacity as a mesh shape. One
                # replica axis × tp_ranks model axis; every published
                # checkpoint is sharded-loaded through the model's TP
                # partition rules (restore_for_topology below) and the
                # jitted predict/decode runs GSPMD-partitioned over
                # the serving mesh. A CPU host with fewer devices than
                # ranks simulates the mesh (virtual CPU devices) — the
                # sharded-load/swap/verify contract is identical. An
                # accelerator host with too few chips is refused by
                # make_topology: it never trades a chip for a CPU mesh.
                self.topo = make_topology(MeshConfig(
                    num_replicas=1, model_parallelism=self.tp_ranks,
                    simulate_devices=(0 if len(jax.devices())
                                      >= self.tp_ranks
                                      else self.tp_ranks)))
            else:
                self.topo = make_topology(MeshConfig(num_replicas=1),
                                          devices=jax.devices()[:1])
        # serve-side compute-dtype resolution (serve.compute_dtype →
        # precision.compute_dtype → model.compute_dtype), validated at
        # the shared seam — a typo is a typed ConfigError here, not a
        # jnp error mid-request
        self.model = get_model(effective_model_config(cfg, serving=True))
        self.tier = self.scfg.precision_tier or "fp32"
        if self.tier not in SERVING_PRECISION_TIERS:
            raise ConfigError(
                f"serve.precision_tier={self.tier!r} is not a known "
                f"tier; valid tiers: "
                f"{', '.join(SERVING_PRECISION_TIERS)}")
        try:
            # shapes and dtypes only: a restore reads the tree's
            # structure off it and returns the saved arrays, so no
            # second copy of the weights lies beside the served one
            self.template = jax.eval_shape(
                lambda: init_train_state(self.model, cfg, self.topo))
            self._param_specs = state_partition_specs(
                self.model, cfg, self.topo).params
        except ValueError as e:
            if self.tp_ranks > 1:
                raise ConfigError(
                    f"serve.tp_ranks={self.tp_ranks} needs a model with "
                    f"tensor-parallel partition rules: {e}") from e
            raise
        self.follower = ckpt.CheckpointFollower(self.train_dir)

        model = self.model

        def predict(params, x):
            logits = model.apply(params, x, train=False)
            return model.predictions(logits)

        # one jit; each bucket shape compiles once on first use. The
        # fp32 predict always exists (it is the fallback every tier
        # degrades to); quant-tier predicts are built lazily on the
        # first sidecar install (quant/ptq.build_tier_predict — int8
        # dequantizes in-graph, bf16 applies the bf16-stored leaves
        # through a bf16-compute model unless serve.compute_dtype
        # pinned something else)
        self._predict = jax.jit(predict)
        self._predict_fp32 = self._predict
        self._tier_predict_fns: dict[str, Any] = {"fp32": self._predict}

        # current weights (batcher-owned) + double buffer staged by the
        # follower thread, flipped at a batch boundary
        self._params = None
        self.model_step = -1
        self.model_digest: str | None = None
        self.model_tier: str | None = None      # tier actually installed
        self.model_source_digest: str | None = None
        # last step a sidecar fallback was journaled for: when the
        # fp32 path ALSO has nothing to restore, the follower cursor
        # stays put and every poll re-reads the same step — the
        # fallback must journal once per publish, not once per tick
        # (quant_sidecar_fallbacks counts refusals, not poll cadence)
        self._quant_fallback_step: int | None = None
        self._staged: tuple | None = None
        self._staged_lock = threading.Lock()

        self._queue: queue.Queue[_Pending] = queue.Queue(
            maxsize=max(1, self.scfg.queue_depth))
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._conn_threads: set[threading.Thread] = set()
        self._conn_lock = threading.Lock()
        self._sock: socket.socket | None = None
        self.bound_port: int | None = None

        self._journal_lock = threading.Lock()
        self._journal_closed = False
        self._serve_log = JsonlSink(self.serve_dir / "serve_log.jsonl")
        self._heartbeat = JsonlSink(self.serve_dir / "train_log.jsonl")
        self._terminals = 0          # responses + rejects ever produced
        self._last_heartbeat = -1
        self.swaps = 0

        # idempotency: request id → (final ok payload, completed_at).
        # A retried request whose execution already completed here —
        # the sibling-failover case, or a reset that ate the response
        # after _terminal journaled it — answers from this cache
        # instead of double-executing (journaled as ``dedup_hit``).
        # Bounded LRU; only FINAL ok outcomes are cached (retryable
        # sheds must stay retryable).
        self._dedup_lock = threading.Lock()
        self._dedup: collections.OrderedDict[Any, tuple[dict, float]] = \
            collections.OrderedDict()
        self.dedup_hits = 0

    # -- journal ------------------------------------------------------

    def _journal(self, record: dict) -> None:
        with self._journal_lock:
            if self._journal_closed:
                return  # a straggler conn thread racing stop()
            self._serve_log.write({"event": "serve",
                                   "time": time.time(), **record})

    def _terminal(self, action: str, req_id, **fields) -> None:
        """Journal one terminal outcome (respond/reject) and bump the
        heartbeat counter — every admitted request must produce exactly
        one of these."""
        self._journal({"action": action, "id": req_id, **fields})
        with self._journal_lock:
            self._terminals += 1

    # -- idempotency / dedup cache ------------------------------------

    def _dedup_put(self, req_id, payload: dict) -> None:
        """Remember a FINAL ok outcome for its request id. Ids are the
        client's idempotency keys; requests without one opt out."""
        if req_id is None or int(self.scfg.dedup_cache_size) <= 0:
            return
        with self._dedup_lock:
            self._dedup[req_id] = (payload, time.time())
            self._dedup.move_to_end(req_id)
            while len(self._dedup) > int(self.scfg.dedup_cache_size):
                self._dedup.popitem(last=False)

    def _dedup_get(self, req_id) -> tuple[dict, float] | None:
        if req_id is None:
            return None
        with self._dedup_lock:
            got = self._dedup.get(req_id)
            if got is not None:
                self._dedup.move_to_end(req_id)
        return got

    def _pressure_fields(self) -> dict:
        """Live replica pressure stamped onto every heartbeat — queue
        occupancy against the admission bound here; the decode replica
        adds KV block-pool occupancy. What ``parse_poll_output``
        surfaces to the resource broker without a second channel."""
        return {"queue_depth": self._queue.qsize(),
                "queue_limit": max(1, self.scfg.queue_depth)}

    def _maybe_heartbeat(self) -> None:
        with self._journal_lock:
            n = self._terminals
            if n == self._last_heartbeat or self._journal_closed:
                return
            self._last_heartbeat = n
            self._write_heartbeat(n)

    def _write_heartbeat(self, n: int) -> None:
        """The write itself, under the journal's lock (the decode
        replica opens a span around it)."""
        self._heartbeat.write({"event": "heartbeat", "step": n,
                               "time": time.time(),
                               **self._pressure_fields()})

    # -- weights ------------------------------------------------------

    def _tier_predict(self, tier: str):
        """The jitted predict for a quant tier, built once per tier
        per replica (each bucket shape still compiles on first use)."""
        fn = self._tier_predict_fns.get(tier)
        if fn is None:
            import dataclasses

            from ..quant.ptq import build_tier_predict
            model = self.model
            if tier == "bf16" and not self.cfg.serve.compute_dtype:
                # the tier's point is MXU-native bf16 end-to-end; an
                # explicit serve.compute_dtype still wins
                model = get_model(dataclasses.replace(
                    effective_model_config(self.cfg, serving=True),
                    compute_dtype="bfloat16"))
            fn = jax.jit(build_tier_predict(model, self.template.params,
                                            tier))
            self._tier_predict_fns[tier] = fn
        return fn

    def _read_quant_tier(self, step: int, t0: float):
        """The sidecar-preference half of the follower read: a
        digest-verified quant sidecar holding the configured tier →
        a staged install; anything else (absent, torn, tier missing)
        journals ``follow_quant_sidecar_fallback`` and returns None so
        the read falls through to the full-precision artifact — the
        cursor still advances through THAT path, so a bad sidecar can
        never wedge the follower's skip loop."""
        def fallback(reason: str):
            if self._quant_fallback_step != step:
                self._quant_fallback_step = step
                self._journal({"action": "follow_quant_sidecar_fallback",
                               "step": step, "tier": self.tier,
                               "reason": reason})
            return None
        try:
            payload = ckpt.read_quant_sidecar(self.train_dir, step)
            tiers = payload["tiers"]
            if self.tier not in tiers:
                raise KeyError(
                    f"sidecar has tiers {sorted(tiers)}, not "
                    f"{self.tier!r}")
        except FileNotFoundError:
            return fallback("sidecar_absent")
        except (OSError, ValueError, KeyError) as e:
            # ValueError covers CheckpointCorruptError: the digest
            # refusal — a torn sidecar is never served, same contract
            # as a torn checkpoint
            return fallback(f"{type(e).__name__}: {e}")
        if step <= self.model_step:
            return ("noswap", step)
        params = jax.device_put(tiers[self.tier])
        meta = payload.get("meta") or {}
        return ("swap", {
            "params": params,
            "predict": self._tier_predict(self.tier),
            "step": step,
            "digest": ckpt.quant_sidecar_digest(self.train_dir, step),
            "tier": self.tier,
            "source_artifact": ckpt.quant_sidecar_path(
                self.train_dir, step).name,
            "source_digest": meta.get("source_params_digest"),
        }, t0)

    def _read_weights(self, ptr_step: int):
        """The follower's ``read``: tier preference first (the quant
        sidecar when ``serve.precision_tier`` names one), then the
        digest-verified full-precision restore with
        fallback-to-previous-loadable-step — a torn/corrupt publish is
        skipped (journaled), never served. Returns a staged swap, or a
        no-swap marker when the fallback landed on (or behind) what we
        already serve."""
        t0 = time.time()
        if self.tier != "fp32":
            got = self._read_quant_tier(ptr_step, t0)
            if got is not None:
                return got
            # journaled fallback: this publish serves full precision
        on_event = lambda rec: self._journal(
            {"action": "follow_" + rec.get("action", "?"),
             **{k: v for k, v in rec.items()
                if k not in ("layer", "action")}})
        if self.tp_ranks > 1:
            # TP replica: the mesh-portable restore — the checkpoint
            # was saved under the TRAINER's world, and every rank of
            # this serving mesh takes only its shard of each leaf when
            # device_put_state places the result over the TP specs
            # below (restore journals follow_cross_world_restore when
            # the worlds differ)
            from ..parallel.api import restore_for_topology
            restored = restore_for_topology(
                self.model, self.cfg, self.topo, self.train_dir,
                self.template, on_event=on_event)
        else:
            restored = ckpt.restore_checkpoint(
                self.train_dir, self.template, None, on_event=on_event)
        if restored is None:
            return None
        state, _, at_step = restored
        if at_step <= self.model_step:
            # the newest publish was unusable and the fallback landed
            # on weights we already serve: consume the pointer step so
            # the follower stops re-reading the torn artifact
            return ("noswap", at_step)
        params = self.topo.device_put_state(state.params, self._param_specs)
        digest = ckpt.artifact_digest(self.train_dir, at_step)
        # name the artifact the restore actually read — single-file
        # layout only; a sharded (manifest) restore records None so
        # the serve_digest invariant keeps its historical step-based
        # match instead of name-matching a file that doesn't exist
        name = f"ckpt-{at_step:08d}.msgpack"
        if not (self.train_dir / name).exists():
            name = None
        return ("swap", {
            # predict None = "the replica's fp32 predict" — resolved at
            # install time so a test-wrapped self._predict stays live
            "params": params, "predict": None,
            "step": at_step, "digest": digest, "tier": "fp32",
            "source_artifact": name,
            "source_digest": digest,
        }, t0)

    def _install(self, staged: dict, t0: float,
                 initial: bool = False,
                 extra: dict | None = None) -> None:
        """Flip the staged weights in (batcher/boot thread only) and
        journal the swap with its tier + source identity. ``extra``:
        additional declared swap-record fields (the decode replica's
        sequences_pinned / sequences_restarted bookkeeping)."""
        prev = self.model_step
        self._params = staged["params"]
        if staged["predict"] is not None:
            self._predict = staged["predict"]
        elif self.model_tier not in (None, "fp32"):
            # downgrading a quant tier to fp32: restore the pristine
            # fp32 predict (a pure-fp32 replica never reassigns
            # self._predict, so tests wrapping it keep their wrapper)
            self._predict = self._predict_fp32
        self.model_step = staged["step"]
        self.model_digest = staged["digest"]
        self.model_tier = staged["tier"]
        self.model_source_digest = staged["source_digest"]
        self.swaps += 1
        rec = {"action": "weight_swap", "step": staged["step"],
               "from_step": prev, "digest": staged["digest"],
               "tier": staged["tier"],
               "source_artifact": staged["source_artifact"],
               "source_digest": staged["source_digest"],
               "swap_ms": round((time.time() - t0) * 1e3, 3),
               **(extra or {})}
        if initial:
            rec["initial"] = True
        self._journal(rec)

    def _load_initial(self, timeout_s: float = 600.0) -> None:
        deadline = time.time() + timeout_s
        while time.time() < deadline and not self._stop.is_set():
            got = self.follower.poll(self._read_weights)
            if got is not None and got[0] == "swap":
                _, staged, t0 = got
                self._install(staged, t0, initial=True)
                return
            time.sleep(min(1.0, self.scfg.poll_secs))
        raise TimeoutError(
            f"no loadable checkpoint in {self.train_dir} within "
            f"{timeout_s:.0f}s")

    def _warm_up(self) -> None:
        """What a replica runs on its first weights before it accepts a
        request. Nothing here: a predict bucket compiles on first use.
        The decode replica compiles its step's shapes."""

    def _follow_loop(self) -> None:
        while not self._stop.is_set():
            try:
                got = self.follower.poll(self._read_weights)
            except Exception as e:  # the service must outlive any read
                logger.warning("checkpoint follow failed (%s: %s)",
                               type(e).__name__, e)
                got = None
            if got is not None and got[0] == "swap":
                with self._staged_lock:
                    self._staged = got[1:]
            self._stop.wait(self.scfg.poll_secs)

    def _maybe_swap(self) -> None:
        """Batch-boundary flip: the in-flight batch already drained on
        the old weights; installing the staged buffer is one reference
        assignment. Journals step + digest + tier + swap latency."""
        with self._staged_lock:
            staged, self._staged = self._staged, None
        if staged is None:
            return
        install, t0 = staged
        if install["step"] <= self.model_step:
            return  # monotone: never swap backwards
        self._install(install, t0)

    # -- socket front door --------------------------------------------

    def _respond(self, conn, payload: dict) -> bool:
        try:
            # write deadline: a peer that stopped reading (half-open,
            # partitioned) costs at most conn_write_timeout_s, never a
            # wedged batcher — the tighter per-connection timeout the
            # decode loop sets stays in force
            wt = float(self.scfg.conn_write_timeout_s)
            cur = conn.gettimeout()
            if wt > 0 and (cur is None or cur > wt):
                conn.settimeout(wt)
            conn.sendall((json.dumps(payload) + "\n").encode())
            return True
        except OSError:
            return False  # client went away; the outcome is journaled
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _reject(self, conn, req_id, reason: str, admitted: bool) -> None:
        self._terminal("reject", req_id, reason=reason, admitted=admitted)
        self._respond(conn, {"id": req_id, "status": "rejected",
                             "reason": reason,
                             "model_step": self.model_step})

    def _meta(self) -> dict:
        return {"status": "ok", "meta": True,
                "model": self.cfg.model.name,
                "input_shape": list(self.model.input_shape),
                "input_dtype": str(np.dtype(self.model.input_dtype)),
                "model_step": self.model_step,
                # which representation this replica PREFERS vs what it
                # actually has installed right now (a sidecar fallback
                # makes these differ), plus the installed tier's source
                # identity — what lets a loadgen artifact record which
                # tier a sweep ACTUALLY measured
                "precision_tier": self.tier,
                "active_tier": self.model_tier,
                "model_digest": self.model_digest,
                "tier_source_digest": self.model_source_digest,
                "max_batch": self.scfg.max_batch}

    def _conn_abort(self, conn, reason: str, bytes_read: int) -> None:
        """Close a connection that never became a request — the read
        deadline fired or the peer went half-open. Nothing was
        admitted, so no terminal outcome is owed; the abort is
        journaled so the books explain the closed socket."""
        self._journal({"action": "conn_abort", "reason": reason,
                       "bytes_read": bytes_read})
        try:
            conn.close()
        except OSError:
            pass

    def _read_request(self, conn) -> bytes | None:
        """Read one request line under a TOTAL deadline — a slowloris
        peer trickling bytes (or sending none: the half-open case)
        costs one bounded stall of at most ``conn_read_timeout_s``,
        then the connection is aborted. Returns None when aborted."""
        total_s = max(0.1, float(self.scfg.conn_read_timeout_s))
        deadline = time.monotonic() + total_s
        buf = b""
        while b"\n" not in buf:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self._conn_abort(conn,
                                 "half_open" if not buf
                                 else "read_deadline", len(buf))
                return None
            conn.settimeout(remaining)
            try:
                chunk = conn.recv(65536)
            except socket.timeout:
                self._conn_abort(conn,
                                 "half_open" if not buf
                                 else "read_deadline", len(buf))
                return None
            if not chunk:
                break
            buf += chunk
            if len(buf) > _MAX_REQUEST_BYTES:
                self._reject(conn, None, "bad_request", admitted=False)
                return None
        return buf

    def _handle_conn(self, conn) -> None:
        """Read one request; admit it (or shed typed). Runs on a
        per-connection thread so a slow client can't stall admission."""
        req_id = None
        try:
            buf = self._read_request(conn)
            if buf is None:
                return  # _read_request aborted or rejected
            try:
                req = json.loads(buf.decode())
                if not isinstance(req, dict):
                    raise ValueError("request must be a JSON object")
            except (ValueError, UnicodeDecodeError):
                self._reject(conn, None, "bad_request", admitted=False)
                return
            if req.get("meta"):
                self._respond(conn, self._meta())
                return
            req_id = req.get("id")
            cached = self._dedup_get(req_id)
            if cached is not None:
                # this id already ran to a final outcome here (the
                # retry's first attempt, on this replica, before a
                # reset ate the response): answer from the cache —
                # exactly-once means never double-executing
                payload, done_at = cached
                with self._journal_lock:
                    self.dedup_hits += 1
                self._journal({"action": "dedup_hit", "id": req_id,
                               "status": payload.get("status"),
                               "age_s": round(time.time() - done_at, 3)})
                self._respond(conn, payload)
                return
            if self._stop.is_set():
                self._reject(conn, req_id, "shutting_down", admitted=False)
                return
            item = self._build_item(req, conn)
            if item is None:
                return  # _build_item already sent the typed reject
            try:
                # admission control: a full queue sheds IMMEDIATELY
                # with a typed reject — bounded queue, bounded latency,
                # never silent starvation
                self._queue.put_nowait(item)
            except queue.Full:
                self._reject(conn, req_id, "overloaded", admitted=False)
                return
            self._journal({"action": "admit", "id": req_id,
                           "deadline_ms": round(
                               (item.deadline_at - item.admitted_at)
                               * 1e3, 3)})
        except OSError:
            # the socket died before we could even reject; if nothing
            # was admitted there is no outcome to owe
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass

    def _build_item(self, req: dict, conn) -> _Pending | None:
        """Validate one request payload into a queue item, or send the
        typed ``bad_request`` and return None. The workload-shaped half
        of admission — the decode replica overrides it to parse
        ``prompt`` requests instead of fixed-shape ``inputs``."""
        req_id = req.get("id")
        try:
            inputs = np.asarray(req["inputs"],
                                dtype=np.dtype(self.model.input_dtype))
        except (KeyError, ValueError, TypeError):
            self._reject(conn, req_id, "bad_request", admitted=False)
            return None
        if tuple(inputs.shape) != tuple(self.model.input_shape):
            self._reject(conn, req_id, "bad_request", admitted=False)
            return None
        now = time.time()
        deadline_ms = req.get("deadline_ms",
                              self.scfg.default_deadline_ms)
        return _Pending(req_id, inputs, conn, now,
                        now + float(deadline_ms) / 1e3)

    def _accept_loop(self) -> None:
        assert self._sock is not None
        self._sock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            t = threading.Thread(target=self._handle_conn, args=(conn,),
                                 daemon=True)
            with self._conn_lock:
                self._conn_threads = {x for x in self._conn_threads
                                      if x.is_alive()}
                self._conn_threads.add(t)
            t.start()

    # -- the batcher --------------------------------------------------

    @staticmethod
    def _bucket(n: int, max_batch: int) -> int:
        b = 1
        while b < n and b < max_batch:
            b *= 2
        return min(b, max_batch)

    def _gather(self) -> list[_Pending]:
        """Pop up to ``max_batch`` requests: block briefly for the
        first, then drain whatever arrived within the batch window."""
        try:
            first = self._queue.get(timeout=0.05)
        except queue.Empty:
            return []
        items = [first]
        window = self.scfg.batch_window_ms / 1e3
        deadline = time.monotonic() + window
        while len(items) < self.scfg.max_batch:
            remaining = deadline - time.monotonic()
            try:
                items.append(self._queue.get(
                    timeout=max(0.0, remaining)))
            except queue.Empty:
                break
        return items

    def _run_batch(self, items: list[_Pending]) -> None:
        now = time.time()
        live: list[_Pending] = []
        for it in items:
            if now >= it.deadline_at:
                self._reject(it.conn, it.req_id, "deadline_exceeded",
                             admitted=True)
            else:
                live.append(it)
        if not live:
            return
        bucket = self._bucket(len(live), self.scfg.max_batch)
        dtype = np.dtype(self.model.input_dtype)
        x = np.zeros((bucket, *self.model.input_shape), dtype)
        for i, it in enumerate(live):
            x[i] = it.inputs
        step, digest, tier = (self.model_step, self.model_digest,
                              self.model_tier)
        probs = np.asarray(jax.device_get(self._predict(self._params, x)))
        for i, it in enumerate(live):
            p = probs[i]
            self._terminal(
                "respond", it.req_id, model_step=step, tier=tier,
                batch=len(live), bucket=bucket,
                latency_ms=round((time.time() - it.admitted_at) * 1e3, 3))
            payload = {
                "id": it.req_id, "status": "ok", "model_step": step,
                "model_digest": digest, "tier": tier,
                "prediction": int(np.argmax(p)),
                "probs": [round(float(v), 6) for v in p]}
            # cache BEFORE sending: if the send dies mid-wire (reset,
            # partition) the retry finds the completed outcome here
            self._dedup_put(it.req_id, payload)
            self._respond(it.conn, payload)

    def _batch_loop(self) -> None:
        while not self._stop.is_set():
            self._maybe_swap()
            items = self._gather()
            if items:
                self._run_batch(items)
            self._maybe_heartbeat()
        # graceful drain: everything still queued gets a TYPED reject —
        # a stopping replica sheds, it never silently drops
        while True:
            try:
                it = self._queue.get_nowait()
            except queue.Empty:
                break
            self._reject(it.conn, it.req_id, "shutting_down", admitted=True)
        self._maybe_heartbeat()

    # -- lifecycle ----------------------------------------------------

    def start(self) -> None:
        """Load initial weights, bind, publish ``serve.json``, and
        start the follower/accept/batcher threads. Idempotent-unsafe:
        one start per replica object."""
        endpoint_path = self.serve_dir / "serve.json"
        endpoint_path.unlink(missing_ok=True)  # stale incarnation
        self._load_initial()
        self._warm_up()
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((self.scfg.host, self.scfg.port))
        self._sock.listen(128)
        self.bound_port = self._sock.getsockname()[1]
        for target in (self._follow_loop, self._accept_loop,
                       self._batch_loop):
            t = threading.Thread(target=target, daemon=True,
                                 name=f"serve-{target.__name__}")
            t.start()
            self._threads.append(t)
        import os
        tmp = endpoint_path.with_suffix(".tmp")
        tmp.write_text(json.dumps(
            {"host": self.scfg.host, "port": self.bound_port,
             "pid": os.getpid(), "model_step": self.model_step,
             "started_at": time.time()}))
        tmp.replace(endpoint_path)
        self._journal({"action": "serve_start", "port": self.bound_port,
                       "model_step": self.model_step,
                       "precision_tier": self.tier,
                       "active_tier": self.model_tier,
                       "queue_depth": self.scfg.queue_depth,
                       "max_batch": self.scfg.max_batch})
        self._maybe_heartbeat()
        logger.info("serving %s step=%d on %s:%d", self.cfg.model.name,
                    self.model_step, self.scfg.host, self.bound_port)

    def request_stop(self) -> None:
        self._stop.set()

    def stop(self) -> None:
        """Stop accepting, drain the queue with typed rejects, close."""
        self.request_stop()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        for t in self._threads:
            t.join(timeout=30)
        # close the admit-vs-drain race: a connection handler that
        # passed its stop check just before request_stop() may enqueue
        # AFTER the batcher's final drain — join the (short-lived)
        # handler threads, then drain once more so every admitted
        # request still gets its typed terminal outcome
        with self._conn_lock:
            stragglers = list(self._conn_threads)
        for t in stragglers:
            t.join(timeout=10)
        while True:
            try:
                it = self._queue.get_nowait()
            except queue.Empty:
                break
            self._reject(it.conn, it.req_id, "shutting_down",
                         admitted=True)
        self._journal({"action": "serve_stop",
                       "terminals": self._terminals,
                       "model_step": self.model_step, "swaps": self.swaps})
        with self._journal_lock:
            self._journal_closed = True
            self._serve_log.close()
            self._heartbeat.close()

    def serve_forever(self, install_signal_handlers: bool = True) -> None:
        """The process entry: start, park until SIGTERM/SIGINT (the
        graceful drain the supervisor's ``stop_all`` relies on), stop."""
        if install_signal_handlers:
            import signal

            def handler(signum, frame):
                logger.warning("received signal %s — draining and "
                               "stopping", signum)
                self.request_stop()

            for sig in (signal.SIGTERM, signal.SIGINT):
                try:
                    signal.signal(sig, handler)
                except (ValueError, OSError):
                    pass
        self.start()
        try:
            while not self._stop.is_set():
                self._stop.wait(0.5)
        finally:
            self.stop()
