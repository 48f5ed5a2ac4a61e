"""The training loop (≙ src/distributed_train.py:109-408, redesigned).

What the reference's 300-line ``train()`` does with a Supervisor,
queue-runner threads, a Twisted startup barrier, and per-step
``sess.run``s, this does with: build step → jit once → feed sharded
batches → log/checkpoint on cadence. There is no chief (every process
is identical; process 0 merely owns file writes), no second forward
pass per step (reference quirk at :332-335), and metric fetches are
batched at log points so the device pipeline stays async between them.
"""

from __future__ import annotations

import math
import signal
import threading
import time
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np

from ..core.config import ExperimentConfig
from ..core.log import JsonlSink, get_logger, step_line
from ..core.mesh import Topology, make_topology
from ..data.datasets import Datasets, load_datasets
from ..data.device_prefetch import DevicePrefetcher
from ..data.pipeline import device_prefetch_pays, make_train_iterator
from .evaluation import run_full_eval
from ..models.registry import Model, get_model
from ..obsv import spans
from ..obsv.timing import StepTimeCollector
from ..parallel.api import (TrainState, build_eval_step, build_train_step,
                            canonical_save_state, init_train_state,
                            logical_params, restore_for_topology,
                            state_partition_specs, world_signature,
                            zero1_plan_for)
from . import checkpoint as ckpt
from . import storage
from .lr_schedule import (constant, decay_steps_for, exponential_decay,
                          warmup_polynomial_decay)

logger = get_logger("train")


class _NonFiniteLoss(Exception):
    """Raised inside the flush path when the NaN/Inf guard trips;
    carries the step the poison was first observed at."""

    def __init__(self, step: int, loss: float):
        super().__init__(f"nonfinite loss {loss!r} at step {step}")
        self.step = step
        self.loss = loss


def _params_finite(state) -> bool:
    """True when every floating-point param leaf is finite — the
    is-this-checkpoint-poisoned test the NaN-guard rollback applies."""
    for leaf in jax.tree.leaves(state.params):
        a = np.asarray(jax.device_get(leaf))
        if np.issubdtype(a.dtype, np.floating) and not np.isfinite(a).all():
            return False
    return True


class Trainer:
    """Builds the whole training stack from one ExperimentConfig."""

    def __init__(self, cfg: ExperimentConfig, topo: Topology | None = None,
                 datasets: Datasets | None = None):
        self.cfg = cfg
        self.topo = topo or make_topology(cfg.mesh)
        # precision.compute_dtype overrides the model section's knob
        # when set — one shared resolution (core.config) so the
        # evaluator/serving tiers build the identical model
        from ..core.config import effective_model_config
        self.model: Model = get_model(effective_model_config(cfg))
        self.datasets = datasets if datasets is not None else load_datasets(
            cfg.data, cfg.model.image_size, cfg.model.num_channels,
            cfg.model.num_classes, cfg.model.seq_len, cfg.model.vocab_size)

        n = self.topo.num_replicas
        if cfg.data.batch_size % n != 0:
            raise ValueError(f"global batch {cfg.data.batch_size} not divisible "
                             f"by {n} replicas")
        if cfg.train.grad_accum_steps < 1:
            raise ValueError(f"train.grad_accum_steps must be >= 1, got "
                             f"{cfg.train.grad_accum_steps}")
        self.grad_accum = int(cfg.train.grad_accum_steps)
        # images/sec accounting and the epoch-based decay pacing both
        # key off the EFFECTIVE batch — one optimizer application
        # consumes batch_size × accum examples
        self.effective_batch = cfg.data.batch_size * self.grad_accum
        # DP×SP: tokens sharded over the seq axis too (transformer only)
        n_seq = self.topo.mesh.shape[self.topo.seq_axis]
        self.seq_sharded = n_seq > 1
        if self.seq_sharded and cfg.model.seq_len % n_seq != 0:
            raise ValueError(f"seq_len {cfg.model.seq_len} not divisible by "
                             f"seq_parallelism {n_seq}")
        n_stage = self.topo.mesh.shape[self.topo.stage_axis]
        if n_stage > 1:
            mb = cfg.mesh.pipeline_microbatches
            if (cfg.data.batch_size // n) % mb != 0:
                raise ValueError(
                    f"per-replica batch {cfg.data.batch_size // n} not "
                    f"divisible by pipeline_microbatches {mb}")
            if cfg.model.num_layers % n_stage != 0:
                raise ValueError(
                    f"num_layers {cfg.model.num_layers} not divisible by "
                    f"pipeline_parallelism {n_stage}")
        from ..parallel.policies import resolve_aggregate_k
        k = resolve_aggregate_k(cfg.sync, n)
        # LR schedule keyed to applied updates.
        if cfg.optim.schedule == "polynomial":
            # linear warmup + polynomial decay — the LARS/LAMB
            # large-batch pacing (train/lr_schedule.py);
            # decay_total_steps=0 resolves to the run's step budget
            total = cfg.optim.decay_total_steps or cfg.train.max_steps
            self.schedule = warmup_polynomial_decay(
                cfg.optim.initial_learning_rate, cfg.optim.warmup_steps,
                total, cfg.optim.end_learning_rate, cfg.optim.poly_power)
        elif cfg.optim.learning_rate_decay_factor == 1.0:
            self.schedule = constant(cfg.optim.initial_learning_rate)
        else:
            # exponential staircase; decay_steps ÷ k
            # (src/distributed_train.py:143-156)
            steps = decay_steps_for(self.datasets.train.num_examples,
                                    self.effective_batch,
                                    cfg.optim.num_epochs_per_decay, k)
            self.schedule = exponential_decay(
                cfg.optim.initial_learning_rate, steps,
                cfg.optim.learning_rate_decay_factor, cfg.optim.staircase)

        self.step_fn = build_train_step(self.model, cfg, self.topo, self.schedule)
        self.eval_fn = build_eval_step(self.model, cfg, self.topo)
        self.state_specs = state_partition_specs(self.model, cfg, self.topo)
        # ZeRO-1 shard plan (parallel.shard_weight_update): governs the
        # momentum layout in self.state AND the checkpoint conversion —
        # artifacts always carry the canonical logical layout
        # (parallel/api.py canonical_save_state), so a sharded run's
        # checkpoint restores onto any discipline and the path digests
        # stay stable across the knob.
        self._zero1_plan = zero1_plan_for(self.model, cfg, self.topo)
        self.state: TrainState = init_train_state(self.model, cfg, self.topo)
        self.state = self.topo.device_put_state(self.state, self.state_specs)

        self.train_iter = make_train_iterator(
            self.datasets.train, cfg.data, seed=cfg.train.seed,
            host_id=jax.process_index(), num_hosts=jax.process_count())
        if self.grad_accum > 1:
            # accum consecutive batches concatenated per step; the
            # inner cursor just advances accum batches per step
            # (data/pipeline.py GradAccumFeed)
            from ..data.pipeline import GradAccumFeed
            self.train_iter = GradAccumFeed(self.train_iter,
                                            self.grad_accum)

        # Dispatch-ahead feed: batches staged through device_put_batch
        # on a producer thread, device_prefetch_depth ahead, so host
        # assembly + H2D overlap device compute instead of sitting on
        # its critical path (data/device_prefetch.py). One shared
        # policy for when the producer thread pays: data.pipeline.
        # device_prefetch_pays (spare core, or an accelerator backend
        # whose drains park the host GIL-free).
        self._device_prefetch = (cfg.data.device_prefetch
                                 and cfg.data.device_prefetch_depth > 0
                                 and device_prefetch_pays())
        self._train_feed: DevicePrefetcher | None = None

        # Measured-timing vector staging: validate once, reuse the
        # sharding + host assembly buffer every step (core/mesh.py
        # MeasuredStage) instead of rebuilding both per step.
        self._measured_stage = (self.topo.measured_stage()
                                if self.topo.measured_timing_supported
                                else None)

        self.collector = StepTimeCollector(num_replicas=n)
        # Adaptive straggler discipline (sync.adaptive): the controller
        # watches the collector's rolling CDF and swaps the traced
        # [k, timeout_ms, interval_ms] step input at flush cadence
        # (train/discipline.py). Every process runs the SAME controller
        # on the SAME replicated [n] timing metrics, so all processes
        # swap identically; only the writer journals the begin/complete
        # pair (_sink_write gates).
        self._discipline = None
        if cfg.sync.adaptive:
            from ..parallel.api import make_discipline_vector
            from .discipline import DisciplineController
            self._discipline = DisciplineController(
                cfg.sync, n, self._sink_write, make_discipline_vector)
            self.collector.enable_rolling_cdf(cfg.sync.adaptive_window_steps)
        # comm-overlap gauges (parallel.comm_buckets > 1): the bucket
        # structure is known at build; the per-bucket comm calibration
        # joins in precompile() (obsv/timing.py set_overlap_info)
        self._comm_buckets = None
        self._bucket_pad_elems = None
        if (self._zero1_plan is not None
                and self._zero1_plan.comm_buckets > 1):
            from ..parallel.partition_rules import comm_bucket_assignment
            buckets = comm_bucket_assignment(self._zero1_plan)
            # empty when no leaf actually shards (e.g. a high
            # shard_min_leaf_size) — then bucketing is NOT active and
            # the overlap report key must not appear
            if buckets:
                lps = jax.tree.leaves(
                    self._zero1_plan.leaf_plans,
                    is_leaf=lambda x: hasattr(x, "sharded"))
                self._comm_buckets = buckets
                # derived once; precompile() re-reports with the
                # calibrated per-bucket comm ms added
                self._bucket_pad_elems = [sum(lps[i].pad for i in b)
                                          for b in buckets]
                self.collector.set_overlap_info(len(buckets),
                                                self._bucket_pad_elems)
        # Test/fault-injection seam: extra per-LOCAL-replica delay (ms)
        # added onto the measured vector — lets tests (and chaos runs)
        # make a specific replica the straggler deterministically.
        self.delay_injection_ms: np.ndarray | None = None
        # Per-replica DEVICE-side timing (sync.measure_device_skew):
        # the probe measures each local replica device's queue-drain
        # skew each step; it joins the measured [n] vector so the
        # policies rank on genuinely per-DEVICE time, not one host dt
        # per process (obsv/timing.py:ReplicaDeviceProbe).
        self._device_probe = None
        self._last_device_skew: np.ndarray | None = None
        if (cfg.sync.measure_device_skew
                and self.topo.measured_timing_supported):
            from ..obsv.timing import ReplicaDeviceProbe
            self._device_probe = ReplicaDeviceProbe(self.topo)
        # Chaos seam for REAL device-side delay (not a config constant):
        # {local_replica_index: (jitted_fn, device_resident_arg)} —
        # dispatched async right after each step so the named replica's
        # device genuinely drains later; the probe observes it.
        self.device_work_injection: dict[int, tuple] | None = None
        self.is_writer = jax.process_index() == 0
        self.train_dir = Path(cfg.train.train_dir)
        # Install the fsync policy process-wide BEFORE any durable
        # write (including the resume below) — an unknown value is a
        # typed ConfigError at trainer build, not a downstream surprise
        storage.set_durability(cfg.train.durability)
        self._sharded_ckpt = ckpt.state_needs_sharded_save(self.state)
        self._use_async_ckpt = cfg.train.async_checkpoint and (
            self.is_writer or self._sharded_ckpt)
        if (self._sharded_ckpt and cfg.train.save_interval_secs > 0
                and jax.process_count() > 1):
            # every process must write its shard for the SAME steps;
            # per-process wall clocks cannot agree on a seconds-based
            # trigger, so each periodic checkpoint would be torn
            # (shard files at different steps, no complete set)
            raise ValueError(
                "a cross-process sharded layout needs a deterministic "
                "checkpoint cadence every process agrees on: set "
                "train.save_interval_steps (and save_interval_secs=0)")
        self._checkpointer: ckpt.AsyncCheckpointer | None = None
        # Donation-safe async snapshot (train.async_snapshot): cadence
        # saves dispatch an async device copy into fresh un-donated
        # buffers — enqueued before the next step's program, so the
        # copy reads the state before donation reuses it — and the D2H
        # fetch + canonical conversion run on the checkpointer's worker
        # thread. Single-file layouts only: the per-host sharded format
        # needs every process's synchronized snapshot semantics as-is.
        self._async_snapshot = (self._use_async_ckpt
                                and cfg.train.async_snapshot
                                and not self._sharded_ckpt)
        self._snapshot_fn = None  # jitted un-donated copy, built lazily
        # Post-training quantization at publish time
        # (quant.publish_tiers): int8/bf16 serving tiers written as a
        # digest-verified sidecar next to every cadence save. Built
        # here so a bad tier name is a typed ConfigError at Trainer
        # build; the pass itself runs after each save — on the
        # AsyncCheckpointer worker for async saves, inline otherwise —
        # and never fails a checkpoint (sidecars are additive).
        self._quant_publisher = None
        if cfg.quant.resolved_publish_tiers():
            from ..parallel.api import abstract_train_params
            from ..quant.ptq import QuantPublisher
            self._quant_publisher = QuantPublisher(
                self.model, cfg,
                abstract_train_params(self.model, cfg, self.topo),
                calib_inputs=self.datasets.test.images,
                calib_labels=self.datasets.test.labels)
        self._sink: JsonlSink | None = None
        # Structured recovery events (NaN rollbacks, corrupt-checkpoint
        # fallbacks, preemption flushes) — the trainer-side half of the
        # journal obsv.journal.summarize_recovery aggregates.
        self._recovery_sink: JsonlSink | None = None
        self._preempt_requested: str | None = None
        # TB scalars on the summary cadence (≙ chief summary writes,
        # src/distributed_train.py:382-390)
        self._tb = None
        if self.is_writer and cfg.train.summary_every_steps > 0:
            from ..obsv.tb import SummaryWriter
            self._tb = SummaryWriter(self.train_dir / "tb")
        self._series: list[tuple[float, int, float, float]] = []  # (t, step, loss, acc)
        self._last_save_time = time.time()
        self._start_step = 0
        # AOT precompile bookkeeping (cfg.compile): the compile record
        # is journaled into train_log.jsonl separately from step time,
        # and re-journaled after a standby adoption re-roots the log.
        self._compile_info: dict[str, Any] | None = None
        self._compile_logged = False

        if cfg.train.resume:
            self._maybe_resume()

    # ------------------------------------------------------------------

    @property
    def train_feed(self):
        """The dispatch-ahead feed over the CURRENT ``train_iter`` —
        the DevicePrefetcher when enabled, the raw iterator otherwise.
        Resolved lazily so the established seam of swapping
        ``trainer.train_iter`` after construction (tests, chaos
        harnesses injecting a slow ingest) keeps working: a swap makes
        the previous wrapper stale and a fresh one is built around the
        new iterator.

        One documented limit: a swapped-in iterator with NO
        state()/restore() supports a single run() — the end-of-run
        stop() cannot push its read-ahead back into such an iterator,
        so the wrapper closes (loudly, at the next next()) rather than
        resume with a silent hole in the batch stream."""
        if not self._device_prefetch:
            return self.train_iter
        if (self._train_feed is None
                or self._train_feed.inner is not self.train_iter):
            if self._train_feed is not None:
                # join the stale wrapper's producer now — left to GC it
                # would keep consuming the old iterator (and hold its
                # cursor at the read-ahead position) indefinitely
                self._train_feed.stop()
            self._train_feed = DevicePrefetcher(
                self.train_iter,
                put=lambda b: self.topo.device_put_batch(
                    b, seq_sharded=self.seq_sharded),
                depth=self.cfg.data.device_prefetch_depth)
        return self._train_feed

    def _recovery_event(self, record: dict) -> None:
        """Append one structured recovery event to
        ``train_dir/recovery_journal.jsonl`` (writer process only)."""
        if not self.is_writer:
            return
        if self._recovery_sink is None:
            self._recovery_sink = JsonlSink(
                self.train_dir / "recovery_journal.jsonl")
        self._recovery_sink.write(
            {"event": "recovery", "time": time.time(), **record})

    def _maybe_resume(self) -> None:
        # mesh-portable restore: an artifact saved under ANY world size
        # reshards onto this run's mesh — the ZeRO-1 plan (padding,
        # chunk ownership) is re-derived from the CURRENT replica
        # count, and a world change is journaled as
        # action:"cross_world_restore" (parallel/api.py)
        restored = restore_for_topology(self.model, self.cfg, self.topo,
                                        self.train_dir, self.state,
                                        on_event=self._recovery_event)
        if restored is None:
            return
        state, extra, step = restored
        # The gpipe layer-stacked and 1f1b chunk-interleaved layouts
        # have identical tree structure and leaf shapes but DIFFERENT
        # layer order — a shape-matched restore across schedules would
        # silently permute the model. Refuse instead.
        saved_mesh = (extra.get("config") or {}).get("mesh", {})
        if self.topo.mesh.shape[self.topo.stage_axis] > 1:
            saved = (saved_mesh.get("pipeline_schedule", "gpipe"),
                     saved_mesh.get("pipeline_chunks", 1))
            want = (self.cfg.mesh.pipeline_schedule,
                    self.cfg.mesh.pipeline_chunks)
            if saved != want:
                raise ValueError(
                    f"checkpoint was written with pipeline layout "
                    f"(schedule, chunks)={saved} but this run uses "
                    f"{want}; the stacked layer orders differ — "
                    "restoring would silently permute the model")
        self.state = self.topo.device_put_state(state, self.state_specs)
        if "data_iter" in extra:
            try:
                # through the feed: a prefetching feed must also drop
                # anything it staged ahead of the restored cursor
                # (RuntimeError: DevicePrefetcher over a non-restorable
                # inner — same degrade-to-fresh-stream semantics)
                self.train_feed.restore(extra["data_iter"])
            except (AttributeError, KeyError, ValueError, RuntimeError):
                logger.warning("could not restore data-iterator state; "
                               "restarting stream")
        self._start_step = int(jax.device_get(self.state.step))
        logger.info("resumed from checkpoint step=%d (loop step %d)",
                    step, self._start_step)

    def _save(self, step: int) -> None:
        # Sharded layouts (a model/seq/stage/expert axis crossing
        # process boundaries): EVERY process writes its shard file;
        # process 0 additionally writes the manifest + pointer
        # (train/checkpoint.py per-host format). Otherwise process 0
        # writes the classic single file alone.
        if not self.is_writer and not ckpt.state_needs_sharded_save(self.state):
            return
        with spans.span(spans.TRAIN_SAVE, step=step):
            self._save_now()

    def _save_now(self) -> None:
        t0 = time.perf_counter()
        # the world the artifact is saved under: what lets a restore
        # tell "same world" from "resized world, reshard" and the
        # supervisor name both sides of an elastic reconfigure
        extra = {"config": self.cfg.to_dict(),
                 "world": world_signature(self.topo)}
        # through the feed: a prefetching feed reports the cursor of
        # the last CONSUMED batch, not the producer's read-ahead
        # position — a resume must replay batches the step never saw
        iter_state = getattr(self.train_feed, "state", None)
        if callable(iter_state) and getattr(self.train_feed, "has_state", True):
            extra["data_iter"] = self.train_feed.state()
        at_step = int(jax.device_get(self.state.step))
        # quant sidecar publish rides the save — BEFORE the
        # artifact/pointer write (on the worker thread for async
        # paths): a follower that sees the pointer name a new step
        # must find its sidecar already on disk, else a fast poll
        # falls back to fp32 and never revisits that step's tier
        publish = None
        if self._quant_publisher is not None and self.is_writer:
            pub, tdir = self._quant_publisher, self.train_dir
            publish = lambda st, s: pub.publish(tdir, st, s)  # noqa: E731
        # arm any at_step-gated disk fault scripts for this save
        storage.note_step(at_step)
        try:
            self._save_inner(at_step, extra, publish)
        except OSError as e:
            # Graceful ENOSPC/EIO degradation: a cadence save that
            # still fails after the bounded I/O retries is journaled
            # and SKIPPED — the run keeps training and the next
            # cadence tries again (async writes report here through
            # the checkpointer's on_error hook instead; a persistently
            # dead disk still stops the run via its consecutive-
            # failure bound).
            logger.error("checkpoint save for step=%d failed (%s) — "
                         "skipping this cadence", at_step, e)
            self._recovery_event({"layer": "train",
                                  "action": "save_failed",
                                  "step": at_step,
                                  "error": f"{type(e).__name__}: {e}",
                                  "errno": getattr(e, "errno", None),
                                  "where": "sync"})
            self._last_save_time = time.time()
            return
        # what the step loop actually paid for this save (async-snapshot
        # dispatch, or the sync host fetch + canonical conversion);
        # journaled, tests/test_async_checkpoint.py reads it
        stall_ms = (time.perf_counter() - t0) * 1e3
        self.collector.add_snapshot_stall_ms(stall_ms)
        # "at_step", deliberately NOT "step": the log-tail parsers
        # (launch/cluster.py parse_poll_output and the resume watch)
        # treat any intact record carrying "step" as training progress
        self._sink_write({"event": "save", "time": time.time(),
                          "at_step": at_step,
                          "save_stall_ms": round(stall_ms, 3),
                          "async_snapshot": self._async_snapshot,
                          **({"quant_tiers":
                              list(self._quant_publisher.tiers)}
                             if publish is not None else {})})
        self._last_save_time = time.time()

    def _ckpt_save_failed(self, step: int, e: Exception) -> None:
        """AsyncCheckpointer on_error hook (worker thread): journal the
        failed background write as a ``save_failed`` recovery event."""
        self._recovery_event({"layer": "train", "action": "save_failed",
                              "step": step,
                              "error": f"{type(e).__name__}: {e}",
                              "errno": getattr(e, "errno", None),
                              "where": "async"})

    def _save_inner(self, at_step: int, extra: dict, publish) -> None:
        if self._async_snapshot:
            # donation-safe snapshot, backend-matched (both variants
            # leave the canonical-layout conversion + the state-dict
            # walk + serialization to the worker thread):
            #   * CPU client — host VIEWS via device_get: PJRT
            #     copy-on-donate protects buffers with live external
            #     references, so the views keep their pre-donation
            #     values, and the grab is
            #     ~free where a device-side copy would execute a
            #     SYNCHRONOUS memcpy at dispatch (measured ~10 ms for
            #     the flagship CNN state).
            #   * accelerators — an async on-device copy into fresh
            #     un-donated buffers, enqueued ahead of the next
            #     step's donating program (so the copy reads the
            #     buffers first); device_get here would be the
            #     blocking D2H stall this knob exists to remove.
            if self._checkpointer is None or self._checkpointer.closed:
                self._checkpointer = ckpt.AsyncCheckpointer(
                    on_error=self._ckpt_save_failed)
            plan = self._zero1_plan
            if jax.default_backend() == "cpu":
                snap = ckpt.host_view_snapshot(self.state)
                prepare = (lambda s: ckpt.snapshot_for_save(
                    canonical_save_state(ckpt.materialize_snapshot(s),
                                         plan)))
            else:
                if self._snapshot_fn is None:
                    import jax.numpy as jnp
                    self._snapshot_fn = jax.jit(
                        lambda s: jax.tree.map(jnp.copy, s))
                snap = self._snapshot_fn(self.state)
                prepare = (lambda s: ckpt.snapshot_for_save(
                    canonical_save_state(s, plan)))
            self._checkpointer.save(
                self.train_dir, snap, at_step, extra=extra,
                keep=self.cfg.train.keep_checkpoints, prepare=prepare,
                publish=publish)
        else:
            # canonical layout on disk: replica-sharded (ZeRO-1)
            # momentum — and resident-sharded params — unpack to their
            # logical shapes so the artifact (and its canonical path
            # digest) is identical to a replicated run's. Only when
            # this process can materialize the buffers (always true
            # single-process); a cross-process sharded layout saves
            # its live layout via the per-host shard format instead.
            state_to_save = self.state
            if (self._zero1_plan is not None
                    and not ckpt.state_needs_sharded_save(self.state)):
                state_to_save = canonical_save_state(self.state,
                                                     self._zero1_plan)
            if self._use_async_ckpt:
                if self._checkpointer is None or self._checkpointer.closed:
                    self._checkpointer = ckpt.AsyncCheckpointer(
                        on_error=self._ckpt_save_failed)
                self._checkpointer.save(self.train_dir, state_to_save,
                                        at_step, extra=extra,
                                        keep=self.cfg.train.keep_checkpoints,
                                        no_skip=self._sharded_ckpt,
                                        publish=publish)
            else:
                if publish is not None:
                    publish(state_to_save, at_step)
                ckpt.save_checkpoint(self.train_dir, state_to_save, at_step,
                                     extra=extra,
                                     keep=self.cfg.train.keep_checkpoints)

    def _rollback_to_last_good(self, err: _NonFiniteLoss) -> int:
        """NaN-guard rollback: restore the newest checkpoint whose
        params are finite (a cadence save may already have captured the
        poison) and return the loop step to continue from. The guard
        exists for transient corruption — a flipped bit, a bad host —
        not for genuinely divergent optimization, which will reproduce
        the NaN and exhaust ``nan_guard_max_rollbacks``."""
        for s in sorted(ckpt.loadable_steps(self.train_dir), reverse=True):
            try:
                # the mesh-portable path (rollback candidates may
                # predate an elastic resize of this very run)
                state, extra, got = restore_for_topology(
                    self.model, self.cfg, self.topo, self.train_dir,
                    self.state, step=s)
            except Exception as e:
                self._recovery_event({"layer": "train",
                                      "action": "rollback_candidate_unusable",
                                      "step": s, "error": str(e)})
                continue
            if not _params_finite(state):
                self._recovery_event({"layer": "train",
                                      "action": "rollback_candidate_poisoned",
                                      "step": s})
                continue
            self.state = self.topo.device_put_state(state, self.state_specs)
            if "data_iter" in extra:
                try:
                    self.train_feed.restore(extra["data_iter"])
                except (AttributeError, KeyError, ValueError, RuntimeError):
                    logger.warning("could not restore data-iterator state "
                                   "on rollback; restarting stream")
            loop_step = int(jax.device_get(self.state.step))
            logger.warning("nonfinite loss at step %d — rolled back to "
                           "checkpoint step=%d", err.step, loop_step)
            self._recovery_event({"layer": "train", "action": "nan_rollback",
                                  "from_step": err.step,
                                  "to_step": loop_step,
                                  "loss": repr(err.loss)})
            return loop_step
        raise RuntimeError(
            f"nonfinite loss at step {err.step} and no finite checkpoint "
            "to roll back to") from err

    def _install_preempt_handlers(self) -> dict | None:
        """SIGTERM/SIGINT → finish the current step, flush a
        checkpoint, stop cleanly (the CLI exits with
        train.resumable_exit_code). Main thread only — elsewhere the
        signal API refuses, and the process owner is handling signals
        itself."""
        if not self.cfg.train.handle_preemption:
            return None
        if threading.current_thread() is not threading.main_thread():
            return None

        def handler(signum, frame):
            self._preempt_requested = signal.Signals(signum).name
            logger.warning("received %s — will flush a checkpoint and "
                           "stop (resumable)", self._preempt_requested)

        saved: dict = {}
        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                saved[sig] = signal.signal(sig, handler)
        except (ValueError, OSError):
            for sig, old in saved.items():
                signal.signal(sig, old)
            return None
        return saved

    def _sink_write(self, record: dict) -> None:
        if self.is_writer:
            if self._sink is None:
                log_path = self.train_dir / "train_log.jsonl"
                if self._start_step == 0 and log_path.exists():
                    # fresh run (not a resume) into a reused train_dir:
                    # starting over must not concatenate onto an older
                    # run's step series — every report/figure consumer
                    # reads this file as ONE monotone series
                    log_path.unlink()
                self._sink = JsonlSink(log_path)
            self._sink.write(record)

    def _dump_series(self) -> None:
        """≙ worker%d_time_acc.npy dumps (src/distributed_train.py:373-379),
        plus the [steps, n_replicas] compute-time matrix the CDF report
        plots (≙ the RPC-gossiped ELAPSED TIMES tables,
        src/timeout_manager.py:31-70)."""
        if self.is_writer and self._series:
            np.save(self.train_dir / "time_acc.npy", np.asarray(self._series))
            m = self.collector.matrix()
            if m.size:
                np.save(self.train_dir / "step_times.npy", m)

    def precompile(self) -> dict[str, Any]:
        """AOT-compile the train step BEFORE the first batch (ROADMAP
        item 5): compile time is measured here — and journaled as its
        own ``event: "compile"`` record — instead of hiding inside the
        first step's wall time, and a warm standby can park fully
        compiled. A warm persistent compilation cache
        (core/compile_cache.py) turns the compile into a cache read;
        idempotent per Trainer."""
        if self._compile_info is not None:
            return self._compile_info
        img = self.datasets.train.images
        lbl = self.datasets.train.labels
        B = self.effective_batch  # accum batches arrive concatenated
        batch = {"image": np.zeros((B, *img.shape[1:]), img.dtype),
                 "label": np.zeros((B, *lbl.shape[1:]), lbl.dtype)}
        gbatch = self.topo.device_put_batch(batch,
                                            seq_sharded=self.seq_sharded)
        from ..core.compile_cache import cache_stats
        # Deliberately NOT enable_persistent_cache here: flipping jax's
        # global cache is an entry-point action (launch CLI,
        # chip_smoke.py), not something each Trainer of a sweep redoes.
        # The stats read whatever directory the entry point enabled.
        before = cache_stats()
        info = self.step_fn.precompile(self.state, gbatch)
        if before["dir"] is not None:
            after = cache_stats()
            # zero new entries across a compile = every program came
            # out of the persistent cache — the warm-restart evidence
            # the worker's compile record carries
            info["persistent_cache"] = {
                "dir": after["dir"],
                "entries": after["entries"],
                "new_entries": after["entries"] - before["entries"],
                "hits": after["hits"] - before["hits"],
                "misses": after["misses"] - before["misses"]}
        logger.info("precompiled train step in %.2fs (source=%s)",
                    info["compile_s"], info["source"])
        if self._comm_buckets:
            # per-bucket comm calibration (small probe compiles — only
            # when overlap is on, and never fatal to the fast path)
            try:
                from ..parallel.api import measure_bucket_comm_ms
                self.collector.set_overlap_info(
                    len(self._comm_buckets), self._bucket_pad_elems,
                    measure_bucket_comm_ms(self.topo, self._zero1_plan))
            except Exception as e:
                logger.warning("bucket comm calibration failed (%s: %s)",
                               type(e).__name__, e)
        self._compile_info = info
        return info

    def adopt_train_dir(self, train_dir: str | Path) -> None:
        """Re-root this trainer onto a different ``train_dir`` and
        resume from whatever checkpoints live there — the warm-standby
        promotion hook: a parked, precompiled process adopts a dead
        worker's logdir and continues its run without paying boot or
        compile again. Sinks and the TB writer are rebuilt against the
        new dir; the compile record is re-journaled there so the
        adopted log still carries the episode's compile evidence."""
        for attr in ("_sink", "_recovery_sink"):
            sink = getattr(self, attr)
            if sink is not None:
                sink.close()
                setattr(self, attr, None)
        self.train_dir = Path(train_dir)
        self.train_dir.mkdir(parents=True, exist_ok=True)
        if self._tb is not None:
            from ..obsv.tb import SummaryWriter
            self._tb.flush()
            self._tb = SummaryWriter(self.train_dir / "tb")
        self._compile_logged = False
        self._series.clear()
        self._start_step = 0
        if self.cfg.train.resume:
            self._maybe_resume()

    # ------------------------------------------------------------------

    def evaluate(self, split: str = "test") -> dict[str, float]:
        """One full-split eval pass (in-loop convenience; the
        continuous evaluator lives in ``evalsvc``). Resident-sharded
        params are gathered to the logical replicated layout the eval
        step places (parallel.api.logical_params — a passthrough
        otherwise)."""
        return run_full_eval(
            self.eval_fn,
            logical_params(self.state.params, self._zero1_plan, self.topo),
            self.topo,
            getattr(self.datasets, split), self.cfg.eval.eval_batch_size,
            prefetch_depth=self.cfg.data.effective_device_prefetch_depth())

    def run(self, max_steps: int | None = None,
            step_callback: Callable[[int, dict], None] | None = None) -> dict[str, Any]:
        """Run the loop; returns a summary dict."""
        cfg = self.cfg.train
        total = max_steps if max_steps is not None else cfg.max_steps
        profile_start, profile_stop = cfg.profile_steps
        profiling = False
        log_every = max(1, cfg.log_every_steps)
        last_log_t = time.time()
        last_log_step = self._start_step
        pending: list[tuple[int, dict, float]] = []
        final_metrics: dict[str, float] = {}
        # With no synthetic straggler model, per-replica step times are
        # driven by the real measured host step time: each process feeds
        # its own measurement into its replicas' rows of the [n] vector
        # (this is what paces interval windows / timeout deadlines and
        # ranks quorum contributors on real hardware). Granularity is
        # per PROCESS by construction: replicas of one process execute
        # inside a single lockstep SPMD program, so a within-process
        # per-replica clock cannot differ — real divergence enters at
        # process boundaries (slow host, ingest, contention), which is
        # exactly what this measures (proven live by
        # tests/test_multihost.py::test_slow_process_loses_quorum_by_
        # measured_time; ≙ per-worker times, src/timeout_manager.py:48-61).
        inject_measured = (self.cfg.sync.straggler_profile == "none"
                           and self.cfg.sync.mode in ("interval", "timeout",
                                                      "quorum", "cdf"))
        host_dt = 0.0

        can_measure = self.topo.measured_timing_supported
        if (inject_measured or self.delay_injection_ms is not None) and not can_measure:
            logger.warning(
                "replicas don't split evenly over processes — per-host "
                "measured timing disabled, policies run on the synthetic "
                "model only")

        def measured_vector() -> jax.Array | None:
            stage = self._measured_stage
            if stage is None or not (inject_measured
                                     or self.delay_injection_ms is not None):
                return None
            # assemble into the stage's reusable buffer; put() reuses
            # the cached sharding (and the staged all-zeros device
            # buffer outright when nothing was injected or measured)
            buf = stage.buffer
            buf[:] = host_dt * 1000.0 if inject_measured else 0.0
            if self.delay_injection_ms is not None:
                buf += np.asarray(self.delay_injection_ms, np.float32)
            if self._last_device_skew is not None:
                # per-device drain skew measured LAST step — the
                # within-host divergence the uniform host dt misses
                buf += self._last_device_skew
            return stage.put()

        def flush(now: float) -> None:
            nonlocal final_metrics, last_log_t, last_log_step
            if not pending:
                return
            upto = pending[-1][0]
            rate = ((upto - last_log_step) * self.effective_batch
                    / max(now - last_log_t, 1e-9))
            # NaN/Inf guard scans the WHOLE window before anything is
            # written: a mid-window raise would have already emitted the
            # earlier records (log lines, TB scalars, step_callbacks)
            # that the post-rollback re-run then emits again
            if self.cfg.train.nan_guard:
                for s, m, t in pending:
                    loss = float(m["loss"])
                    if not (math.isfinite(loss)
                            and math.isfinite(float(m["train_acc"]))):
                        self._recovery_event(
                            {"layer": "train",
                             "action": "nonfinite_loss_detected",
                             "step": s, "loss": repr(loss)})
                        raise _NonFiniteLoss(s, loss)
            for s, m, t in pending:
                loss = float(m["loss"])
                acc = float(m["train_acc"])
                self._series.append((t, s, loss, acc))
                record = {
                    "event": "step", "step": s, "time": t, "loss": loss,
                    "train_acc": acc, "lr": float(m["lr"]),
                    "updates_applied": int(m["updates_applied"]),
                    "num_contributors": float(m["num_contributors"]),
                    "examples_per_sec": rate,
                    # per-replica contribution mask — which replicas'
                    # gradients entered this step's masked mean
                    "flags": np.asarray(m["flags"]).astype(int).tolist(),
                    # adaptive mode: the [k, timeout_ms] in force for
                    # this step — params only change at flush end, so
                    # every pending step ran under the current pair
                    **({"discipline": self._discipline.params_list()}
                       if self._discipline is not None else {}),
                    # per-token routed layers: the pairs each held
                    # expert took this step, a row a layer
                    **({"expert_counts":
                        np.asarray(m["expert_counts"]).astype(int).tolist()}
                       if "expert_counts" in m else {}),
                }
                self._sink_write(record)
                final_metrics = record
                if (self._tb is not None
                        and s % self.cfg.train.summary_every_steps == 0):
                    self._tb.add_scalars(
                        {"train/loss": loss, "train/accuracy": acc,
                         "train/learning_rate": record["lr"],
                         "train/examples_per_sec": rate,
                         "train/num_contributors":
                             record["num_contributors"]},
                        step=s, wall_time=t)
                    # on-cadence flush: live `tensorboard --logdir`
                    # sees the run, and a crash loses at most one window
                    self._tb.flush()
                if step_callback:
                    step_callback(s, record)
            # canonical line for the last flushed step
            logger.info(step_line(jax.process_index(), upto,
                                  final_metrics["loss"],
                                  final_metrics["train_acc"], rate,
                                  (now - last_log_t) / max(upto - last_log_step, 1)))
            pending.clear()
            last_log_t, last_log_step = now, upto
            # adaptive discipline: evaluate AFTER the window's records
            # are written — a change licensed here governs from the
            # NEXT step (effective_step = upto + 1), so the records
            # above correctly carry the pre-change pair
            if self._discipline is not None:
                rolling = self.collector.rolling_cdf()
                if rolling is not None:
                    from .discipline import WindowStats
                    self._discipline.maybe_adapt(upto, WindowStats(
                        p50_ms=rolling["p50_ms"],
                        p90_ms=rolling["p90_ms"],
                        p99_ms=rolling["p99_ms"],
                        n_samples=rolling["window_steps"],
                        fast_p50_ms=rolling["fast_p50_ms"]))

        # Recurring per-window trace dumps (cfg.trace_every_steps): a
        # one-step trace each cadence window, each under its own
        # step_<k> directory — ≙ the reference's --timeline_logging
        # per-iteration Chrome traces (src/distributed_train.py:354-358)
        # at a bounded cadence instead of every step. Mutually
        # exclusive with the one-shot profile_steps window (two
        # concurrent jax.profiler traces cannot nest).
        trace_every = max(0, cfg.trace_every_steps)
        if trace_every and profile_stop > profile_start:
            raise ValueError("set either train.profile_steps or "
                             "train.trace_every_steps, not both "
                             "(profiler traces cannot nest)")
        tracing_step = None

        # Dispatch-ahead: the feed (train_feed property) either hands
        # back pre-staged sharded global arrays (DevicePrefetcher —
        # host assembly and H2D ran on the producer thread while the
        # previous step executed) or the raw host batch to stage
        # inline. Re-resolved each iteration (two attribute compares)
        # so the train_iter swap seam works mid-run too — the property
        # joins a stale wrapper before handing back the fresh one.
        prefetching = self._device_prefetch

        self.train_dir.mkdir(parents=True, exist_ok=True)
        if self.cfg.compile.precompile and self._compile_info is None:
            try:
                self.precompile()
            except Exception as e:
                # the fast path must never cost a run: fall back to the
                # classic first-step inline compile
                logger.warning("precompile failed (%s: %s) — first step "
                               "will compile inline", type(e).__name__, e)
                self._compile_info = {"compile_s": None, "source": "inline",
                                      "error": f"{type(e).__name__}: {e}"}
        if self._compile_info is not None and not self._compile_logged:
            self._sink_write({"event": "compile", "time": time.time(),
                              **self._compile_info})
            self._compile_logged = True
        step = self._start_step
        rollbacks = 0
        self._preempt_requested = None
        saved_handlers = self._install_preempt_handlers()
        try:
          # outer loop: one iteration per NaN-guard rollback episode —
          # the inner loop re-enters from the restored step
          while True:
            try:
              while step < total and self._preempt_requested is None:
                feed = self.train_feed
                in_window = profile_stop > profile_start and profile_start <= step < profile_stop
                if in_window and not profiling and self.is_writer:
                    spans.start_profile(self.train_dir / "profile")
                    profiling = True
                if (trace_every and self.is_writer and tracing_step is None
                        and step % trace_every == 0):
                    spans.start_profile(
                        self.train_dir / "profile" / f"step_{step}")
                    tracing_step = step
                t0 = time.time()
                with spans.span(spans.TRAIN_FEED):
                    if prefetching:
                        gbatch = next(feed)
                        # gauge AT dequeue: sampled any later, the
                        # producer has refilled and a producer-bound
                        # pipeline (the "pinned at 0" reading) would
                        # look healthy
                        queue_depth = feed.qsize
                    else:
                        gbatch = self.topo.device_put_batch(
                            next(feed), seq_sharded=self.seq_sharded)
                with spans.span(spans.TRAIN_DISPATCH, step=step):
                    self.state, metrics = self.step_fn(
                        self.state, gbatch, measured_vector(),
                        None if self._discipline is None
                        else self._discipline.vector)
                # host_dt is the per-HOST base time and must be captured
                # BEFORE the probe's drain poll — otherwise one slow device
                # would inflate every local replica's base (and the slow
                # one's skew would double-count)
                host_dt = time.time() - t0
                if self._device_probe is not None:
                    if self.device_work_injection:
                        for _r, (fn, arg) in self.device_work_injection.items():
                            # async: queues real work on that device; the
                            # probe polls the output's readiness so the
                            # delay is attributed to the right replica
                            # even on backends without per-device FIFO
                            self._device_probe.note(_r, fn(arg))
                    with spans.span(spans.TRAIN_PROBE):
                        self._last_device_skew = (
                            self._device_probe.measure_skew_ms())
                step += 1
                self.collector.add(
                    metrics["step_times_ms"], host_dt,
                    prefetch_depth=queue_depth if prefetching else None)
                pending.append((step, metrics, time.time()))

                if tracing_step is not None:
                    # one full step per window; fetch a scalar first so the
                    # trace covers the device work, not just the dispatch
                    float(metrics["loss"])
                    spans.stop_profile()
                    tracing_step = None

                if cfg.step_pace_ms > 0:
                    # deliberate wall throttle (serving-chaos publisher
                    # pacing) — after the step, before any cadence work
                    time.sleep(cfg.step_pace_ms / 1e3)

                if step % log_every == 0:
                    with spans.span(spans.TRAIN_FLUSH):
                        flush(time.time())

                if profiling and step >= profile_stop:
                    spans.stop_profile()
                    profiling = False

                if cfg.save_interval_secs > 0:
                    if time.time() - self._last_save_time >= cfg.save_interval_secs:
                        self._save(step)
                elif cfg.save_interval_steps > 0 and step % cfg.save_interval_steps == 0:
                    self._save(step)
                if cfg.save_results_period > 0 and step % cfg.save_results_period == 0:
                    self._dump_series()
              with spans.span(spans.TRAIN_FLUSH):
                  flush(time.time())  # records past the last log boundary
              break
            except _NonFiniteLoss as e:
                # NaN/Inf guard: discard the poisoned window, stop any
                # open trace, roll back to the newest finite
                # checkpoint and re-enter the loop from there. (If that
                # checkpoint predates the last flushed window, the
                # re-run appends the overlapping steps again — the same
                # overlap a kill + resume produces; no poisoned window
                # is ever written, per the flush pre-scan.)
                pending.clear()
                if tracing_step is not None:
                    spans.stop_profile()
                    tracing_step = None
                if profiling:
                    spans.stop_profile()
                    profiling = False
                rollbacks += 1
                if rollbacks > self.cfg.train.nan_guard_max_rollbacks:
                    raise RuntimeError(
                        f"nonfinite loss recurred after "
                        f"{rollbacks - 1} rollback(s) — deterministic "
                        "divergence, giving up") from e
                step = self._rollback_to_last_good(e)
                last_log_step = step
                last_log_t = time.time()
        finally:
            if saved_handlers is not None:
                for sig, old in saved_handlers.items():
                    signal.signal(sig, old)
            if self._train_feed is not None:
                # normal exit OR an exception escaping the loop: join
                # the producer and re-sync the inner cursor to the
                # consumed position, so nothing holds the process open
                # and a later run()/checkpoint observes no phantom
                # read-ahead progress (the live wrapper directly — the
                # property would construct a fresh one after a swap)
                self._train_feed.stop()

        if profiling:
            spans.stop_profile()
        if self._preempt_requested:
            self._recovery_event({"layer": "train", "action": "preempt_flush",
                                  "signal": self._preempt_requested,
                                  "step": step})
        # final save (≙ chief final saver.save, src/distributed_train.py:405-408)
        self._save(step)
        if self._checkpointer is not None:
            # drain + join the writer thread (a sweep builds many
            # Trainers in one process); raises if the final write failed
            self._checkpointer.close()
            self._checkpointer = None
        self._dump_series()
        if self._tb is not None:
            self._tb.flush()  # not closed: run() may be called again
        if self._sink:
            self._sink.close()
            self._sink = None
        if self._recovery_sink is not None:
            self._recovery_sink.close()
            self._recovery_sink = None
        summary = {
            "final_step": step,
            "updates_applied": int(jax.device_get(self.state.updates_applied)),
            "last_metrics": final_metrics,
            # bitwise identity of the final params (train/checkpoint.py
            # state_params_digest): the chaos invariant checker compares
            # a faulted-but-recovered run against its fault-free
            # same-seed reference by this — and against the final
            # checkpoint's own digest (the two must agree). None when
            # shards live on other processes (this process cannot
            # materialize the full params to hash them). Canonicalized
            # first: a resident-sharded run must hash the same LOGICAL
            # params a replicated same-seed run does — with momentum
            # dropped before the conversion, since the digest reads
            # params only and unpacking whole moment trees for it
            # would be a wasted D2H fetch.
            "params_digest": (ckpt.state_params_digest(
                                  canonical_save_state(
                                      self.state.replace(momentum=None),
                                      self._zero1_plan))
                              if not self._sharded_ckpt else None),
            "timing": self.collector.report(),
            # self-healing outcome: None/0 on a clean run; the CLI maps
            # "preempted" to train.resumable_exit_code
            "preempted": self._preempt_requested,
            "nan_rollbacks": rollbacks,
            # compile evidence (None when precompile is off): seconds,
            # source, and what the persistent cache did — journaled in
            # train_log.jsonl too
            "compile": self._compile_info,
        }
        if self._discipline is not None:
            # adaptive-controller roll-up: change count + epoch trace
            summary["discipline"] = self._discipline.summary()
        return summary
