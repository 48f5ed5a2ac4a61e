"""Step-time CDF collection and straggler statistics.

≙ the reference's cluster-wide timing gossip: workers RPC-broadcast
token-dequeue / gradients-done timestamps to worker 0, which aggregates
and periodically logs ``ELAPSED TIMES`` / ``ITERATION TIMES`` tables
(src/timeout_manager.py:31-70, src/distributed_train.py:305-307,
344-345), later parsed into stdev/p80/p90/p95/p99/p100 stats and CDF
plots (tools/benchmark.py:60-111,226-263).

TPU-native collapse: per-replica step times come out of the train step
as an all-gathered [n] vector (no RPC mesh, no shared-dict bug — the
reference's ``[{}] * n`` aliasing, src/timeout_manager.py:31-32, is a
documented quirk we do not copy). Collection is async-friendly: the
collector holds device arrays and only materializes them at report
points, so the device pipeline is never synced per step (SURVEY §7
"hard parts": timing capture must not cost scaling efficiency).

:class:`LoopClock` is the decode replica's counterpart on the host's
clock: cumulative seconds of its serial loop by phase, carried by every
heartbeat.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

PERCENTILES = (50.0, 80.0, 90.0, 95.0, 99.0, 100.0)  # ≙ tools/benchmark.py:86-111


@dataclasses.dataclass
class CdfStats:
    count: int
    mean: float
    stdev: float
    percentiles: dict[str, float]

    def to_dict(self) -> dict[str, Any]:
        return {"count": self.count, "mean": self.mean, "stdev": self.stdev,
                **{f"p{p:g}": v for p, v in zip(PERCENTILES, self.percentiles.values())}}


def compute_stats(samples: np.ndarray) -> CdfStats:
    samples = np.asarray(samples, np.float64).ravel()
    if samples.size == 0:
        return CdfStats(0, float("nan"), float("nan"),
                        {f"p{p:g}": float("nan") for p in PERCENTILES})
    pcts = np.percentile(samples, PERCENTILES)
    return CdfStats(
        count=int(samples.size),
        mean=float(samples.mean()),
        stdev=float(samples.std()),
        percentiles={f"p{p:g}": float(v) for p, v in zip(PERCENTILES, pcts)},
    )


class StepTimeCollector:
    """Accumulates per-step, per-replica time vectors lazily.

    ``add`` accepts a device array (or numpy) of shape [n_replicas] —
    kept as-is; conversion happens at ``snapshot``/report time so adds
    never force a device sync.
    """

    def __init__(self, num_replicas: int, capacity: int = 100_000):
        self.num_replicas = num_replicas
        self.capacity = capacity
        self._raw: list[Any] = []
        self._materialized = 0  # prefix of _raw already fetched to host
        self._host_steps: list[float] = []  # host-measured wall per step
        self._prefetch_depths: list[int] = []  # staged-queue gauge per step
        # ZeRO-1 overlap gauges (set only when comm bucketing is on —
        # the prefetch_queue_depth pattern: the report key exists iff
        # the feature does): bucket structure + calibrated per-bucket
        # comm time, plus the per-save snapshot stall series.
        self._overlap: dict[str, Any] | None = None
        self._snapshot_stalls: list[float] = []  # ms per save event
        # rolling-CDF window (set only when the adaptive discipline
        # controller is armed — same present-iff-on pattern): the
        # report then carries per-replica p50/p90/p99 over the LAST
        # window, the exact gauges the controller decides on.
        self._rolling_window: int | None = None

    def add(self, per_replica_times: Any, host_step_seconds: float | None = None,
            prefetch_depth: int | None = None) -> None:
        if len(self._raw) < self.capacity:
            self._raw.append(per_replica_times)
        if host_step_seconds is not None and len(self._host_steps) < self.capacity:
            self._host_steps.append(host_step_seconds)
        if prefetch_depth is not None and len(self._prefetch_depths) < self.capacity:
            self._prefetch_depths.append(int(prefetch_depth))

    def matrix(self) -> np.ndarray:
        """[steps, n_replicas] materialized compute times.

        Materialization is incremental: entries already fetched from
        device stay numpy, so periodic report/dump calls only transfer
        rows added since the last call (not O(steps) device fetches
        each time)."""
        if not self._raw:
            return np.zeros((0, self.num_replicas))
        for i in range(self._materialized, len(self._raw)):
            self._raw[i] = np.asarray(self._raw[i])
        self._materialized = len(self._raw)
        return np.stack(self._raw)

    def per_replica_stats(self) -> list[CdfStats]:
        """≙ per-worker ELAPSED TIMES stats (tools/benchmark.py:67-111)."""
        m = self.matrix()
        return [compute_stats(m[:, i]) for i in range(m.shape[1])] if m.size else []

    def per_step_stats(self) -> CdfStats:
        """Distribution over per-step *slowest replica* (the barrier
        time in a full-sync step) — the p99 the north star tracks."""
        m = self.matrix()
        return compute_stats(m.max(axis=1) if m.size else np.empty(0))

    def host_step_stats(self) -> CdfStats:
        return compute_stats(np.asarray(self._host_steps))

    def set_overlap_info(self, bucket_count: int,
                         per_bucket_pad_elems: list[int],
                         per_bucket_comm_ms: list[float] | None = None
                         ) -> None:
        """Record the comm-overlap structure (``parallel.comm_buckets``
        > 1): how many layer-ordered buckets the ZeRO-1 collectives are
        grouped into, each bucket's padded element count, and — when a
        calibration probe ran (Trainer.precompile) — the measured
        per-bucket scatter+gather wall ms in isolation. Structural
        gauges, not per-step measurements: inside one fused XLA program
        the per-bucket comm time is not separately observable, so the
        report carries the calibrated cost next to the live step
        times instead of pretending to split them."""
        self._overlap = {
            "bucket_count": int(bucket_count),
            "per_bucket_pad_elems": [int(x) for x in per_bucket_pad_elems],
        }
        if per_bucket_comm_ms is not None:
            self._overlap["per_bucket_comm_ms"] = [
                round(float(x), 3) for x in per_bucket_comm_ms]

    def add_snapshot_stall_ms(self, ms: float) -> None:
        """One checkpoint save's step-loop stall (train/loop.py _save):
        the sync-fetch path pays host fetch + canonical conversion
        here; the async-snapshot path only the device-copy dispatch."""
        if len(self._snapshot_stalls) < self.capacity:
            self._snapshot_stalls.append(float(ms))

    def snapshot_stall_stats(self) -> CdfStats:
        return compute_stats(np.asarray(self._snapshot_stalls, np.float64))

    def enable_rolling_cdf(self, window_steps: int) -> None:
        """Arm the rolling-window gauges (the adaptive discipline
        controller's view of the CDF; train/loop.py sets this iff
        ``sync.adaptive``)."""
        if window_steps < 1:
            raise ValueError(f"window_steps must be >= 1, got {window_steps}")
        self._rolling_window = int(window_steps)

    def rolling_cdf(self, window_steps: int | None = None
                    ) -> dict[str, Any] | None:
        """Per-replica p50/p90/p99 (and the pooled tail ratio) over the
        last ``window_steps`` rows — None until the window is full, so
        callers never decide on a half-filled CDF."""
        w = self._rolling_window if window_steps is None else int(window_steps)
        if w is None or len(self._raw) < w:
            return None
        tail = self.matrix()[-w:]
        pcts = np.percentile(tail, (50.0, 90.0, 99.0), axis=0)  # [3, n]
        pooled = np.percentile(tail, (50.0, 90.0, 99.0))
        p50 = float(pooled[0])
        # the fastest replica's median = the cohort pace. The pooled
        # p50 drifts to the midpoint once ~half the replicas straggle;
        # the controller's tail ratio divides by THIS instead
        fast_p50 = float(pcts[0].min())
        return {
            "window_steps": w,
            "per_replica": [
                {"p50": float(pcts[0, i]), "p90": float(pcts[1, i]),
                 "p99": float(pcts[2, i])}
                for i in range(tail.shape[1])],
            "p50_ms": p50,
            "p90_ms": float(pooled[1]),
            "p99_ms": float(pooled[2]),
            "fast_p50_ms": fast_p50,
            "tail_ratio": (float(pooled[2]) / fast_p50
                           if fast_p50 > 0 else 0.0),
        }

    def prefetch_depth_stats(self) -> CdfStats:
        """Distribution of the device-prefetch queue depth sampled at
        each step's dequeue: pinned at 0 means the producer (host
        assembly + H2D) is the bottleneck; pinned at the configured
        depth means the device is — the one gauge that says which side
        of the overlap to optimize next."""
        return compute_stats(np.asarray(self._prefetch_depths, np.float64))

    def report(self) -> dict[str, Any]:
        per_replica = self.per_replica_stats()
        out = {
            "num_steps": len(self._raw),
            "per_replica": [s.to_dict() for s in per_replica],
            "barrier": self.per_step_stats().to_dict(),
            "host_wall": self.host_step_stats().to_dict(),
        }
        if self._prefetch_depths:
            out["prefetch_queue_depth"] = self.prefetch_depth_stats().to_dict()
        if self._rolling_window is not None:
            rolling = self.rolling_cdf()
            if rolling is not None:
                out["rolling_cdf"] = rolling
        if self._overlap is not None:
            overlap = dict(self._overlap)
            if self._snapshot_stalls:
                overlap["snapshot_stall_ms"] = (
                    self.snapshot_stall_stats().to_dict())
            out["overlap"] = overlap
        elif self._snapshot_stalls:
            # async snapshots pay off without bucketing too — the stall
            # series stays visible when only that half is on
            out["snapshot_stall_ms"] = self.snapshot_stall_stats().to_dict()
        return out


class LoopClock:
    """Cumulative seconds of one serial loop by phase, always on: the
    decode replica's batcher thread reads ``time.perf_counter()`` at the
    boundaries its host spans already mark (``obsv/spans.py``) and adds
    the differences up, as ``decode_steps`` counts dispatches. Counters,
    not a store of spans: any two heartbeats that carry them give the
    milliseconds an iteration spent in each phase over every iteration
    between them, on the host's clock alone and with no profiler
    session. A phase is entered together with the span of the same
    region (the clock read inside it), so the two cannot name different
    regions; phases do not nest. What the named phases leave of
    :meth:`wall_s` is the loop's ``other``."""

    def __init__(self, phases: tuple[str, ...]):
        self.seconds = dict.fromkeys(phases, 0.0)
        self._began: float | None = None   # the first phase entered

    def phase(self, name: str, span=None) -> "_Phase":
        """``with clock.phase("fetch", spans.span(...)):``; ``span`` is
        any context manager, entered first and left last."""
        return _Phase(self, name, span)

    def wall_s(self) -> float:
        """Seconds since the loop entered its first phase."""
        return (0.0 if self._began is None
                else time.perf_counter() - self._began)


class _Phase:
    __slots__ = ("clock", "name", "span", "t0")

    def __init__(self, clock: LoopClock, name: str, span):
        self.clock, self.name, self.span = clock, name, span

    def __enter__(self) -> None:
        if self.span is not None:
            self.span.__enter__()
        self.t0 = time.perf_counter()
        if self.clock._began is None:
            self.clock._began = self.t0

    def __exit__(self, *exc) -> None:
        self.clock.seconds[self.name] += time.perf_counter() - self.t0
        if self.span is not None:
            self.span.__exit__(*exc)


class ReplicaDeviceProbe:
    """Per-replica DEVICE-side completion probes.

    One representative device per LOCAL replica is probed each step
    with a trivial jitted op on a device-resident token. On real
    accelerator backends per-device execution is FIFO, so the probe
    completes only once everything queued on that device — the train
    step's program slice plus any work dispatched after it (injected
    chaos programs, per-device callbacks) — has drained. Readiness is
    POLLED (not serially blocked) so each device gets its own
    completion timestamp.

    FIFO does NOT hold everywhere: the CPU client executes
    data-independent same-device computations on a shared host pool, so
    a bare token probe there either completes while injected work is
    still in flight (reads zero skew) or queues behind it on EVERY
    device at once (the shared pool stalls all probes together and the
    min-subtraction erases the differential). For work the dispatcher
    has a handle on, :meth:`note` registers the dispatched output with
    its replica and a dispatch timestamp; the drain measurement times
    each noted output from its OWN dispatch — a per-device load signal
    no shared-pool stall can smear across devices — and takes the max
    of that and the token-probe skew, so FIFO backends (where the token
    probe already queues behind the noted work) do not double-count.

    The lockstep SPMD step itself cannot produce skew (its collectives
    barrier the devices); what this measures is precisely the
    per-device work OUTSIDE the shared program — the part a per-host
    wall clock is blind to. ≙ the per-worker measured times the
    reference gossips (src/timeout_manager.py:48-61), at per-DEVICE
    granularity on one host.
    """

    def __init__(self, topo) -> None:
        import jax
        me = jax.process_index()
        n = topo.num_replicas
        grid = topo.mesh.devices.reshape(n, -1)
        self.devices: list = []   # (replica_index, device), local only
        for r in range(n):
            local = [d for d in grid[r] if d.process_index == me]
            if local:
                self.devices.append((r, local[0]))
        self._tokens = [jax.device_put(np.float32(0), d)
                        for _, d in self.devices]
        self._inc = jax.jit(lambda x: x + 1.0)
        # warm the per-device executables NOW: the first call per token
        # sharding compiles, and a compile inside measure_skew_ms would
        # charge ~tens of ms of compiler time to whichever device the
        # loop reached first
        for t in self._tokens:
            self._inc(t).block_until_ready()
        self._index_of = {r: i for i, (r, _) in enumerate(self.devices)}
        self._noted: list[list] = [[] for _ in self.devices]

    def note(self, replica: int, out) -> None:
        """Register a just-dispatched computation's output as part of
        ``replica``'s device queue for the NEXT ``measure_skew_ms``
        (the chaos-injection seam; no-op for non-local replicas)."""
        i = self._index_of.get(replica)
        if i is not None:
            self._noted[i].append((out, time.perf_counter()))

    def measure_skew_ms(self) -> np.ndarray:
        """Dispatch one probe per local replica device and poll
        completions; returns per-local-replica drain skew in ms.

        Per device: the token probe's completion time (min-subtracted
        across devices — the differential a lockstep step reads as
        ~zero) maxed with each noted output's dispatch-to-ready
        duration (zero when nothing was noted).

        The noted duration is an UPPER bound on the replica's excess:
        on FIFO backends it also includes whatever residual step drain
        was queued ahead at dispatch (the token differential alone
        reports the exact excess there, and the max keeps it when it is
        larger… the noted value can only overstate the magnitude, never
        the ORDERING — the noted replica genuinely drains last, which
        is what quorum selection ranks on). Separating the shared-drain
        component out is not robustly measurable across queue
        disciplines: subtracting the token baseline erases the signal
        on shared-pool backends, where that baseline is itself the
        noted program's doing."""
        import jax  # noqa: F401  (tokens/jit already bound)
        outs = [self._inc(t) for t in self._tokens]
        noted, self._noted = self._noted, [[] for _ in self.devices]
        t0 = time.perf_counter()
        times = np.zeros(len(outs), np.float64)
        extra = np.zeros(len(outs), np.float64)
        pending = set(range(len(outs)))
        npending = {i for i in range(len(outs)) if noted[i]}
        while pending or npending:
            now = time.perf_counter()
            for i in list(pending):
                if outs[i].is_ready():
                    times[i] = (now - t0) * 1000.0
                    pending.discard(i)
            for i in list(npending):
                # drop entries as they finish; the device's extra is
                # its slowest noted program's dispatch→ready duration
                still = []
                for a, at in noted[i]:
                    if a.is_ready():
                        extra[i] = max(extra[i], (now - at) * 1000.0)
                    else:
                        still.append((a, at))
                noted[i] = still
                if not still:
                    npending.discard(i)
            if pending or npending:
                time.sleep(0.0002)
        return np.maximum(times - times.min(), extra).astype(np.float32)
