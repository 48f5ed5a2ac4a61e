"""Device-mesh / topology discovery.

Replaces the reference's cluster plumbing — ``tf.train.ClusterSpec`` +
per-process ``tf.train.Server`` with explicit ps_hosts/worker_hosts
strings (reference: src/mnist_distributed_train.py:25-31,
src/distributed_train.py:41-48) and the EC2 role-assignment machinery
(tools/tf_ec2.py:462-491) — with TPU-slice discovery: every host runs
the same SPMD program, devices come from ``jax.devices()``, and the
"cluster spec" is just a `jax.sharding.Mesh`.

There is no parameter-server role: parameters are replicated and
gradient aggregation is a compiler-scheduled psum over ICI (SURVEY §5.8).
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .config import MeshConfig

P = PartitionSpec


shard_map = jax.shard_map  # the one spelling the package imports


def gather_chunks_replicated(chunk, axis_name: str, full_len: int,
                             offset) -> "jax.Array":
    """Reassemble per-replica 1-D ``chunk``s (this replica's slice
    starting at ``offset`` of a ``full_len`` vector) into the FULL
    vector on every replica — the allgather leg of the ZeRO-1 weight
    update (parallel/api.py).

    An ``all_gather`` result stays marked device-varying under
    shard_map's replication check and could not leave under a P()
    out_spec (the same constraint behind parallel/api.py's
    ``_gather_replicated`` one-hot psum for the [n] metrics vector), so
    each replica scatters its chunk into a zeros vector and one psum
    reassembles a statically-replicated result; communication is an
    all-reduce where an allgather would do, correctness and the
    sharded-optimizer-state memory win are unchanged."""
    import jax.numpy as jnp
    buf = jnp.zeros((full_len,), chunk.dtype)
    buf = jax.lax.dynamic_update_slice(buf, chunk, (offset,))
    return jax.lax.psum(buf, axis_name)


def gather_bucket_replicated(chunk, axis_name: str, n: int) -> "jax.Array":
    """Per-BUCKET variant of :func:`gather_chunks_replicated`: stack
    each replica's 1-D concatenated bucket chunk (every sharded leaf's
    ``[chunk]`` slice for one comm bucket, concatenated) into the
    replicated ``[n, C]`` matrix whose row ``r`` is replica ``r``'s
    contribution — ONE collective reassembles a whole bucket's params
    instead of one per leaf (the bucketed ZeRO-1 allgather leg and the
    resident-sharded just-in-time weight gather, parallel/api.py).
    Column slices of the result recover each leaf's ``[n, chunk]``
    view, which flattens row-major to exactly its padded ``[pad]``
    layout.

    Same form as the per-leaf helper: each replica scatters its row
    into a zeros matrix and one psum produces a statically-replicated
    result."""
    import jax.numpy as jnp
    buf = jnp.zeros((n,) + tuple(chunk.shape), chunk.dtype)
    buf = jax.lax.dynamic_update_slice(
        buf, chunk[None], (jax.lax.axis_index(axis_name), 0))
    return jax.lax.psum(buf, axis_name)


def initialize_distributed() -> None:
    """Multi-host bring-up (≙ tf.train.Server + startup barrier,
    src/mnist_distributed_train.py:27-35, src/timeout_manager.py:198-211).

    On a real multi-host TPU slice, `jax.distributed.initialize()`
    discovers the coordinator (from TPU pod metadata, or the
    JAX_COORDINATOR_ADDRESS / slurm env). MUST be called before
    anything initializes the XLA backend, so this function touches no
    other jax APIs first. A no-op when already initialized or when
    nothing indicates a multi-host environment. Safe to call twice.
    """
    from jax._src import distributed as _dist
    if _dist.global_state.client is not None:
        return  # already initialized
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    explicit = os.environ.get("JAX_COORDINATOR_ADDRESS")
    multi_host_hint = (
        explicit
        or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
        or len([h for h in hostnames.split(",") if h]) > 1)
    if not multi_host_hint:
        return  # single-process run (one chip / CPU simulation)
    if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
        # multi-process CPU collectives run over gloo
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if explicit and ("JAX_NUM_PROCESSES" in os.environ
                     or "JAX_PROCESS_ID" in os.environ):
        # Generic-cluster bring-up (≙ the reference's explicit
        # ps_hosts/worker_hosts + task_index flags,
        # src/mnist_distributed_train.py:25-31): jax's auto-detection
        # only covers TPU-metadata / SLURM / MPI environments, so a
        # plain N-process launch names its coordinator explicitly.
        missing = [v for v in ("JAX_NUM_PROCESSES", "JAX_PROCESS_ID")
                   if v not in os.environ]
        if missing:
            raise RuntimeError(
                "explicit multi-process launch needs JAX_COORDINATOR_ADDRESS, "
                f"JAX_NUM_PROCESSES and JAX_PROCESS_ID; missing: {missing}")
        jax.distributed.initialize(
            coordinator_address=explicit,
            num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
            process_id=int(os.environ["JAX_PROCESS_ID"]))
    else:
        jax.distributed.initialize()


# Env values as they were BEFORE the first simulate_devices call (None
# = the variable was unset). strip_forced_platform_env restores exactly
# this snapshot, so operator-set values survive untouched.
_env_before_force: dict | None = None


_DEVICE_COUNT_FLAG = re.compile(
    r"--xla_force_host_platform_device_count=(\d+)")


def forced_device_count(flags: str) -> int | None:
    """The virtual-CPU-device count an ``XLA_FLAGS`` string forces."""
    got = _DEVICE_COUNT_FLAG.search(flags)
    return int(got.group(1)) if got else None


def force_device_count_flag(flags: str, n: int | None) -> str:
    """``flags`` with the virtual-CPU-device count set to ``n``
    (``None`` strips the flag)."""
    flags = _DEVICE_COUNT_FLAG.sub("", flags).strip()
    if n is None:
        return flags
    return f"{flags} --xla_force_host_platform_device_count={n}".strip()


def simulate_devices(n: int) -> None:
    """Force an ``n``-virtual-CPU-device platform. MUST run before the
    XLA backend initializes — call from conftest/env setup.

    This is the framework's answer to the reference's total lack of a
    mock distributed backend (SURVEY §4): N-device SPMD semantics are
    testable on one CPU host. The single point of truth for this idiom
    (tests/conftest.py and the launch CLI both route through it).
    """
    global _env_before_force
    if _env_before_force is None:
        _env_before_force = {
            "XLA_FLAGS": os.environ.get("XLA_FLAGS"),
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
        }
    os.environ["XLA_FLAGS"] = force_device_count_flag(
        os.environ.get("XLA_FLAGS", ""), n)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    jax.config.update("jax_platforms", "cpu")
    # XLA_FLAGS is parsed once per process; jax_num_cpu_devices also
    # works after a CPU backend was torn down (ensure_mesh), but jax
    # refuses the update while a backend is live
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:
        pass  # backend already initialized; XLA_FLAGS path applies


def strip_forced_platform_env(env: dict) -> dict:
    """Undo :func:`simulate_devices`' env mutations in a CHILD's env so
    a subprocess boots the true ambient backend (the campaign's lean
    single-device evaluator). Restores the exact pre-force snapshot —
    values the operator set themselves (e.g. a deliberate
    JAX_PLATFORMS=cpu pin) are preserved, and if simulate_devices never
    ran in this process the env passes through unchanged. The one
    exception: a ``--xla_force_host_platform_device_count`` flag is
    stripped even if it predates the force — an evaluator child on a
    forced multi-device mesh would recreate exactly the trainer
    contention this function exists to avoid. Kept here, next to the
    code that writes the flag, so the two can't drift."""
    env = dict(env)
    if _env_before_force is not None:
        for key, orig in _env_before_force.items():
            if orig is None:
                env.pop(key, None)
            else:
                env[key] = orig
    flags = force_device_count_flag(env.get("XLA_FLAGS", ""), None)
    if flags:
        env["XLA_FLAGS"] = flags
    else:
        env.pop("XLA_FLAGS", None)
    return env


_ambient_mesh: tuple[int, str] | None = None  # (device_count, platform)


def _replace_cpu_backend(n: int) -> None:
    """Tear down the live CPU backend and force ``n`` virtual CPU
    devices in its place. Refuses when the live backend is an
    accelerator: a simulated-mesh config must never silently swap a
    chip for a CPU mesh under a run that believes it is on the chip."""
    live = jax.default_backend()
    if live != "cpu":
        raise RuntimeError(
            f"mesh.simulate_devices={n} asks for a virtual CPU mesh but "
            f"the live backend is {live!r} with {len(jax.devices())} "
            "device(s); run simulated-mesh configs in a process started "
            "with JAX_PLATFORMS=cpu")
    import jax.extend.backend as jeb
    jeb.clear_backends()
    simulate_devices(n)


def ensure_mesh(simulate: int) -> None:
    """Make the process's device set match what a config expects.

    ``simulate > 0`` forces that many virtual CPU devices (tearing down
    a previously initialized backend if the count differs);
    ``simulate == 0`` means "the ambient devices" — captured at this
    helper's first call — and RESTORES them if a previous config left a
    different simulated platform behind.

    This is the guard that makes mixed sweeps safe: without it, a
    ``launch sweep`` over a directory where one config forces a
    50-device mesh (configs/quorum50_*) would silently run every
    subsequent ambient-mesh config 50-wide under its 8-wide name.
    Restoration is only possible when the ambient platform was CPU
    (re-forcing a torn-down accelerator backend is not supported) —
    otherwise this raises rather than continuing on the wrong mesh.
    """
    global _ambient_mesh
    if _ambient_mesh is None:
        _ambient_mesh = (len(jax.devices()), jax.default_backend())
    want, platform = ((simulate, "cpu") if simulate > 0 else _ambient_mesh)
    if len(jax.devices()) == want and jax.default_backend() == platform:
        return
    if platform != "cpu":
        raise RuntimeError(
            f"cannot restore the ambient {platform} backend after a "
            "simulated-mesh config ran in this process; run "
            "simulated-mesh configs (mesh.simulate_devices > 0) in their "
            "own process")
    _replace_cpu_backend(want)


@dataclasses.dataclass(frozen=True)
class Topology:
    """Resolved topology: the mesh plus canonical shardings."""

    mesh: Mesh
    replica_axis: str
    model_axis: str
    seq_axis: str
    stage_axis: str
    expert_axis: str = "expert"

    @property
    def num_replicas(self) -> int:
        return self.mesh.shape[self.replica_axis]

    @property
    def replicated(self) -> NamedSharding:
        """Sharding for parameters/state: replicated everywhere
        (≙ vars pinned to the PS and read by all workers,
        src/distributed_train.py:133-136 — except here every replica
        holds the copy and XLA keeps them identical)."""
        return NamedSharding(self.mesh, P())

    @property
    def batch_sharded(self) -> NamedSharding:
        """Sharding for a global batch: leading dim split over replicas."""
        return NamedSharding(self.mesh, P(self.replica_axis))

    def device_put_batch(self, batch, seq_sharded: bool = False):
        """Place a batch sharded over replicas (rows) and, when
        ``seq_sharded``, over the seq axis (second dim — the DP×SP
        token layout).

        Single-process: a plain device_put of the global batch.
        Multi-host: each process holds only its local rows
        (global_batch / process_count — see data.pipeline), so the
        global array must be assembled from process-local shards.
        (Sequence sharding should stay within a host for ingest: each
        process holds full rows, and the placement splits the token dim
        across its local devices.)
        """
        sharding = (NamedSharding(self.mesh, P(self.replica_axis, self.seq_axis))
                    if seq_sharded else self.batch_sharded)
        if jax.process_count() > 1:
            return jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(
                    sharding, np.asarray(x)),
                batch)
        return jax.device_put(batch, sharding)

    @property
    def measured_timing_supported(self) -> bool:
        """Per-host measured timing is well-defined only when every
        replica lives wholly on one process (replicas split evenly
        across processes). E.g. cross-host TP with num_replicas=1 on 2
        processes has no owner whose measurement could fill the row —
        and two hosts writing different values into a replicated array
        would silently diverge its shards."""
        return (self.num_replicas % jax.process_count() == 0
                and self.num_replicas >= jax.process_count())

    @property
    def local_replica_count(self) -> int:
        """Replicas whose shards this process owns (even split)."""
        return self.num_replicas // jax.process_count()

    def zeros_measured(self) -> jax.Array:
        """The all-zeros measured vector [n] — valid on ANY mesh shape
        (zeros are identical whoever materializes them)."""
        n = self.num_replicas
        sharding = NamedSharding(self.mesh, P(self.replica_axis))
        return jax.make_array_from_callback(
            (n,), sharding, lambda idx: np.zeros(n, np.float32)[idx])

    def device_put_measured(self, local_ms) -> jax.Array:
        """Assemble the per-replica measured-step-time vector [n] from
        this process's local entries (shape [local_replica_count]).

        Each host contributes only the rows for its own replicas — the
        real per-host measurement — giving the policies a genuinely
        per-replica time base (≙ the per-worker timing tables the
        reference gossips over RPC, src/timeout_manager.py:48-61)."""
        if not self.measured_timing_supported:
            raise ValueError(
                f"per-host measured timing needs num_replicas "
                f"({self.num_replicas}) to split evenly over "
                f"{jax.process_count()} processes")
        local = np.asarray(local_ms, np.float32)
        if local.shape != (self.local_replica_count,):
            raise ValueError(
                f"measured vector must be [{self.local_replica_count}] "
                f"(local replicas), got {local.shape}")
        sharding = NamedSharding(self.mesh, P(self.replica_axis))
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, local)
        return jax.device_put(local, sharding)

    def measured_stage(self) -> "MeasuredStage":
        """A per-step staging handle for the measured-timing vector —
        validate once, reuse the sharding and the host assembly buffer
        every step (see :class:`MeasuredStage`)."""
        return MeasuredStage(self)

    def device_put_replicated(self, tree):
        return jax.device_put(tree, self.replicated)

    def device_put_state(self, tree, specs):
        """Place a state pytree per a PartitionSpec tree. ``specs`` may
        be a *prefix* of ``tree`` (a single spec covering a subtree —
        e.g. P() for all params when not tensor-parallel)."""
        is_spec = lambda x: isinstance(x, PartitionSpec)  # noqa: E731
        spec_leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
        subtrees = treedef.flatten_up_to(tree)
        placed = [jax.device_put(sub, NamedSharding(self.mesh, spec))
                  for sub, spec in zip(subtrees, spec_leaves)]
        return jax.tree.unflatten(treedef, placed)


class MeasuredStage:
    """Pre-staged assembly for the per-step measured-timing vector.

    :meth:`Topology.device_put_measured` validates its arguments and
    builds a fresh ``NamedSharding`` on every call — fine for one-shot
    placement (tests, multihost bring-up), wasteful at once-per-step
    cadence in the train loop. The stage validates ONCE, caches the
    sharding, and owns a reusable host-side ``buffer`` the loop writes
    its per-replica milliseconds into; :meth:`put` hands back the
    staged ``[n]`` device array. The all-zeros vector — every step
    with no injection and no skew — is staged once and that device
    buffer is reused outright (no H2D at all on those steps).
    """

    def __init__(self, topo: Topology):
        if not topo.measured_timing_supported:
            raise ValueError(
                f"per-host measured timing needs num_replicas "
                f"({topo.num_replicas}) to split evenly over "
                f"{jax.process_count()} processes")
        self._n_local = topo.local_replica_count
        self._sharding = NamedSharding(topo.mesh, P(topo.replica_axis))
        self._multi = jax.process_count() > 1
        self._zeros: jax.Array | None = None
        self._zeros_fn = topo.zeros_measured
        #: host assembly scratch — write this step's values here, then
        #: :meth:`put` with no argument
        self.buffer = np.zeros(self._n_local, np.float32)

    def put(self, local_ms=None) -> jax.Array:
        """Stage ``local_ms`` (default: the assembly ``buffer``) as the
        sharded ``[n]`` measured vector."""
        local = (self.buffer if local_ms is None
                 else np.asarray(local_ms, np.float32))
        if local.shape != (self._n_local,):
            raise ValueError(
                f"measured vector must be [{self._n_local}] "
                f"(local replicas), got {local.shape}")
        if not local.any():
            if self._zeros is None:
                self._zeros = self._zeros_fn()
            return self._zeros
        # device_put may alias the host buffer (CPU backend) or copy
        # asynchronously (accelerators) — stage a private copy so the
        # loop reusing ``buffer`` next step can't corrupt this one
        local = np.array(local, np.float32)
        if self._multi:
            return jax.make_array_from_process_local_data(
                self._sharding, local)
        return jax.device_put(local, self._sharding)


def make_topology(cfg: MeshConfig | None = None,
                  devices: Sequence[jax.Device] | None = None) -> Topology:
    """Build the device mesh.

    Axes: (replica, model, seq, stage, expert). Data parallelism rides
    ``replica``; ``model`` carries Megatron tensor parallelism, ``seq``
    ring/all-to-all sequence parallelism, ``stage`` GPipe layer
    pipelining, ``expert`` MoE expert sharding. Unused axes default to
    size 1.
    """
    cfg = cfg or MeshConfig()
    if cfg.pipeline_chunks > 1 and cfg.pipeline_schedule != "1f1b":
        # chunks only exist under the interleaved schedule — silently
        # ignoring them would hand back plain GPipe with its full
        # bubble while the config promises interleaving
        raise ValueError(
            f"mesh.pipeline_chunks={cfg.pipeline_chunks} requires "
            f"pipeline_schedule='1f1b' (got {cfg.pipeline_schedule!r})")
    if (devices is None and cfg.simulate_devices > 0
            and len(jax.devices()) < cfg.simulate_devices):
        # A config that trained on a simulated mesh must be loadable by
        # every consumer (evaluator, sweep, report), not just the train
        # CLI — tear down the 1-device CPU backend and force the mesh
        # (refused on a live accelerator: _replace_cpu_backend).
        # Capture the TRUE ambient devices first: if ensure_mesh's
        # lazy capture ran only after this forcing, it would record the
        # simulated mesh as "ambient" and a later simulate_devices=0
        # config would silently keep running on the forced mesh.
        global _ambient_mesh
        if _ambient_mesh is None:
            _ambient_mesh = (len(jax.devices()), jax.default_backend())
        _replace_cpu_backend(cfg.simulate_devices)
    devs = list(devices if devices is not None else jax.devices())
    mp, sp = max(1, cfg.model_parallelism), max(1, cfg.seq_parallelism)
    pp = max(1, cfg.pipeline_parallelism)
    ep = max(1, cfg.expert_parallelism)
    n = cfg.num_replicas
    if n == -1:
        n = len(devs) // (mp * sp * pp * ep)
    want = n * mp * sp * pp * ep
    if want > len(devs):
        raise ValueError(
            f"mesh needs {want} devices (replica={n} × model={mp} × seq={sp} "
            f"× stage={pp} × expert={ep}) but only {len(devs)} are visible")
    grid = np.array(devs[:want]).reshape(n, mp, sp, pp, ep)
    mesh = Mesh(grid, (cfg.replica_axis, cfg.model_axis, cfg.seq_axis,
                       cfg.stage_axis, cfg.expert_axis))
    return Topology(mesh=mesh,
                    replica_axis=cfg.replica_axis,
                    model_axis=cfg.model_axis,
                    seq_axis=cfg.seq_axis,
                    stage_axis=cfg.stage_axis,
                    expert_axis=cfg.expert_axis)


def make_seq_topology(n_seq: int, devices: Sequence[jax.Device] | None = None) -> Topology:
    """A mesh that spends its devices on the sequence axis (ring
    attention / context parallelism — the long-context path)."""
    return make_topology(
        MeshConfig(num_replicas=1, seq_parallelism=n_seq), devices=devices)
