"""The SPMD train step — replaces reference layers L3 (modified sync
optimizer) and L4 (Twisted RPC mesh) with one compiled program.

Where the reference pushes gradients into PS-hosted accumulators,
blocks on per-worker token queues, and lets a chief thread apply the
update (sync_replicas_optimizer_modified.py:237-429), here every
replica computes its gradient, a masked-mean ``lax.psum`` over the ICI
mesh aggregates exactly the contributions the active policy allows,
and every replica applies the identical update to its replicated
parameters. Barriers, tokens, staleness checks and the chief role all
disappear into collective semantics.

The step is built once per (model, config, topology) and jitted with
donated state; everything inside is static-shaped and control flow is
`lax.cond`, so XLA compiles a single fused program per mode.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import mesh as mesh_lib
from ..core import prng
from ..core.config import ExperimentConfig
from ..core.log import get_logger
from ..core.mesh import Topology
from ..models.registry import Model, replicated_partition_rules
from ..ops.drop_connect import drop_connect_grads
from ..ops.masked_psum import contribution_scale, masked_mean_psum
from . import policies
from .partition_rules import (RuleAxes, Zero1Plan, comm_bucket_assignment,
                              match_partition_rules, make_zero1_plan,
                              zero1_init_state, zero1_pack,
                              zero1_state_specs, zero1_unpack)

logger = get_logger("parallel")

# LR schedule: updates_applied -> lr (see train.lr_schedule; kept as a
# plain callable type here to avoid a parallel<->train import cycle).
Schedule = Callable[[jax.Array], jax.Array]

# Runtime discipline vector layout: the aggregation-discipline
# parameters ride into the compiled step as ONE replicated [3] float32
# input (spec P()), so the adaptive controller (train/discipline.py)
# changes discipline by swapping a 12-byte buffer — never by
# recompiling. Indexed symbolically everywhere; order is part of the
# AOT signature, so reordering would invalidate precompiled caches.
DISC_K = 0            # quorum size (integer-valued float; rounded in use)
DISC_TIMEOUT_MS = 1   # timeout-mode deadline
DISC_INTERVAL_MS = 2  # interval-mode window / staleness bound


# What ``precompile`` asks of the TPU compiler where the replica axis
# holds more than one device: left alone it keeps every all-reduce
# synchronous and schedules the gradient's masked psums after the whole
# backward pass, so the chip computes nothing while they are on the
# wire. With both options (either alone changes nothing) each matrix's
# all-reduce becomes an asynchronous fusion in flight beside the next
# matrix's weight-gradient product. The values are the same float32
# sums; the fusions do not reduce in place, so the program holds more
# temporaries (opt-6.7b, 3 layers, 2x2 v5e: 11.05 -> 13.19 GB).
ASYNC_ALL_REDUCE_OPTIONS = {
    "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": True,
    "xla_enable_async_all_reduce": True,
}
# an asynchronous collective's first half, by the name the compiler
# gives its definition (the TPU's fusions; plain XLA's all-reduce-start)
_ASYNC_START = re.compile(
    r"^\s*%?(?:async-collective-start|all-reduce-start)[\w.]* = ", re.M)


def count_async_collectives(hlo_text: str) -> int:
    """Collectives in flight beside other work in a compiled program:
    the asynchronous starts its entry computation defines."""
    _, _, entry = hlo_text.rpartition("\nENTRY ")
    return len(_ASYNC_START.findall(entry))


def _async_options(mesh, replica_axis: str) -> dict[str, bool]:
    """:data:`ASYNC_ALL_REDUCE_OPTIONS` where they have something to
    hide: read from the mesh's own devices, not ``jax.default_backend()``
    (the step is also compiled for devices that are described and not
    attached, and a CPU compile refuses TPU options), and only with more
    than one replica (one has no collective, and keeps its program)."""
    on_tpu = mesh.devices.flat[0].platform == "tpu"
    return (ASYNC_ALL_REDUCE_OPTIONS
            if on_tpu and mesh.shape[replica_axis] > 1 else {})


def make_discipline_vector(k: float, timeout_ms: float,
                           interval_ms: float) -> jax.Array:
    """Pack runtime discipline params as the traced [3] step input."""
    return jnp.asarray([float(k), float(timeout_ms), float(interval_ms)],
                       jnp.float32)


class TrainState(struct.PyTreeNode):
    """Replicated training state (a pure pytree).

    ``updates_applied`` is the reference's global_step — it counts
    *applied updates* (PS applies, src/distributed_train.py:140), while
    ``step`` counts loop iterations; the two differ in interval mode.

    ``momentum`` holds the optimizer's moment slots in the registry's
    layout (train/optim.py): None (stateless sgd), a params-shaped
    tree (momentum/LARS — byte-identical to the historical layout), or
    ``{"m": tree, "v": tree}`` (LAMB). Under
    ``precision.master_weights``, ``params`` ARE the float32 masters;
    the train step derives the low-precision forward view per step, so
    no second param tree ever enters the state or its checkpoints.
    """

    params: Any
    momentum: Any            # optimizer moment slots or None
    step: jax.Array          # int32, loop iterations
    updates_applied: jax.Array  # int32, ≙ global_step
    root_key: jax.Array
    # interval mode only (None otherwise):
    window_acc: Any          # accumulated sum of per-step masked means
    window_rounds: jax.Array  # float32 rounds accumulated in this window
    wall_ms: jax.Array       # modeled wall clock
    next_apply_ms: jax.Array


def _build_params(model: Model, cfg: ExperimentConfig,
                  topo: Topology | None) -> Any:
    """Init params in the layout the mesh trains (pp-transformed when
    the stage axis is active) — shared by :func:`init_train_state` and
    the abstract-shape path the spec engine maps rules over."""
    params = model.init(jax.random.PRNGKey(cfg.model.init_seed))
    if (topo is not None and topo.mesh.shape[topo.stage_axis] > 1):
        if model.pp_transform is None:
            raise ValueError(f"mesh has pipeline stages but model "
                             f"{model.name!r} has no pp_transform")
        if cfg.mesh.pipeline_schedule == "1f1b":
            if model.pp_transform_chunked is None:
                raise ValueError(
                    f"pipeline_schedule='1f1b' but model {model.name!r} "
                    "has no pp_transform_chunked")
            # chunk-interleaved layer order: device d's contiguous
            # stage shard holds global chunks {d, S+d, ...}
            params = model.pp_transform_chunked(
                params, topo.mesh.shape[topo.stage_axis],
                cfg.mesh.pipeline_chunks)
        else:
            params = model.pp_transform(params)  # layer-stacked layout
    return params


@functools.lru_cache(maxsize=128)
def _abstract_train_params_cached(model: Model, cfg: ExperimentConfig,
                                  topo: Topology | None) -> Any:
    return jax.eval_shape(lambda: _build_params(model, cfg, topo))


def abstract_train_params(model: Model, cfg: ExperimentConfig,
                          topo: Topology | None) -> Any:
    """Shape/dtype skeleton of the trained param tree (no FLOPs, no
    device buffers) — what the rule engine needs to name leaves.

    Memoized on the (model, cfg, topo) triple: one Trainer build calls
    through here several times (state specs, train step, eval step,
    ZeRO-1 plan) with the same frozen objects, and re-tracing init each
    time is pure waste (~0.1 s per trace). Falls back to a direct trace
    for unhashable inputs."""
    try:
        return _abstract_train_params_cached(model, cfg, topo)
    except TypeError:
        return jax.eval_shape(lambda: _build_params(model, cfg, topo))


def params_partition_specs(model: Model, cfg: ExperimentConfig,
                           topo: Topology, params: Any = None) -> Any:
    """The per-leaf PartitionSpec tree for the trained params, derived
    by mapping the model's declarative rule table
    (``models/registry.py``) over the real param tree with the active
    mesh axes bound — ``parallel/partition_rules.py``. Replaces the
    hand-built spec trees ``state_partition_specs`` used to assemble
    per layout; the models' spec builders remain as the parity oracle
    (tests/test_partition_rules.py)."""
    n_model = topo.mesh.shape[topo.model_axis]
    n_stage = topo.mesh.shape[topo.stage_axis]
    n_expert = topo.mesh.shape[topo.expert_axis]
    if n_model > 1 and model.tp_param_specs is None:
        raise ValueError(f"mesh has model_parallelism={n_model} but model "
                         f"{model.name!r} has no tensor-parallel parameter "
                         "specs")
    if n_expert > 1 and (model.tp_param_specs is None or not model.has_aux):
        raise ValueError(f"mesh has expert_parallelism={n_expert} but model "
                         f"{model.name!r} has no experts to shard")
    if n_stage > 1 and model.pp_param_specs is None:
        raise ValueError(f"mesh has pipeline_parallelism={n_stage} but model "
                         f"{model.name!r} has no pipeline parameter specs")
    axes = RuleAxes(
        model=topo.model_axis if n_model > 1 else None,
        expert=topo.expert_axis if n_expert > 1 else None,
        stage=topo.stage_axis if n_stage > 1 else None)
    if model.partition_rules is None and (axes.model or axes.expert
                                          or axes.stage):
        # the replicated fallback table is only safe when nothing needs
        # sharding — silently replicating a TP/PP/EP model's weights
        # would double-count its model-axis psums, the exact failure
        # the rule engine's unmatched-leaf error exists to prevent
        raise ValueError(
            f"model {model.name!r} declares sharded-parallelism support "
            "but no partition_rules table (models/registry.py) — cannot "
            f"derive placements for active axes {axes}")
    rules = (model.partition_rules or replicated_partition_rules)(axes)
    if params is None:
        params = abstract_train_params(model, cfg, topo)
    return match_partition_rules(rules, params)


def zero1_plan_for(model: Model, cfg: ExperimentConfig, topo: Topology,
                   params: Any = None) -> Zero1Plan | None:
    """The ZeRO-1 shard plan when ``parallel.shard_weight_update`` is
    both enabled and applicable, else None. Inapplicable: a replica
    axis of 1 (nothing is redundant), or interval mode (the windowed
    accumulator averages the FULL mean across steps; sharding it too is
    possible but not worth the extra state surface — documented
    fallback, see README Performance)."""
    par = cfg.parallel
    par.validate()  # typed ConfigError at build time, not mid-step
    if not par.shard_weight_update:
        return None
    if topo.num_replicas <= 1 or cfg.sync.mode == "interval":
        return None
    if params is None:
        params = abstract_train_params(model, cfg, topo)
    pspecs = params_partition_specs(model, cfg, topo, params=params)
    return make_zero1_plan(params, pspecs, topo.replica_axis,
                           topo.num_replicas,
                           min_leaf_size=par.shard_min_leaf_size,
                           comm_buckets=par.comm_buckets,
                           params_sharded=par.resident_sharded)


def resolved_param_dtype(cfg: ExperimentConfig):
    """The dtype ``TrainState.params`` is STORED in: float32 masters
    when ``precision.master_weights`` (the low-precision view is
    derived per step), else ``precision.param_dtype`` itself. Typed
    validation, matching the optim section's convention: a bad dtype
    string is a ConfigError naming the key, not a numpy TypeError from
    deep inside state init."""
    from ..core.config import ConfigError
    try:
        dt = jnp.dtype(cfg.precision.param_dtype)
    except TypeError as e:
        raise ConfigError(
            f"precision.param_dtype={cfg.precision.param_dtype!r} is not "
            f"a recognized dtype ({e}); use e.g. 'float32' or 'bfloat16'"
        ) from e
    if not jnp.issubdtype(dt, jnp.floating):
        raise ConfigError(
            f"precision.param_dtype={cfg.precision.param_dtype!r} is not a "
            "floating dtype")
    return jnp.float32 if cfg.precision.master_weights else dt


def state_partition_specs(model: Model, cfg: ExperimentConfig,
                          topo: Topology) -> TrainState:
    """A TrainState-shaped pytree of PartitionSpecs: P() (replicated)
    scalars, per-leaf engine-derived specs for param-shaped subtrees
    (tensor/pipeline/expert placements per the model's rule table), and
    — under ``parallel.shard_weight_update`` — optimizer moment slots
    split over the replica axis per the ZeRO-1 plan (every slot of a
    multi-slot optimizer shards the same way). Under
    ``parallel.resident_sharded`` the PARAMS take the same
    replica-split flat placement as the slots — the plan is the single
    source of truth for both layouts."""
    from jax.sharding import PartitionSpec as P_
    from ..train import optim as optim_lib

    abstract = abstract_train_params(model, cfg, topo)
    pspec = params_partition_specs(model, cfg, topo, params=abstract)
    opt = optim_lib.make_optimizer(cfg.optim)
    interval = cfg.sync.mode == "interval"
    plan = zero1_plan_for(model, cfg, topo, params=abstract)
    slot_spec = (zero1_state_specs(plan, pspec) if plan is not None
                 else pspec)
    mspec = optim_lib.init_slots(opt, lambda: slot_spec)
    param_spec = (slot_spec if plan is not None and plan.params_sharded
                  else pspec)
    return TrainState(
        params=param_spec,
        momentum=mspec,
        step=P_(), updates_applied=P_(), root_key=P_(),
        window_acc=pspec if interval else None,
        window_rounds=P_(), wall_ms=P_(), next_apply_ms=P_())


def init_train_state(model: Model, cfg: ExperimentConfig,
                     topo: Topology | None = None) -> TrainState:
    from ..train import optim as optim_lib

    params = _build_params(model, cfg, topo)
    store_dt = resolved_param_dtype(cfg)
    if store_dt != jnp.float32:
        # true low-precision training (no master copy): params are cast
        # once here and updated in this dtype from now on
        params = jax.tree.map(
            lambda p: (p.astype(store_dt)
                       if jnp.issubdtype(p.dtype, jnp.floating) else p),
            params)
    plan = (zero1_plan_for(model, cfg, topo, params=params)
            if topo is not None else None)
    opt = optim_lib.make_optimizer(cfg.optim)

    def one_slot_tree():
        if plan is not None:
            return zero1_init_state(params, plan,
                                    dtype_fn=optim_lib.slot_dtype)
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, optim_lib.slot_dtype(p.dtype)),
            params)

    momentum = optim_lib.init_slots(opt, one_slot_tree)
    interval = cfg.sync.mode == "interval"
    if plan is not None and plan.params_sharded:
        # resident-sharded layout: params live flattened-padded like
        # the slots (host-side pack at init; the engine's padding is
        # zeros by contract so the pack is exact)
        params = zero1_pack(params, plan)
    return TrainState(
        params=params,
        momentum=momentum,
        step=jnp.zeros((), jnp.int32),
        updates_applied=jnp.zeros((), jnp.int32),
        root_key=prng.root_key(cfg.train.seed),
        # fp32 always: the window accumulates float32 masked means even
        # when params store low-precision (precision.param_dtype)
        window_acc=(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if interval else None),
        window_rounds=jnp.zeros((), jnp.float32),
        wall_ms=jnp.zeros((), jnp.float32),
        next_apply_ms=jnp.asarray(cfg.sync.interval_ms, jnp.float32),
    )


# ---------------------------------------------------------------------------
# canonical checkpoint layout (ZeRO-1 pack/unpack) + mesh portability
# ---------------------------------------------------------------------------

def world_signature(topo: Topology) -> dict:
    """The world a checkpoint is saved under — JSON-clean, stamped into
    every artifact's ``extra["world"]`` (train/loop.py ``_save``) so a
    restore can tell "same world, graft directly" from "resized world,
    reshard" and name both sides in errors
    (train/checkpoint.py ``WorldSizeMismatchError``). Only axes > 1
    enter the mesh record, so a pure-DP world compares equal however
    many size-1 axes the mesh spells out."""
    return {"num_replicas": int(topo.num_replicas),
            "process_count": int(jax.process_count()),
            "mesh": {ax: int(topo.mesh.shape[ax])
                     for ax in topo.mesh.axis_names
                     if int(topo.mesh.shape[ax]) > 1}}


def restore_for_topology(model: Model, cfg: ExperimentConfig,
                         topo: Topology, train_dir, template_state: TrainState,
                         step: int | None = None,
                         on_event: Callable[[dict], None] | None = None,
                         ) -> tuple[TrainState, dict, int] | None:
    """Mesh-portable restore (ROADMAP item 2, TF-Replicator's
    resource-shape-agnostic replicas): load an artifact saved under ANY
    world size and reshard it for the CURRENT mesh.

    Why this works without migration code:

    * **Params / sharded (tp/pp) state** — checkpoints store logical
      global arrays (the per-host sharded layout reassembles them from
      every saver process's shard file regardless of the reader's
      process count); the caller re-splits them per the NEW spec trees
      by placing the result with ``Topology.device_put_state`` over
      ``state_partition_specs`` — the rule engine derives those from
      the current mesh, not the saver's.
    * **ZeRO-1 optimizer state** — the canonical-layout contract
      unpacks momentum to logical shapes on save, so restore re-derives
      the :class:`~..parallel.partition_rules.Zero1Plan` (padding,
      chunk ownership) from the NEW replica count and repacks; an
      artifact that kept the flat layout (cross-process sharded saves)
      carries a foreign ``pad`` and is re-padded exactly
      (``zero1_pack`` truncates zero padding, never data).
    * **Data cursor** — ``extra["data_iter"]`` carries the lockstep
      ``batches`` coordinate plus the saver's world; the new world's
      ``BatchIterator.restore`` reassigns it so no sample range is
      dropped or double-visited (data/pipeline.py).

    A world change is reported through ``on_event`` as
    ``action: "cross_world_restore"`` naming both worlds — the
    journaled evidence the chaos cross-world resume invariant pairs
    with the supervisor's ``event: "reconfigure"`` license.

    **Cross-optimizer guard**: an artifact whose saved config carries a
    different optimizer-STATE kind (none/momentum/lars/lamb —
    train/optim.opt_state_kind) than this run raises the typed
    :class:`~..train.checkpoint.OptimizerStateMismatchError` BEFORE any
    graft is attempted. LARS and momentum state share a tree shape, so
    a structural check alone would silently reinterpret one as the
    other; and a shape mismatch (momentum tree into LAMB's
    ``{"m","v"}`` slots) would surface as an opaque flax structure
    error. Neither is a fallback-past-it condition — a kind mismatch
    affects every step of the run equally."""
    from ..train import checkpoint as ckpt
    from ..train import optim as optim_lib
    try:
        extra_got = ckpt.read_checkpoint_extra(train_dir, step)
    except (OSError, ValueError, KeyError):
        # unreadable/torn LATEST artifact: the restore call below owns
        # corrupt-checkpoint fallback (older steps of the same run
        # carry the same optimizer config, so the guard loses nothing)
        extra_got = None
    if extra_got is not None:
        saved_extra, probe_step = extra_got
        saved_optim = ((saved_extra or {}).get("config") or {}).get("optim")
        saved_kind = optim_lib.saved_opt_state_kind(saved_optim)
        want_kind = optim_lib.opt_state_kind(cfg.optim)
        if saved_kind is not None and saved_kind != want_kind:
            raise ckpt.OptimizerStateMismatchError(
                f"checkpoint step={probe_step} in {train_dir} holds "
                f"{saved_kind!r} optimizer state (saved optim config "
                f"{saved_optim!r}) but this run's optim.name="
                f"{cfg.optim.name!r} needs {want_kind!r} state; refusing "
                "to graft mismatched opt-state trees — restore under the "
                "saving optimizer, or start the new optimizer fresh "
                "(train.resume=false / a fresh train_dir)",
                saved_kind=saved_kind, requested_kind=want_kind)
    restored = ckpt.restore_checkpoint(train_dir, template_state,
                                       step=step, on_event=on_event)
    if restored is None:
        return None
    state, extra, got_step = restored
    # precision portability: params are stored in the saving run's
    # storage dtype (fp32 masters, or a low-precision no-master layout);
    # normalize to THIS config's storage dtype so a precision-knob
    # change never leaves a stale-dtype tree in the live state
    store_dt = resolved_param_dtype(cfg)

    def _to_storage_dtype(p):
        dt = getattr(p, "dtype", None)
        if dt is None or not jnp.issubdtype(jnp.dtype(dt), jnp.floating):
            return p
        return p if jnp.dtype(dt) == store_dt else p.astype(store_dt)

    state = state.replace(params=jax.tree.map(_to_storage_dtype,
                                              state.params))
    # the plan (padding, chunk ownership) comes from the CURRENT
    # replica count — never the saver's n
    plan = zero1_plan_for(model, cfg, topo)
    state = pack_restored_state(state, plan)
    saved_world = (extra or {}).get("world")
    current = world_signature(topo)
    if isinstance(saved_world, dict) and saved_world != current:
        logger.info("cross-world restore: checkpoint step=%d saved under "
                    "world %s resharded onto %s", got_step, saved_world,
                    current)
        if on_event is not None:
            on_event({"layer": "checkpoint",
                      "action": "cross_world_restore", "step": got_step,
                      "saved_world": saved_world, "new_world": current})
    return state, extra, got_step


def canonical_save_state(state: TrainState,
                         plan: Zero1Plan | None) -> TrainState:
    """The state as checkpoints store it: optimizer buffers in their
    LOGICAL shapes regardless of the in-memory ZeRO-1 layout, so the
    artifact (and its canonical path digest, train/checkpoint.py) is
    byte-stable across ``parallel.shard_weight_update`` settings and a
    sharded run's checkpoint restores onto a replicated config (and
    vice versa) with no migration. Multi-slot optimizer state (LAMB's
    first/second moments) unpacks per slot, same contract; under
    ``parallel.resident_sharded`` the params unpack too — artifacts
    carry logical params whatever layout the live state keeps them in,
    so the path digest is identical across comm_buckets /
    resident_sharded / shard_weight_update. Host-side; a no-op without
    a plan."""
    from ..train import optim as optim_lib
    if plan is None:
        return state
    if state.momentum is not None:
        state = state.replace(momentum=optim_lib.map_slots(
            lambda tree: zero1_unpack(tree, plan), state.momentum))
    if plan.params_sharded:
        state = state.replace(params=zero1_unpack(state.params, plan))
    return state


def pack_restored_state(state: TrainState,
                        plan: Zero1Plan | None) -> TrainState:
    """Inverse of :func:`canonical_save_state` on the restore path:
    fold canonically-saved (logical-shape) optimizer slots — and, when
    the plan keeps params resident-sharded, the params — back into the
    flattened-padded replica-shard layout the live state uses.
    Exact — padding is zeros, truncation only ever removes padding."""
    from ..train import optim as optim_lib
    if plan is None:
        return state
    if state.momentum is not None:
        state = state.replace(momentum=optim_lib.map_slots(
            lambda tree: zero1_pack(tree, plan), state.momentum))
    if plan.params_sharded:
        state = state.replace(params=zero1_pack(state.params, plan))
    return state


def _spec_norm_axes(spec) -> tuple[str, ...]:
    """The mesh axes a PartitionSpec pins any dim to — what a partial
    leaf's sum-of-squares must psum over so the trust-ratio math sees
    the FULL logical leaf's norms (TP/stage/expert placements hold
    shards inside shard_map)."""
    axes: list[str] = []
    for entry in tuple(spec):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.extend(a for a in entry if a is not None)
        else:
            axes.append(entry)
    return tuple(axes)


@jax.named_scope("update")
def _apply_tree_update(opt, params: Any, grads: Any, opt_state: Any,
                       lr: jax.Array, t: jax.Array,
                       param_specs: Any) -> tuple[Any, Any]:
    """The replicated-discipline weight update: map the optimizer's
    pure per-leaf rule (train/optim.py) over full logical leaves.
    ``norm_reduce`` completes partial sums over whatever non-replica
    axes a leaf is sharded on (its PartitionSpec); fully-replicated
    leaves reduce with the identity. NO masking guard here — callers
    own the all-masked no-op semantics (lr·applied for stateless sgd,
    a select for stateful optimizers whose moments would decay)."""
    from ..train import optim as optim_lib

    p_leaves, treedef = jax.tree.flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    spec_leaves = treedef.flatten_up_to(param_specs)
    in_slot_trees = optim_lib.slot_trees(opt, opt_state)
    slot_leaves = [treedef.flatten_up_to(tr) for tr in in_slot_trees]

    new_p: list = []
    new_slots: list[list] = [[] for _ in in_slot_trees]
    for i, (p, g, spec) in enumerate(zip(p_leaves, g_leaves, spec_leaves)):
        axes = _spec_norm_axes(spec)
        nr = ((lambda x, a=axes: lax.psum(x, a)) if axes
              else (lambda x: x))
        slots = tuple(sl[i] for sl in slot_leaves)
        np_, ns = opt.update_leaf(p, g, slots, lr, t, nr,
                                  adapt=len(getattr(p, "shape", ())) > 1)
        new_p.append(np_)
        for j, s in enumerate(ns):
            new_slots[j].append(s)
    return (jax.tree.unflatten(treedef, new_p),
            optim_lib.from_slot_trees(
                opt, [jax.tree.unflatten(treedef, sl) for sl in new_slots]))


def _pad_flat(x: jax.Array, lp) -> jax.Array:
    """Flatten a logical leaf and zero-pad it to the plan's ``pad``
    length (the even-split layout; padding math lives in the engine,
    partition_rules.LeafShardPlan)."""
    flat = x.reshape(-1)
    if lp.pad == lp.size:
        return flat
    return jnp.concatenate(
        [flat, jnp.zeros((lp.pad - lp.size,), flat.dtype)])


@jax.named_scope("update")
def _zero1_update(params: Any, grads: Any, opt_state: Any,
                  flag: jax.Array, lr: jax.Array, t: jax.Array,
                  axis: str, plan: Zero1Plan, opt, param_specs: Any
                  ) -> tuple[Any, Any, jax.Array, jax.Array]:
    """The ZeRO-1 weight-update discipline (arXiv:2004.13336), inside
    shard_map: per sharded leaf, the masked gradients are
    REDUCE-SCATTERED over the replica axis (each replica receives the
    summed 1/n slice — the full mean gradient is never materialized),
    the optimizer's moment slots and param slice are updated locally
    via the same pure per-leaf rule the replicated path uses
    (train/optim.py — trust-ratio norms complete over the replica axis,
    exact because ZeRO padding is zeros), and the fresh param slices
    are allgathered back to the replicated layout the forward pass
    consumes. Fallback leaves (tensor-parallel placements, leaves below
    the shard floor) take the classic replicated psum + full update,
    with their norms completed over whatever axes their spec shards.

    Masking semantics match the replicated path exactly: gradients are
    pre-scaled by ``flag / max(psum(flag), 1)`` so the scattered sum IS
    the masked mean, and an all-masked step is a true no-op (stateless
    SGD scales lr by the applied flag; stateful optimizers — whose
    moments would decay — are select-guarded).

    **Bucketed overlap** (``plan.comm_buckets > 1``, arXiv:1810.11112):
    the sharded leaves' collectives are regrouped into layer-ordered
    buckets (``partition_rules.comm_bucket_assignment``) — per bucket,
    each leaf's padded gradient reshapes to ``[n, chunk]``, the rows
    concatenate into one ``[n, C_b]`` matrix, and ONE ``psum_scatter``
    hands this replica its concatenated chunk row; the allgather leg
    reassembles per bucket the same way. A bucket's scatter depends
    only on its own leaves' gradients, so the compiler can issue it
    while earlier layers' backward still runs, and the per-collective
    launch cost amortizes over the bucket. The per-ELEMENT cross-
    replica sums are untouched by the regrouping — same addends, same
    collective op, same dtype — so losses/params stay bitwise equal to
    the monolithic path (pinned in tests/test_zero1.py).

    **Resident-sharded params** (``plan.params_sharded``): the param
    leaf arriving here IS this replica's ``[chunk]`` slice (the state
    keeps the flat layout between steps), so the update skips both the
    pre-update ``dynamic_slice`` and the post-update allgather — the
    NEXT forward's just-in-time bucket gather replaces it
    (:func:`_gather_resident_params`).

    Returns ``(new_params, new_opt_state, num_contributors, applied)``.
    """
    from ..train import optim as optim_lib

    scale, num = contribution_scale(flag, axis)
    applied = (num > 0).astype(jnp.int32)
    me = lax.axis_index(axis)

    p_leaves, treedef = jax.tree.flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    lp_leaves = treedef.flatten_up_to(plan.leaf_plans)
    spec_leaves = treedef.flatten_up_to(param_specs)
    in_slot_trees = optim_lib.slot_trees(opt, opt_state)
    slot_leaves = [treedef.flatten_up_to(tr) for tr in in_slot_trees]
    stateless = opt.num_slots == 0
    # stateless sgd: lr·0 is exact, so scaling lr by the applied flag
    # IS the all-masked no-op (same trick as the replicated path)
    lr_eff = lr * applied.astype(jnp.float32) if stateless else lr
    resident = plan.params_sharded
    bucketed = plan.comm_buckets > 1
    buckets = (comm_bucket_assignment(plan) if bucketed or resident
               else [])

    def guard(new, old):
        return new if stateless else jnp.where(applied > 0, new, old)

    # device scopes (obsv/spans.py): inside this function's `update`,
    # the mask and every reduction of a gradient are `aggregate`, as
    # masked_mean_psum is on the replicated path
    with jax.named_scope("aggregate"):
        gm_leaves = [g * scale.astype(g.dtype) for g in g_leaves]

    # bucketed reduce-scatter: one collective per layer-ordered bucket,
    # issued as soon as that bucket's gradients exist in the dataflow
    gsh_by_leaf: dict[int, jax.Array] = {}
    if bucketed:
        for bucket in buckets:
            with jax.named_scope("aggregate"):
                rows = [_pad_flat(gm_leaves[i], lp_leaves[i])
                        .reshape(plan.n, lp_leaves[i].chunk)
                        for i in bucket]
                scat = lax.psum_scatter(
                    jnp.concatenate(rows, axis=1), axis,
                    scatter_dimension=0, tiled=True)[0]
            off = 0
            for i in bucket:
                c = lp_leaves[i].chunk
                gsh_by_leaf[i] = scat[off:off + c]
                off += c

    new_p: list = []
    new_slots: list[list] = [[] for _ in in_slot_trees]
    upd_chunks: dict[int, jax.Array] = {}  # bucketed gather leg inputs
    for i, (p, gm, lp, spec) in enumerate(
            zip(p_leaves, gm_leaves, lp_leaves, spec_leaves)):
        slots = tuple(sl[i] for sl in slot_leaves)
        adapt = len(lp.shape) > 1
        if lp.sharded:
            if bucketed:
                gsh = gsh_by_leaf[i]
            else:
                # monolithic discipline: reduce-scatter per leaf —
                # [pad] masked grads → this replica's summed [chunk]
                # slice (already the mean via the pre-scale)
                with jax.named_scope("aggregate"):
                    gsh = lax.psum_scatter(_pad_flat(gm, lp), axis,
                                           scatter_dimension=0, tiled=True)
            psh = (p if resident
                   else lax.dynamic_slice(_pad_flat(p, lp),
                                          (me * lp.chunk,), (lp.chunk,)))
            nps, nslots = opt.update_leaf(
                psh, gsh, slots, lr_eff, t,
                lambda x: lax.psum(x, axis), adapt)
            # select on the chunk — 1/n of the replicated guard cost
            nps = guard(nps, psh)
            nslots = tuple(guard(ns, s) for ns, s in zip(nslots, slots))
            if resident:
                new_p.append(nps)  # stays a chunk; next forward gathers
            elif bucketed:
                upd_chunks[i] = nps
                new_p.append(None)  # filled by the bucket gather below
            else:
                full = mesh_lib.gather_chunks_replicated(
                    nps, axis, lp.pad, me * lp.chunk)
                new_p.append(full[:lp.size].reshape(lp.shape))
        else:
            with jax.named_scope("aggregate"):
                mean = lax.psum(gm, axis)
            axes = _spec_norm_axes(spec)
            nr = ((lambda x, a=axes: lax.psum(x, a)) if axes
                  else (lambda x: x))
            npv, nslots = opt.update_leaf(p, mean, slots, lr_eff, t,
                                          nr, adapt)
            new_p.append(guard(npv, p))
            nslots = tuple(guard(ns, s) for ns, s in zip(nslots, slots))
        for j, s in enumerate(nslots):
            new_slots[j].append(s)
    if bucketed and not resident:
        # allgather leg, per bucket: one collective reassembles every
        # leaf of the bucket; column slices of the replicated [n, C_b]
        # recover each leaf's [n, chunk] view, whose row-major flatten
        # IS its padded layout
        for bucket in buckets:
            cat = jnp.concatenate([upd_chunks[i] for i in bucket])
            full = mesh_lib.gather_bucket_replicated(cat, axis, plan.n)
            off = 0
            for i in bucket:
                lp = lp_leaves[i]
                flat = full[:, off:off + lp.chunk].reshape(-1)
                new_p[i] = flat[:lp.size].reshape(lp.shape)
                off += lp.chunk
    params_out = jax.tree.unflatten(treedef, new_p)
    state_out = optim_lib.from_slot_trees(
        opt, [jax.tree.unflatten(treedef, sl) for sl in new_slots])
    return params_out, state_out, num, applied


def _gather_resident_params(params: Any, plan: Zero1Plan,
                            axis: str) -> Any:
    """The just-in-time weight gather of the resident-sharded layout
    (``parallel.resident_sharded``): reassemble full LOGICAL param
    leaves from the per-replica flat chunks the state carries, one
    collective per layer-ordered comm bucket — the next forward's
    gather replacing the classic post-update allgather
    (arXiv:2004.13336 §5). Runs inside shard_map on the chunk view;
    fallback (unsharded) leaves pass through untouched."""
    leaves, treedef = jax.tree.flatten(params)
    lp_leaves = treedef.flatten_up_to(plan.leaf_plans)
    out = list(leaves)
    for bucket in comm_bucket_assignment(plan):
        cat = jnp.concatenate([leaves[i] for i in bucket])
        full = mesh_lib.gather_bucket_replicated(cat, axis, plan.n)
        off = 0
        for i in bucket:
            lp = lp_leaves[i]
            flat = full[:, off:off + lp.chunk].reshape(-1)
            out[i] = flat[:lp.size].reshape(lp.shape)
            off += lp.chunk
    return jax.tree.unflatten(treedef, out)


# jitted gather per (plan, mesh) — a fresh jax.jit wrapper per call
# would miss the jit cache and recompile the gather on every
# evaluate(). Keyed by id(plan) with the plan itself stored for the
# identity check (its dict-structured leaf_plans make it unhashable);
# the stored reference pins the plan, so ids can't be recycled under a
# live entry — hence the size cap, which bounds what the cache keeps
# alive across many short-lived Trainers.
_logical_params_fns: dict[int, tuple] = {}


def logical_params(state_params: Any, plan: Zero1Plan | None,
                   topo: Topology) -> Any:
    """A REPLICATED logical-layout view of possibly resident-sharded
    live params — what in-process consumers that want the classic
    layout (Trainer.evaluate feeding build_eval_step) call. A
    passthrough without a resident plan; otherwise a jitted
    truncate-and-reshape with replicated out_shardings (cached per
    plan, so repeated evals pay a gather, not a recompile), working on
    multi-host meshes too (checkpoint consumers never need this —
    artifacts already store the canonical logical layout)."""
    if plan is None or not plan.params_sharded:
        return state_params
    from jax.sharding import NamedSharding
    cached = _logical_params_fns.get(id(plan))
    if cached is None or cached[0] is not plan or cached[1] is not topo.mesh:

        def unpack(tree):
            return jax.tree.map(
                lambda x, lp: (x[:lp.size].reshape(lp.shape) if lp.sharded
                               else x),
                tree, plan.leaf_plans)

        if len(_logical_params_fns) >= 32:
            _logical_params_fns.clear()
        cached = (plan, topo.mesh,
                  jax.jit(unpack,
                          out_shardings=NamedSharding(topo.mesh, P())))
        _logical_params_fns[id(plan)] = cached
    return cached[2](state_params)


def _gather_replicated(x: jax.Array, axis: str, n: int) -> jax.Array:
    """All-gather a per-replica scalar into a REPLICATED [n] vector.

    Expressed as a one-hot psum instead of ``lax.all_gather`` because
    psum's output is statically known to be replicated over ``axis`` —
    so it can leave shard_map under an out_spec of P() and every host
    of a multi-host run holds the full vector (an all_gather result
    stays marked device-varying and would need a sharded out_spec,
    which non-addressable processes cannot materialize)."""
    me = lax.axis_index(axis)
    onehot = (jnp.arange(n) == me).astype(x.dtype)
    return lax.psum(onehot * x, axis)


def measure_bucket_comm_ms(topo: Topology, plan: Zero1Plan,
                           repeats: int = 3) -> list[float]:
    """Calibrate each comm bucket's scatter+gather wall ms in
    isolation (median of ``repeats`` timed runs of a tiny jitted
    program per bucket) — the per-bucket comm gauge the timing report
    surfaces when overlap is on (obsv/timing.py). Inside the fused
    train step the per-bucket comm time is not separately observable;
    this measures the same collectives on zeros of the same shapes.
    One small compile per bucket — call from precompile, not per
    step."""
    import statistics
    import time as _time
    axis = topo.replica_axis
    n = plan.n
    lps = jax.tree.leaves(plan.leaf_plans,
                          is_leaf=lambda x: hasattr(x, "sharded"))
    out: list[float] = []
    for bucket in comm_bucket_assignment(plan):
        c_b = sum(lps[i].chunk for i in bucket)

        def probe(x):
            s = lax.psum_scatter(x, axis, scatter_dimension=0,
                                 tiled=True)[0]
            g = mesh_lib.gather_bucket_replicated(s, axis, n)
            return g.sum()

        fn = jax.jit(mesh_lib.shard_map(probe, mesh=topo.mesh,
                                        in_specs=P(), out_specs=P()))
        x = jnp.zeros((n, c_b), jnp.float32)
        float(fn(x))  # compile + warm
        times = []
        for _ in range(max(1, repeats)):
            t0 = _time.perf_counter()
            float(fn(x))
            times.append((_time.perf_counter() - t0) * 1e3)
        out.append(statistics.median(times))
    return out


def build_train_step(model: Model, cfg: ExperimentConfig, topo: Topology,
                     schedule: Schedule) -> Callable[[TrainState, dict], tuple[TrainState, dict]]:
    """Compile the per-step SPMD training function.

    Returns ``step_fn(state, batch, measured_ms=None, discipline=None)
    -> (state, metrics)`` where ``batch = {"image": [B, ...], "label":
    [B]}`` is globally batched and sharded over the replica axis, and
    state/metrics are replicated. ``measured_ms`` is an optional
    per-replica [n] vector of real measured step times (ms), sharded
    over the replica axis: each host feeds the entries for its own
    replicas (Topology.device_put_measured), so quorum/timeout/interval
    policies select on genuine per-replica speed — ≙ the reference's
    measured per-worker CDF semantics (src/timeout_manager.py:48-61)
    without the RPC mesh. Defaults to zeros (pure synthetic-profile
    timing).

    ``discipline`` is an optional replicated [3] float32 vector
    ``(k, timeout_ms, interval_ms)`` (make_discipline_vector) carrying
    the aggregation-discipline parameters as *traced* inputs: the
    adaptive straggler controller (train/discipline.py) swaps this
    scalar buffer at runtime and the same compiled executable keeps
    running — a discipline change costs a device_put, not a recompile.
    Defaults to the static values from ``cfg.sync``.
    """
    axis = topo.replica_axis
    n = topo.num_replicas
    sync = cfg.sync
    sync.validate(num_replicas=n)
    mode = sync.mode
    if mode not in ("sync", "quorum", "timeout", "interval", "cdf"):
        raise ValueError(f"unknown sync mode {mode!r}")
    k = policies.resolve_aggregate_k(sync, n)
    from ..train import optim as optim_lib
    opt = optim_lib.make_optimizer(cfg.optim)  # validates the section
    # Gradient accumulation (train.grad_accum_steps): the step receives
    # accum host batches concatenated along dim 0 and scans them as
    # microbatches, accumulating gradients in float32 before ONE
    # optimizer application — effective batch = data.batch_size × accum.
    accum = max(1, int(cfg.train.grad_accum_steps))
    # Mixed precision (cfg.precision): with master weights the state
    # params are float32 and the forward pass sees a derived
    # param_dtype view; differentiating w.r.t. the view is exact — the
    # cast's transpose casts cotangents back, and grads are accumulated
    # in float32 regardless.
    param_dtype = jnp.dtype(cfg.precision.param_dtype)
    fwd_cast = (cfg.precision.master_weights
                and param_dtype != jnp.float32)

    def fwd_view(params):
        if not fwd_cast:
            return params
        with jax.named_scope("cast"):
            return jax.tree.map(
                lambda p: (p.astype(param_dtype)
                           if jnp.issubdtype(p.dtype, jnp.floating) else p),
                params)

    # Sequence parallelism: when the mesh spends devices on the seq
    # axis, the model must provide a sequence-sharded apply (the
    # transformer does, via ring/all-to-all attention). Each shard then
    # computes a PARTIAL loss/gradient over its token slice; psum over
    # the seq axis reassembles the exact full-sequence gradient before
    # the replica-axis aggregation disciplines see it.
    #
    # Tensor parallelism: when the mesh's model axis is >1, params are
    # placed per the model's TP partition specs; each rank holds its
    # head/MLP column shard, activations stay replicated over the axis
    # (psums inside apply), and each rank's param gradients are its own
    # shard's — no model-axis reduction of gradients is needed.
    seq_ax = topo.seq_axis
    n_seq = topo.mesh.shape[seq_ax]
    model_ax = topo.model_axis
    n_model = topo.mesh.shape[model_ax]
    # Pipeline parallelism: layers sharded over the stage axis, batch
    # microbatched through the activation pipeline (ops/pipeline.py).
    # Stage-sharded param grads stay local; replicated leaves (embed,
    # norms) get their stage-psum from the AD transpose of replication.
    stage_ax = topo.stage_axis
    n_stage = topo.mesh.shape[stage_ax]
    # Expert parallelism: experts sharded over the expert axis; composes
    # with TP (model axis splits heads + every expert's hidden dim).
    expert_ax = topo.expert_axis
    n_expert = topo.mesh.shape[expert_ax]
    if ((n_seq > 1 or n_model > 1 or n_expert > 1) and n_stage == 1
            and model.sharded_apply_factory is None):
        raise ValueError(
            f"mesh has seq_parallelism={n_seq} / model_parallelism="
            f"{n_model} / expert_parallelism={n_expert} but model "
            f"{model.name!r} supports none of them "
            "(no sharded_apply_factory)")
    pp_schedule = cfg.mesh.pipeline_schedule
    if pp_schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline_schedule {pp_schedule!r}")
    pp_1f1b_grads_fn = None
    if n_stage > 1:
        if model.pp_apply_factory is None:
            raise ValueError(f"mesh has pipeline_parallelism={n_stage} but "
                             f"model {model.name!r} has no pipeline apply")
        if pp_schedule == "1f1b":
            # fused interleaved schedule (ops/pipeline.py): explicit
            # forward/backward chunk-works in one scan — built below
            # instead of value_and_grad. TP/SP/EP collectives inside
            # the chunk bodies execute inside the engine's
            # stage-varying switch branches; that is safe because they
            # reduce over NON-stage axes whose participant groups share
            # a stage coordinate and hence a branch (ops/pipeline.py).
            if model.pp_1f1b_grads_factory is None:
                raise ValueError(f"model {model.name!r} has no 1f1b "
                                 "pipeline support")
            pp_1f1b_grads_fn = model.pp_1f1b_grads_factory(
                stage_ax, cfg.mesh.pipeline_microbatches,
                cfg.mesh.pipeline_chunks,
                model_ax if n_model > 1 else None,
                seq_ax if n_seq > 1 else None,
                expert_ax if n_expert > 1 else None)
            pp_apply = None
        else:
            # PP outermost; TP (model axis) inside each stage; SP (seq
            # axis) through the stage blocks' sharded attention; EP
            # (expert axis) through the blocks' grouped MoE dispatch —
            # every device runs the same tick schedule so attention and
            # expert collectives stay lockstep inside the pipeline scan
            pp_apply = model.pp_apply_factory(
                stage_ax, cfg.mesh.pipeline_microbatches,
                model_ax if n_model > 1 else None,
                seq_ax if n_seq > 1 else None,
                expert_ax if n_expert > 1 else None)
    else:
        pp_apply = None
    sharded_apply = (model.sharded_apply_factory(
        seq_ax if n_seq > 1 else None, model_ax if n_model > 1 else None,
        expert_ax if n_expert > 1 else None)
        if ((n_seq > 1 or n_model > 1 or n_expert > 1)
            and pp_apply is None and pp_1f1b_grads_fn is None)
        else None)
    # The SP/PP loss paths do not thread a dropout key; refuse loudly
    # instead of silently training a dropout model without dropout.
    if ((sharded_apply is not None or pp_apply is not None
            or pp_1f1b_grads_fn is not None)
            and model.uses_dropout):
        raise ValueError(
            f"model {model.name!r} uses dropout, but the sharded "
            "(SP/TP/PP) loss paths do not thread a dropout key; set "
            "model.dropout_rate=0 or run it data-parallel only")
    # raw per-shard grads are needed w.r.t. the axes the masks/explicit
    # psums manage; the model axis stays as-is (sharded params are
    # already device-varying there)
    grad_axes = (axis, seq_ax) if n_seq > 1 else (axis,)
    state_specs = state_partition_specs(model, cfg, topo)
    # per-leaf LOGICAL param placements — what the trust-ratio norm
    # reductions complete partial sums over for non-replica-sharded
    # leaves (NOT state_specs.params, which under resident_sharded
    # carries the flat replica-split layout instead)
    pspec_tree = params_partition_specs(model, cfg, topo)
    # ZeRO-1 (parallel.shard_weight_update): reduce-scatter grads,
    # update only this replica's param/momentum slice, allgather fresh
    # params — per the engine's shard plan, which state_partition_specs
    # and init_train_state derived the state layout from.
    z_plan = zero1_plan_for(model, cfg, topo)
    if cfg.parallel.shard_weight_update and z_plan is None:
        logger.warning(
            "parallel.shard_weight_update=true is a no-op here (%s); "
            "running the replicated update",
            "replica axis is 1" if n <= 1 else
            f"sync.mode={mode!r} keeps the full windowed accumulator")

    has_aux = model.has_aux
    aux_w = model.aux_weight

    def local_loss(params, batch, dropout_key):
        """-> (loss, (logits, counts)): ``counts`` the pairs each held
        expert of each per-token routed layer took (``aux["counts"]``,
        [layers, held]), None from a model that gives none."""
        if has_aux:
            logits, aux = model.apply(params, batch["image"], train=True,
                                      dropout_key=dropout_key,
                                      return_aux=True)
            return (model.loss(logits, batch["label"]) + aux_w * aux["loss"],
                    (logits, aux.get("counts")))
        logits = model.apply(params, batch["image"], train=True,
                             dropout_key=dropout_key)
        return model.loss(logits, batch["label"]), (logits, None)

    def local_loss_pp(params, batch, dropout_key):
        del dropout_key
        if has_aux:  # MoE: per-group aux, tick-accumulated (apply_pp)
            logits, aux = pp_apply(params, batch["image"], return_aux=True)
            return (model.loss(logits, batch["label"]) + aux_w * aux["loss"],
                    logits)
        logits = pp_apply(params, batch["image"])  # stage-replicated
        return model.loss(logits, batch["label"]), logits

    def make_sp_loss(apply_fn, with_aux):
        """Per-(replica, seq-shard) partial next-token loss over any
        seq-sharded apply (the DP×SP×TP path, or the pipeline apply for
        PP×SP).

        Targets are inputs shifted left by one GLOBAL position, so the
        target of a shard's last token lives on the next shard — one
        ppermute fetches each neighbor's first column. The global last
        position has no target (weight 0), matching the dense
        ``transformer.loss_fn`` exactly: partial sums are normalized by
        the global valid-token count so psum(partials) == dense loss.
        """
        def sp_loss(params, batch, dropout_key):
            del dropout_key
            tokens = batch["image"]
            labels = batch["label"]
            b, s_loc = tokens.shape
            me_s = lax.axis_index(seq_ax)
            positions = me_s * s_loc + jnp.arange(s_loc)
            if with_aux:  # MoE: EP-only, SP×EP, or PP×SP×EP
                logits, aux = apply_fn(params, tokens, positions,
                                       return_aux=True)
                aux = aux["loss"]
            else:
                logits = apply_fn(params, tokens, positions)  # [b, s_loc, V]
                aux = 0.0

            # shard j receives shard (j+1)'s first target column
            perm = [((j + 1) % n_seq, j) for j in range(n_seq)]
            nxt = lax.ppermute(labels[:, :1], seq_ax, perm)
            tgt = jnp.concatenate([labels[:, 1:], nxt], axis=1).astype(jnp.int32)

            from ..models.transformer import sp_partial_token_loss
            s_global = s_loc * n_seq
            # total = this replica's global token count; the shared
            # kernel keeps this path and the 1F1B seed head identical
            loss_part, acc_part = sp_partial_token_loss(
                logits, tgt, positions, s_global, b * (s_global - 1))
            # aux is already the full-token value on every seq shard
            # (moe_ffn pmeans its stats over the stats_axes), so the
            # caller's psum over the seq axis would count it n_seq
            # times — pre-divide so the psum reassembles exactly one.
            return loss_part + aux_w * aux / n_seq, acc_part
        return sp_loss

    local_loss_sp = (make_sp_loss(sharded_apply, has_aux)
                     if sharded_apply is not None else
                     make_sp_loss(pp_apply, has_aux)
                     if (pp_apply is not None and n_seq > 1) else None)

    def shard_fn(state: TrainState, batch: dict, measured_ms: jax.Array,
                 discipline: jax.Array) -> tuple[TrainState, dict]:
        me = lax.axis_index(axis)
        step = state.step
        my_measured_ms = measured_ms[0]  # this replica's [1]-shard
        # runtime discipline params (replicated [3]): traced, so the
        # adaptive controller swaps them without a recompile
        disc_k = discipline[DISC_K]
        disc_timeout_ms = discipline[DISC_TIMEOUT_MS]
        disc_interval_ms = discipline[DISC_INTERVAL_MS]

        # --- local forward+backward (one pass: the reference's second
        # forward per step, src/distributed_train.py:332-335, is a
        # documented quirk we do not replicate) -----------------------
        #
        # Params are replicated over the mesh; differentiating w.r.t. a
        # *replicated* value inside shard_map makes AD insert the
        # cross-axis psum itself (transpose of the broadcast). We need
        # the raw per-shard gradient — masks must apply BEFORE the
        # replica aggregation, and the seq-axis psum must be explicit —
        # so cast params to varying over every grad axis first.
        # Resident-sharded layout: the state carries per-replica flat
        # chunks; the just-in-time bucket gather reassembles the full
        # logical weights HERE — in the next step's forward — instead
        # of the update's trailing allgather (arXiv:2004.13336 §5).
        fwd_source = (state.params
                      if z_plan is None or not z_plan.params_sharded
                      else _gather_resident_params(state.params, z_plan,
                                                   axis))
        local_params = jax.tree.map(
            lambda x: lax.pcast(x, grad_axes, to="varying"), fwd_source)
        # master weights: the forward sees the derived param_dtype view
        fwd_params = fwd_view(local_params)

        def compute_grads(mb_batch, dkey):
            """(loss, train_acc, grads, counts) for ONE microbatch — the
            per-parallelism branch chain, shared by the single-shot and
            the accumulation paths. ``counts``: ``local_loss``'s, None on
            the other branches."""
            counts = None
            if pp_1f1b_grads_fn is not None:
                # fused 1F1B: the engine computes loss, accuracy and
                # grads in one interleaved scan — no outer
                # value_and_grad. Under SP the engine returns
                # per-seq-shard partials; psum reassembles the exact
                # dense values (same as the SP branch below).
                loss, train_acc, grads = pp_1f1b_grads_fn(
                    fwd_params, mb_batch["image"], mb_batch["label"])
                if n_seq > 1:
                    loss = lax.psum(loss, seq_ax)
                    train_acc = lax.psum(train_acc, seq_ax)
                    grads = jax.tree.map(lambda g: lax.psum(g, seq_ax),
                                         grads)
            elif local_loss_sp is not None:  # DP×SP×TP, or PP×SP
                (loss_p, acc_p), grads = jax.value_and_grad(
                    local_loss_sp, has_aux=True)(fwd_params, mb_batch, dkey)
                # reassemble the full-sequence gradient / metrics
                loss = lax.psum(loss_p, seq_ax)
                train_acc = lax.psum(acc_p, seq_ax)
                grads = jax.tree.map(lambda g: lax.psum(g, seq_ax), grads)
            elif pp_apply is not None:
                (loss, logits), grads = jax.value_and_grad(
                    local_loss_pp, has_aux=True)(fwd_params, mb_batch, dkey)
                train_acc = model.accuracy(logits, mb_batch["label"])
            else:
                (loss, (logits, counts)), grads = jax.value_and_grad(
                    local_loss, has_aux=True)(fwd_params, mb_batch, dkey)
                train_acc = model.accuracy(logits, mb_batch["label"])
            return loss, train_acc, grads, counts

        expert_counts = None
        if accum == 1:
            dkey = prng.replica_key(state.root_key, "dropout", step, me)
            loss, train_acc, grads, expert_counts = compute_grads(batch,
                                                                  dkey)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            # microbatch scan: fp32 accumulation, one optimizer apply.
            # The local rows are any accum-way partition of this
            # replica's slice of the effective batch — every sample
            # carries weight 1/(accum·b_local) locally and 1/n across
            # replicas, so the accumulated mean is exactly the
            # effective-batch mean regardless of the grouping.
            mb_batch = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)
            # the scan carry must enter with the varying mesh axes the
            # body's outputs have (per-replica loss/grads), so the
            # zeros take them from the abstract per-microbatch result
            def zeros_as(sds):
                z = jnp.zeros(sds.shape, jnp.float32)
                vma = tuple(sds.vma)
                return lax.pcast(z, vma, to="varying") if vma else z

            l_zero, a_zero, g_zero = jax.tree.map(
                zeros_as, jax.eval_shape(
                    lambda *a: compute_grads(*a)[:3],
                    jax.tree.map(lambda x: x[0], mb_batch),
                    prng.replica_key(state.root_key, "dropout", step, me)))

            def mb_body(carry, xs):
                g_acc, l_acc, a_acc = carry
                one_batch, idx = xs
                dkey = prng.replica_key(state.root_key, "dropout",
                                        step * accum + idx, me)
                l, a, g, _ = compute_grads(one_batch, dkey)
                g_acc = jax.tree.map(
                    lambda s, gi: s + gi.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l, a_acc + a), None

            (g_sum, l_sum, a_sum), _ = lax.scan(
                mb_body, (g_zero, l_zero, a_zero),
                (mb_batch, jnp.arange(accum)))
            grads = jax.tree.map(lambda g: g / accum, g_sum)
            loss = l_sum / accum
            train_acc = a_sum / accum

        # --- per-worker drop-connect before aggregation
        # (src/distributed_train.py:194-196) --------------------------
        if sync.drop_connect:
            dckey = prng.replica_key(state.root_key, "drop_connect", step, me)
            grads = drop_connect_grads(grads, dckey, sync.drop_connect_probability)

        # --- step-time model & contribution mask ---------------------
        with jax.named_scope("timing"):
            t_ms = policies.sample_step_time_ms(sync, state.root_key, step,
                                                me, my_measured_ms)
            if mode in ("sync", "cdf"):
                flag = jnp.ones((), jnp.float32)
            elif mode == "quorum":
                flag = policies.quorum_flag(t_ms, disc_k, axis)
            elif mode == "timeout":
                flag = policies.timeout_flag(t_ms, disc_timeout_ms)
            else:  # interval: stale if slower than a whole window
                flag = policies.timeout_flag(t_ms, disc_interval_ms)

        # --- apply discipline ----------------------------------------
        t_next = state.updates_applied.astype(jnp.float32) + 1.0
        if mode == "interval":
            mean_grads, num_contrib = masked_mean_psum(grads, flag, axis)
            new_state, applied = _interval_apply(state, mean_grads, t_ms,
                                                 disc_interval_ms)
        elif z_plan is not None:
            # ZeRO-1: no full mean gradient is ever built — the
            # reduce-scatter inside _zero1_update hands each replica
            # its slice of it directly
            lr = schedule(state.updates_applied)
            new_params, new_opt, num_contrib, applied = _zero1_update(
                state.params, grads, state.momentum, flag, lr, t_next,
                axis, z_plan, opt, pspec_tree)
            new_state = state.replace(
                params=new_params, momentum=new_opt,
                updates_applied=state.updates_applied + applied)
        else:
            mean_grads, num_contrib = masked_mean_psum(grads, flag, axis)
            lr = schedule(state.updates_applied)
            applied = (num_contrib > 0).astype(jnp.int32)
            # If every replica was masked out (possible under timeout),
            # the mean is zero and the update must be a true no-op.
            if opt.num_slots == 0:
                # stateless sgd: lr·0 is exact, so scaling the scalar
                # lr by the applied flag IS the no-op — no full-size
                # per-parameter select pass (a measured tax on small steps:
                # the harness removed at PR 48, BENCH_r04/r05.json in history)
                new_params, new_opt = _apply_tree_update(
                    opt, state.params, mean_grads, None,
                    lr * applied.astype(jnp.float32), t_next, pspec_tree)
            else:
                new_params, new_opt = _apply_tree_update(
                    opt, state.params, mean_grads, state.momentum, lr,
                    t_next, pspec_tree)
                # moment slots decay even on zero gradients, so a true
                # no-op needs the select
                with jax.named_scope("update"):
                    new_params = jax.tree.map(
                        lambda new, old: jnp.where(applied > 0, new, old),
                        new_params, state.params)
                    new_opt = jax.tree.map(
                        lambda new, old: jnp.where(applied > 0, new, old),
                        new_opt, state.momentum)
            new_state = state.replace(
                params=new_params, momentum=new_opt,
                updates_applied=state.updates_applied + applied)

        new_state = new_state.replace(step=step + 1)

        # --- metrics: everything comes out REPLICATED (scalars via
        # pmean/psum, per-replica series via all_gather) so every host
        # holds the full [n] timing vector — a multi-host process can
        # materialize its own copy without touching non-addressable
        # shards (≙ the CDF timing gossip, src/timeout_manager.py:48-61,
        # with no RPC mesh at all) ------------------------------------
        with jax.named_scope("timing"):
            metrics = {
                "loss": lax.pmean(loss, axis),
                "train_acc": lax.pmean(train_acc, axis),
                "lr": schedule(state.updates_applied),
                "num_contributors": num_contrib,
                "updates_applied": new_state.updates_applied,
                "step_times_ms": _gather_replicated(t_ms, axis, n),  # [n]
                "flags": _gather_replicated(flag, axis, n),          # [n]
                "applied": applied,
            }
            if expert_counts is not None:
                # pairs each held expert took, summed over the replicas
                metrics["expert_counts"] = lax.psum(expert_counts, axis)
        return new_state, metrics

    @jax.named_scope("update")
    def _interval_apply(state: TrainState, mean_grads: Any,
                        t_ms: jax.Array,
                        interval_ms: jax.Array) -> tuple[TrainState, jax.Array]:
        """Wall-clock-windowed aggregation (≙ the chief's recurring
        Timer running take_grad(1)-average-of-arrived,
        sync_replicas_optimizer_modified.py:208-215,371-373,392-393).

        A wall-clock-async update is not expressible inside one SPMD
        program (SURVEY §7), so the window is re-expressed over the
        lockstep loop: each step's masked mean joins a window
        accumulator; the modeled wall clock advances by the mean
        replica pace; when it crosses the window boundary the
        accumulated average is applied and the window resets.
        """
        acc = jax.tree.map(lambda a, g: a + g, state.window_acc, mean_grads)
        rounds = state.window_rounds + 1.0
        wall = state.wall_ms + lax.pmean(t_ms, axis)
        fire = wall >= state.next_apply_ms

        lr = schedule(state.updates_applied)
        window_mean = jax.tree.map(lambda a: a / rounds, acc)
        applied_params, applied_bufs = _apply_tree_update(
            opt, state.params, window_mean, state.momentum, lr,
            state.updates_applied.astype(jnp.float32) + 1.0, pspec_tree)

        def pick(new, old):
            return jax.tree.map(lambda a, b: jnp.where(fire, a, b), new, old)

        new_params = pick(applied_params, state.params)
        new_bufs = (None if state.momentum is None
                    else pick(applied_bufs, state.momentum))
        zeros = jax.tree.map(jnp.zeros_like, acc)
        new_acc = pick(zeros, acc)
        new_rounds = jnp.where(fire, 0.0, rounds)
        # Reschedule relative to *now*, as the reference timer does by
        # re-arming after each run (skipped windows are not replayed).
        next_apply = jnp.where(fire, wall + interval_ms, state.next_apply_ms)
        applied = fire.astype(jnp.int32)
        return state.replace(
            params=new_params, momentum=new_bufs, window_acc=new_acc,
            window_rounds=new_rounds, wall_ms=wall, next_apply_ms=next_apply,
            updates_applied=state.updates_applied + applied), applied

    mesh = topo.mesh
    metrics_specs = P()  # every metric comes out replicated
    batch_spec = P(axis, seq_ax) if n_seq > 1 else P(axis)
    sharded = mesh_lib.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(state_specs, batch_spec, P(axis), P()),
        out_specs=(state_specs, metrics_specs))
    jitted = jax.jit(sharded, donate_argnums=0)
    async_options = _async_options(mesh, axis)

    zeros_ms: list[jax.Array] = []  # lazily built + cached default
    disc_default: list[jax.Array] = []  # static-cfg discipline vector
    # AOT fast path: precompile() fills this with the
    # ahead-of-time compiled executable + the argument signature it was
    # lowered for; step_fn then dispatches matching concrete calls
    # through it — the first training step after a precompile (or a
    # warm-standby promotion) never waits on jit's compile path.
    aot_box: dict[str, Any] = {}

    def _default_measured() -> jax.Array:
        if not zeros_ms:
            zeros_ms.append(topo.zeros_measured())
        return zeros_ms[0]

    def _default_discipline() -> jax.Array:
        if not disc_default:
            disc_default.append(make_discipline_vector(
                k, sync.timeout_ms, sync.interval_ms))
        return disc_default[0]

    def _args_sig(args):
        leaves, treedef = jax.tree.flatten(args)
        return (treedef,
                tuple((getattr(x, "shape", ()), getattr(x, "dtype", None))
                      for x in leaves))

    def step_fn(state: TrainState, batch: dict,
                measured_ms: jax.Array | None = None,
                discipline: jax.Array | None = None):
        if measured_ms is None:
            measured_ms = _default_measured()
        if discipline is None:
            discipline = _default_discipline()
        exe = aot_box.get("exe")
        if exe is not None:
            # one flatten covers both guards: tracers ANYWHERE in the
            # args (a caller jitting over step_fn — e.g. a lax.scan of
            # steps, or a jit closing over state but tracing the batch)
            # must take the traceable jit path, and a different
            # signature (a test swapping batch shapes) simply compiles
            # through jit as before. Compared leafwise with early exit —
            # no per-step sig allocation on this hot path.
            leaves, treedef = jax.tree.flatten(
                (state, batch, measured_ms, discipline))
            sig_td, sig_leaves = aot_box["sig"]
            if (treedef == sig_td and len(leaves) == len(sig_leaves)
                    and not any(isinstance(x, jax.core.Tracer)
                                for x in leaves)
                    and all(getattr(x, "shape", ()) == s
                            and getattr(x, "dtype", None) == d
                            for x, (s, d) in zip(leaves, sig_leaves))):
                return exe(state, batch, measured_ms, discipline)
        return jitted(state, batch, measured_ms, discipline)

    def precompile(state: TrainState, batch: dict,
                   measured_ms: jax.Array | None = None,
                   discipline: jax.Array | None = None) -> dict[str, Any]:
        """AOT-compile the step for these exact avals (no execution, no
        donation — lowering only reads shapes) and arm the fast path.

        On TPU devices with more than one replica the compile carries
        :data:`ASYNC_ALL_REDUCE_OPTIONS`; a compiler that refuses them
        compiles without, with one warning. ``step_fn.jitted``, which
        runs when this was not called or the call's shapes differ, is
        compiled without them: the same values from a program whose
        all-reduces wait for the backward pass to end."""
        if measured_ms is None:
            measured_ms = _default_measured()
        if discipline is None:
            discipline = _default_discipline()
        args = (state, batch, measured_ms, discipline)
        t0 = time.perf_counter()
        # through jax's persistent compilation cache when an entry
        # point enabled it (core/compile_cache.py): the one warm path
        # across processes. The options are part of the cache's key.
        lowered = jitted.lower(*args)
        options = async_options
        try:
            exe = lowered.compile(compiler_options=options or None)
        except Exception as e:
            if not options:
                raise
            logger.warning(
                "the compiler refused %s (%s: %s): the train step is "
                "compiled without, its all-reduces synchronous",
                sorted(options), type(e).__name__, e)
            options = {}
            exe = lowered.compile()
        aot_box["exe"] = exe
        aot_box["sig"] = _args_sig(args)
        # the fields Trainer journals as the event:"compile" record
        # (its inline-compile fallback writes source "inline")
        return {"compile_s": round(time.perf_counter() - t0, 3),
                "source": "compiled",
                "compiler_options": sorted(options),
                "async_collectives": count_async_collectives(exe.as_text())}

    step_fn.precompile = precompile
    step_fn.jitted = jitted
    # the AOT executable the fast path runs (None before precompile) —
    # chip_smoke.py reads its text for the Mosaic custom calls
    step_fn.executable = lambda: aot_box.get("exe")
    step_fn.default_discipline = _default_discipline
    return step_fn


def build_eval_step(model: Model, cfg: ExperimentConfig, topo: Topology):
    """Sharded inference step: weighted accuracy/loss so padded
    examples (batch not divisible by replica count) don't bias metrics.

    ``batch = {"image", "label", "weight"}``; returns summed
    (correct, weighted_loss, weight) — caller divides.
    """
    axis = topo.replica_axis
    model_ax = topo.model_axis
    n_model = topo.mesh.shape[model_ax]
    n_stage = topo.mesh.shape[topo.stage_axis]
    n_expert = topo.mesh.shape[topo.expert_axis]
    if n_stage > 1:
        # pipeline-parallel params: stacked layout. Eval pipelines at
        # the largest microbatch count that divides the per-replica
        # eval rows (capped by the training cadence) — M=1 would run
        # the stages fully serialized, an S× eval slowdown measured in
        # the tens of minutes on deep CPU-mesh evals.
        if model.pp_apply_factory is None:
            raise ValueError(f"mesh has pipeline_parallelism={n_stage} but "
                             f"model {model.name!r} has no pipeline apply")
        tp_ax = model_ax if n_model > 1 else None
        ep_ax = topo.expert_axis if n_expert > 1 else None
        pspec: Any = params_partition_specs(model, cfg, topo)
        if (cfg.mesh.pipeline_schedule == "1f1b"
                and model.pp_1f1b_apply_factory is None):
            # mirror the train-path guard: fail with a clear error at
            # build time instead of an opaque trace-time NoneType call
            raise ValueError(f"model {model.name!r} has no 1f1b "
                             "pipeline support")
        cap = max(1, cfg.mesh.pipeline_microbatches)

        def run(params, images):
            # per-replica rows are static at trace time (eval batches
            # are padded to a fixed shape); pipeline at the largest
            # microbatch count ≤ the training cadence that divides
            # them. MoE included: token groups nest inside sequence
            # rows (ops/moe.py), so routing capacity and metrics are
            # identical for every microbatch split — the round-4 M=1
            # force is gone (tests pin M-invariance).
            b = images.shape[0]
            m_eval = max(m for m in range(1, cap + 1) if b % m == 0)
            if cfg.mesh.pipeline_schedule == "1f1b":
                apply_fn = model.pp_1f1b_apply_factory(
                    topo.stage_axis, m_eval, cfg.mesh.pipeline_chunks,
                    tp_ax, ep_ax)
            else:
                apply_fn = model.pp_apply_factory(topo.stage_axis, m_eval,
                                                  tp_ax, None, ep_ax)
            return apply_fn(params, images)
    elif n_model > 1 or n_expert > 1:
        # tensor-/expert-parallel params: sharded apply (full sequence
        # per device — eval batches are not seq-sharded), sharded in_spec
        if (model.tp_param_specs is None
                or model.sharded_apply_factory is None):
            raise ValueError(f"mesh has model_parallelism={n_model} / "
                             f"expert_parallelism={n_expert} but model "
                             f"{model.name!r} is not tensor-/expert-parallel "
                             "capable")
        tp_ax = model_ax if n_model > 1 else None
        ep_ax = topo.expert_axis if n_expert > 1 else None
        pspec: Any = params_partition_specs(model, cfg, topo)
        tp_apply = model.sharded_apply_factory(None, tp_ax, ep_ax)

        def run(params, images):
            return tp_apply(params, images, None)
    else:
        # engine-derived per-leaf tree (all P() on a pure-DP mesh) —
        # same derivation as the train step, one source of truth
        pspec = params_partition_specs(model, cfg, topo)

        def run(params, images):
            return model.apply(params, images, train=False)

    def shard_fn(params, batch):
        logits = run(params, batch["image"])
        correct, loss_sum, weight = model.eval_metrics(
            logits, batch["label"], batch["weight"])
        return (lax.psum(correct, axis), lax.psum(loss_sum, axis),
                lax.psum(weight, axis))

    sharded = mesh_lib.shard_map(
        shard_fn, mesh=topo.mesh,
        in_specs=(pspec, P(axis)),
        out_specs=(P(), P(), P()))
    return jax.jit(sharded)
