"""Masked-mean cross-replica reduction — the framework's core op.

Replaces the reference's entire parameter-server aggregation stack:
PS-hosted ``ConditionalAccumulator``s that average the first k
gradients and drop stale ones
(sync_replicas_optimizer_modified.py:287-306,363-378), per-worker token
queues (:199-206), and the chief's sync loop (:389-410).

TPU-native form: every replica contributes ``(grad · flag, flag)`` to a
single ``lax.psum`` over the mesh's replica axis; the aggregated
gradient is ``psum(grad·flag) / max(psum(flag), 1)``. Masked-out
replicas (backups, stragglers past deadline, outside the interval
window) contribute zeros — semantically identical to the PS dropping
their gradients, but with no queues, no staleness window, and the
reduction compiler-scheduled onto ICI all-reduce.

Staleness (SURVEY §7 "hard parts") is structurally impossible here:
SPMD replicas are in lockstep, so a masked-out step-t gradient simply
never enters any accumulator that step t+1 could read.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax


@jax.named_scope("aggregate")
def contribution_scale(flag: jax.Array,
                       axis_name: str) -> tuple[jax.Array, jax.Array]:
    """(scale, num_contributors): pre-multiplying each replica's
    contribution by ``scale = flag / max(psum(flag), 1)`` makes any
    subsequent cross-replica SUM the masked mean directly — one
    elementwise pass, shared by the all-reduce path below and the
    ZeRO-1 reduce-scatter path (parallel/api.py), so the two
    disciplines cannot drift in masking semantics."""
    flag = flag.astype(jnp.float32)
    num = lax.psum(flag, axis_name)
    return flag / jnp.maximum(num, 1.0), num


@jax.named_scope("aggregate")
def masked_mean_psum(tree: Any, flag: jax.Array, axis_name: str) -> tuple[Any, jax.Array]:
    """Cross-replica masked mean of a pytree.

    Args:
      tree: per-replica pytree (e.g. gradients), inside shard_map.
      flag: scalar 0/1 (or fractional weight) — this replica's
        contribution mask.
      axis_name: mesh axis to reduce over.

    Returns:
      (mean_tree, num_contributors): the masked mean — identical on all
      replicas — and ``psum(flag)``. If no replica contributes, the mean
      is all-zeros (the update becomes a no-op, mirroring a PS step with
      an empty accumulator never firing).
    """
    # One elementwise pass per leaf: pre-scale by the SCALAR flag/denom
    # so psum produces the mean directly (scaling after the psum would
    # spend a second full-size HBM pass per leaf — a measured tax on small
    # steps: the harness removed at PR 48, BENCH_r04/r05.json in history).
    scale, num = contribution_scale(flag, axis_name)
    mean = jax.tree.map(
        lambda g: lax.psum(g * scale.astype(g.dtype), axis_name), tree)
    return mean, num


