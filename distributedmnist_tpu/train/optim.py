"""Optimizer registry: the large-batch update rules as pure per-leaf
functions.

The reference hardwires ``tf.train.GradientDescentOptimizer``
(src/distributed_train.py:176); this registry opens that into the
MLPerf-on-TPU-pods large-batch recipe (arXiv:1909.09756): plain /
momentum SGD plus the layer-wise adaptive trust-ratio optimizers —
LARS (arXiv:1708.03888) and LAMB (arXiv:1904.00962).

Design constraints, in order:

1. **Per-leaf purity.** Every optimizer is one
   ``update_leaf(p, g, slots, lr, t, norm_reduce, adapt)`` function
   over same-shaped arrays — a FULL logical leaf on the replicated
   update path, or this replica's 1/n ZeRO-1 *chunk* on the sharded
   path (parallel/api.py ``_zero1_update``). The only cross-element
   quantity the trust-ratio math needs is a sum of squares, so the
   caller supplies ``norm_reduce`` — identity for full leaves, a
   ``lax.psum`` over the replica axis for chunks (zero padding
   contributes 0 to a sum of squares, so chunked norms are exact).
   One update rule, both weight-update disciplines.
2. **Float32 math.** Inputs are cast to float32 on entry and the new
   param value is cast back to the leaf's storage dtype on exit, so a
   bf16 param leaf (precision.param_dtype without master weights)
   still takes its update in full precision. Moment slots are always
   float32 (``slot_dtype``).
3. **Layer-wise semantics per the papers.** The trust ratio and weight
   decay apply only to leaves with ``adapt=True`` — the caller passes
   the leaf's logical rank, and 1-D leaves (biases, norm scales) skip
   adaptation, the standard LARS/LAMB exclusion list.

Slot layout: ``None`` (stateless sgd), a params-shaped tree (one-slot
optimizers — byte-identical to the historical momentum layout, so
existing momentum checkpoints and their canonical digests are
untouched), or ``{"m": tree, "v": tree}`` (LAMB). The ``{"m", "v"}``
top-level key set is reserved for the two-slot layout; no registered
model's param tree uses it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from ..core.config import ConfigError, OptimConfig

OPTIMIZER_NAMES = ("sgd", "momentum", "lars", "lamb")

# update_leaf(p, g, slots, lr, t, norm_reduce, adapt) -> (new_p, new_slots)
#   p, g        same-shaped arrays (full leaf or ZeRO-1 chunk)
#   slots       tuple of moment arrays, same shape as p (len == num_slots)
#   lr          scalar learning rate
#   t           float32 applied-update count AFTER this apply (>= 1) —
#               LAMB bias correction; ignored by the others
#   norm_reduce scalar -> scalar: completes a partial sum-of-squares to
#               the full-leaf value (identity, or psum over axes)
#   adapt       static bool: apply weight decay + trust ratio (ndim > 1)
UpdateLeaf = Callable[..., tuple[jax.Array, tuple]]


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """One registry entry: the canonical state kind, how many moment
    slots a leaf carries, and the pure per-leaf update rule."""

    kind: str
    num_slots: int
    update_leaf: UpdateLeaf


def validate(ocfg: OptimConfig) -> None:
    """Typed validation for the optimizer section — raised at build
    time (make_optimizer) so every consumer (Trainer, benchmark, tests)
    fails loudly before tracing anything."""
    if ocfg.name not in OPTIMIZER_NAMES:
        raise ConfigError(
            f"unknown optimizer {ocfg.name!r}; valid: {list(OPTIMIZER_NAMES)}")
    if ocfg.name in ("lars", "lamb") and ocfg.momentum != 0.0:
        raise ConfigError(
            f"optim.momentum={ocfg.momentum} combined with "
            f"optim.name={ocfg.name!r}: trust-ratio optimizers own their "
            "momentum term (optim.beta1); set optim.momentum=0")
    if ocfg.name == "momentum" and ocfg.momentum <= 0.0:
        raise ConfigError(
            f"optim.name='momentum' with optim.momentum={ocfg.momentum}: "
            "the explicit momentum optimizer needs a positive coefficient "
            "(heavyball at 0 is exactly plain sgd — name that instead)")
    if ocfg.schedule not in ("exponential", "polynomial"):
        raise ConfigError(
            f"unknown optim.schedule {ocfg.schedule!r}; valid: "
            "['exponential', 'polynomial']")


def opt_state_kind(ocfg: OptimConfig) -> str:
    """The canonical optimizer-STATE identity a checkpoint carries:
    ``none`` (stateless), ``momentum``, ``lars`` or ``lamb``. ``sgd``
    with ``momentum > 0`` is heavyball momentum (the knob's historical
    meaning), so its state kind is ``momentum``. This is what the
    cross-optimizer restore guard compares (parallel/api.py
    ``restore_for_topology``): LARS and momentum state share a tree
    shape but not semantics, so kinds differ even when layouts match."""
    validate(ocfg)
    if ocfg.name == "sgd":
        return "momentum" if ocfg.momentum > 0.0 else "none"
    return ocfg.name


def saved_opt_state_kind(optim_dict: dict | None) -> str | None:
    """``opt_state_kind`` over a checkpoint's saved ``config.optim``
    dict — tolerant of foreign/extra keys (an older or newer schema)
    and of invalid saved combinations (the identity is still the name).
    None when the dict carries nothing usable."""
    if not isinstance(optim_dict, dict):
        return None
    name = optim_dict.get("name", "sgd")
    if name == "sgd":
        return "momentum" if optim_dict.get("momentum", 0.0) else "none"
    return str(name)


def slot_dtype(param_dtype) -> Any:
    """Moment-slot dtype for a param leaf: float32 for any float param
    (a bf16 moment would quantize the accumulation the slot exists to
    carry), the param dtype otherwise."""
    return (jnp.float32 if jnp.issubdtype(jnp.dtype(param_dtype), jnp.floating)
            else jnp.dtype(param_dtype))


# ---------------------------------------------------------------------------
# slot-tree plumbing (opt state = None | tree | {"m": tree, "v": tree})
# ---------------------------------------------------------------------------

_SLOT_KEYS = frozenset({"m", "v"})


def is_slot_dict(opt_state: Any) -> bool:
    """True for the two-slot ``{"m": tree, "v": tree}`` layout."""
    return isinstance(opt_state, dict) and set(opt_state) == _SLOT_KEYS


def map_slots(fn: Callable[[Any], Any], opt_state: Any) -> Any:
    """Apply ``fn`` to each params-shaped slot tree of an optimizer
    state, preserving the layout. The structural twin of the per-slot
    ZeRO-1 pack/unpack/spec derivations — callers that cannot see the
    Optimizer (e.g. ``canonical_save_state``) detect the two-slot
    layout by its reserved key set."""
    if opt_state is None:
        return None
    if is_slot_dict(opt_state):
        return {k: fn(tree) for k, tree in opt_state.items()}
    return fn(opt_state)


def slot_trees(opt: Optimizer, opt_state: Any) -> list:
    """The optimizer state as an ordered list of params-shaped trees
    (length ``opt.num_slots``)."""
    if opt.num_slots == 0:
        return []
    if opt.num_slots == 1:
        return [opt_state]
    return [opt_state["m"], opt_state["v"]]


def from_slot_trees(opt: Optimizer, trees: Sequence) -> Any:
    if opt.num_slots == 0:
        return None
    if opt.num_slots == 1:
        return trees[0]
    return {"m": trees[0], "v": trees[1]}


def init_slots(opt: Optimizer, make_tree: Callable[[], Any]) -> Any:
    """Zeros-initialized optimizer state: ``make_tree()`` builds ONE
    params-shaped (or ZeRO-1-packed) float32 tree; called once per
    slot."""
    return from_slot_trees(opt, [make_tree() for _ in range(opt.num_slots)])


# ---------------------------------------------------------------------------
# the update rules
# ---------------------------------------------------------------------------

def _f32(x: jax.Array) -> jax.Array:
    return x.astype(jnp.float32)


def _norm(x32: jax.Array, norm_reduce) -> jax.Array:
    return jnp.sqrt(norm_reduce(jnp.sum(x32 * x32)))


def _sgd_leaf(p, g, slots, lr, t, norm_reduce, adapt):
    del slots, t, norm_reduce, adapt
    new_p = _f32(p) - lr * _f32(g)
    return new_p.astype(p.dtype), ()


def _make_momentum_leaf(mu: float) -> UpdateLeaf:
    def update(p, g, slots, lr, t, norm_reduce, adapt):
        del t, norm_reduce, adapt
        (b,) = slots
        nb = mu * _f32(b) + _f32(g)
        new_p = _f32(p) - lr * nb
        return new_p.astype(p.dtype), (nb.astype(b.dtype),)
    return update


def _make_lars_leaf(ocfg: OptimConfig) -> UpdateLeaf:
    mu, eta, wd = ocfg.beta1, ocfg.trust_coefficient, ocfg.weight_decay

    def update(p, g, slots, lr, t, norm_reduce, adapt):
        del t
        (b,) = slots
        p32, g32 = _f32(p), _f32(g)
        if adapt:
            gw = g32 + wd * p32
            w_norm = _norm(p32, norm_reduce)
            g_norm = _norm(gw, norm_reduce)
            # trust = eta·‖w‖/‖g + wd·w‖; 1 when either norm is 0
            # (fresh zero leaves must still move)
            trust = jnp.where((w_norm > 0.0) & (g_norm > 0.0),
                              eta * w_norm / jnp.maximum(g_norm, 1e-30), 1.0)
            gw = trust * gw
        else:
            gw = g32  # biases/norms: no decay, no adaptation
        nb = mu * _f32(b) + gw
        new_p = p32 - lr * nb
        return new_p.astype(p.dtype), (nb.astype(b.dtype),)
    return update


def _make_lamb_leaf(ocfg: OptimConfig) -> UpdateLeaf:
    b1, b2, eps, wd = ocfg.beta1, ocfg.beta2, ocfg.eps, ocfg.weight_decay

    def update(p, g, slots, lr, t, norm_reduce, adapt):
        m, v = slots
        p32, g32 = _f32(p), _f32(g)
        nm = b1 * _f32(m) + (1.0 - b1) * g32
        nv = b2 * _f32(v) + (1.0 - b2) * g32 * g32
        m_hat = nm / (1.0 - jnp.power(b1, t))
        v_hat = nv / (1.0 - jnp.power(b2, t))
        u = m_hat / (jnp.sqrt(v_hat) + eps)
        if adapt:
            u = u + wd * p32
            w_norm = _norm(p32, norm_reduce)
            u_norm = _norm(u, norm_reduce)
            ratio = jnp.where((w_norm > 0.0) & (u_norm > 0.0),
                              w_norm / jnp.maximum(u_norm, 1e-30), 1.0)
        else:
            ratio = 1.0
        new_p = p32 - lr * ratio * u
        return new_p.astype(p.dtype), (nm.astype(m.dtype), nv.astype(v.dtype))
    return update


def make_optimizer(ocfg: OptimConfig) -> Optimizer:
    """Resolve the config into a registry entry (validating it)."""
    kind = opt_state_kind(ocfg)
    if kind == "none":
        return Optimizer(kind="none", num_slots=0, update_leaf=_sgd_leaf)
    if kind == "momentum":
        return Optimizer(kind="momentum", num_slots=1,
                         update_leaf=_make_momentum_leaf(ocfg.momentum))
    if kind == "lars":
        return Optimizer(kind="lars", num_slots=1,
                         update_leaf=_make_lars_leaf(ocfg))
    return Optimizer(kind="lamb", num_slots=2,
                     update_leaf=_make_lamb_leaf(ocfg))
