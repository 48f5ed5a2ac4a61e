"""Post-training quantization at checkpoint-publish time.

The serving tier runs inference only — none of the training-precision
guarantees apply to a predict pass, and mixed/low precision is the
single largest per-chip inference lever on TPUs (arXiv:1909.09756).
This module extends the storage-vs-compute dtype axis ``PrecisionConfig``
opened for training (PR 10, following arXiv:2004.13336's treatment of
storage dtype as an independent axis) to the SERVING side:

* **int8 tier** — per-channel symmetric int8 weights: every float
  param leaf with ndim ≥ 2 is quantized along its LAST axis (the
  output-channel axis for both HWIO conv kernels and ``[in, out]``
  dense kernels in this repo) as ``q = round(w / scale)`` with
  ``scale = amax(|w|, per-channel) / 127`` kept in float32; 1-D
  leaves (biases, norm scales) stay float32 — quantizing them buys
  nothing and costs parity, per the standard PTQ recipe. At serve
  time the int8 leaves live on-device (≈4× less weight HBM) and the
  predict function dequantizes them INSIDE the jitted graph — the
  per-channel rescale is a broadcast multiply XLA fuses into the
  matmul/conv operand pipeline (scale fusion), so no fp32 weight copy
  is ever resident. Activations keep the model's compute dtype, with
  one exception: the network INPUT — the one activation tensor every
  model family exposes without a per-family graph rewrite — is
  round-tripped through a per-tensor DYNAMIC int8 quantization
  (scale = amax(|x|)/127 computed in-graph per batch) when it is a
  float tensor, so the tier's precision claim covers the input edge
  too; integer token inputs pass through untouched.

* **bf16 tier** — a straight bfloat16 cast of the float leaves: the
  cheap middle tier (2× less weight HBM, MXU-native matmuls via the
  ``effective_model_config`` compute-dtype seam on the serving side).

* **Calibration** — at publish time the pass runs a held-out
  (test-split) batch through the fp32 graph and every tier's graph,
  records the observed input activation range and the per-tier top-1
  agreement in the sidecar metadata, and REFUSES to publish a tier
  whose agreement drops more than ``quant.parity_epsilon`` below the
  full-precision predictions — a publish-time guard so speed never
  silently buys wrongness (the serving replica then falls back to
  fp32 for that publish). The sidecar itself is written through
  ``train/checkpoint.py``'s atomic-write + sha256 machinery, so a torn
  sidecar is refused by digest verification exactly like a torn
  checkpoint.

The full-precision artifact is BYTE-UNCHANGED by all of this — the
sidecar is additive, pinned by the cross-knob digest test.
"""

from __future__ import annotations

import hashlib
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from ..core.log import get_logger

logger = get_logger("quant")

# int8 symmetric range: ±127 (not −128) so the scale maps amax exactly
# and negation is closed — the standard symmetric-PTQ convention.
_QMAX = 127.0


def _is_quantizable(a: np.ndarray) -> bool:
    """Per-channel int8 applies to float weight MATRICES/KERNELS
    (ndim ≥ 2); 1-D floats (biases, norm scales) and integer leaves
    pass through in their storage dtype."""
    return (isinstance(a, np.ndarray)
            and np.issubdtype(a.dtype, np.floating) and a.ndim >= 2)


def quantize_leaf_int8(w: np.ndarray) -> dict[str, np.ndarray]:
    """One float leaf → ``{"q": int8, "scale": float32}`` with the
    scale per LAST-axis channel (kept broadcast-shaped so the
    dequantize is one multiply). An all-zero channel gets scale 1.0 —
    its int8 zeros dequantize to exact zeros either way."""
    w = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)),
                    keepdims=True)
    scale = np.where(absmax > 0, absmax / _QMAX, 1.0).astype(np.float32)
    q = np.clip(np.round(w / scale), -_QMAX, _QMAX).astype(np.int8)
    return {"q": q, "scale": scale}


def quantize_tree_int8(params_sd: Any) -> Any:
    """A state-dict-shaped params tree → the int8 tier: quantizable
    leaves become ``{"q", "scale"}`` pairs, the rest stay float32 (or
    their integer storage dtype) as-is."""
    def leaf(a):
        a = np.asarray(a)
        if _is_quantizable(a):
            return quantize_leaf_int8(a)
        if np.issubdtype(a.dtype, np.floating):
            return a.astype(np.float32)
        return a
    return jax.tree.map(leaf, params_sd)


def cast_tree_bf16(params_sd: Any) -> Any:
    """A state-dict-shaped params tree → the bf16 tier (float leaves
    cast; integer leaves untouched)."""
    import ml_dtypes

    def leaf(a):
        a = np.asarray(a)
        if np.issubdtype(a.dtype, np.floating):
            return a.astype(ml_dtypes.bfloat16)
        return a
    return jax.tree.map(leaf, params_sd)


def _is_qpair(node: Any) -> bool:
    return (isinstance(node, dict) and set(node) == {"q", "scale"})


def dequantize_tree_int8(qtree: Any, dtype=jnp.float32) -> Any:
    """The int8 tier back to a float state-dict tree. jnp-traceable:
    the serving predict calls this INSIDE jit, so the per-channel
    rescale lowers next to its consuming matmul (scale fusion) and the
    int8 leaves are what stays resident on device."""
    def leaf(node):
        if _is_qpair(node):
            return node["q"].astype(dtype) * node["scale"].astype(dtype)
        return node
    return jax.tree.map(leaf, qtree, is_leaf=_is_qpair)


def dynamic_input_fake_quant(x: jax.Array) -> jax.Array:
    """Per-tensor DYNAMIC int8 round-trip of a float activation
    tensor: scale = amax(|x|)/127 computed in-graph for THIS batch, x
    rounded onto that grid and dequantized — the input edge of the
    int8 tier's precision claim, with no calibration constant to go
    stale (out-of-calibration inputs rescale instead of clipping)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / _QMAX
    return jnp.clip(jnp.round(x / scale), -_QMAX, _QMAX) * scale


def tier_param_bytes(tree: Any) -> int:
    """Resident weight bytes of a tier tree (the memory claim the
    sidecar's meta records; tests/test_quant.py bounds it)."""
    return sum(np.asarray(l).nbytes for l in jax.tree.leaves(tree))


def tree_params_digest(params_sd: Any) -> str:
    """sha256 over a host state-dict params tree — the 'source digest'
    the sidecar meta records, computed with the SAME canonical walk as
    ``train/checkpoint.py``'s artifact digests so it equals
    ``checkpoint_params_digest`` of the artifact the pass rode along
    with (single-file layout)."""
    from ..train.checkpoint import _digest_tree
    h = hashlib.sha256()
    _digest_tree(params_sd, h)
    return h.hexdigest()


# ---------------------------------------------------------------------------
# parity: the accuracy oracle shared by calibration and tests
# ---------------------------------------------------------------------------

def build_tier_predict(model, template_params: Any,
                       tier: str) -> Callable[[Any, Any], Any]:
    """The per-tier predict function (UNjitted; callers jit): takes
    the tier's stored tree (state-dict shaped) + an input batch,
    reconstructs the model's param pytree via ``from_state_dict``
    (structure only — template values unused), and returns
    ``model.predictions`` probabilities. ``fp32`` consumes the plain
    float state dict; ``bf16`` applies the bf16-stored leaves
    directly; ``int8`` dequantizes in-graph and fake-quants a float
    input dynamically."""
    input_is_float = np.issubdtype(np.dtype(model.input_dtype),
                                   np.floating)

    def predict(tree, x):
        if tier == "int8":
            if input_is_float:
                x = dynamic_input_fake_quant(x)
            tree = dequantize_tree_int8(tree)
        params = serialization.from_state_dict(template_params, tree)
        return model.predictions(model.apply(params, x, train=False))
    return predict


def parity_report(probs_ref: np.ndarray, probs_tier: np.ndarray,
                  labels: np.ndarray | None = None) -> dict[str, Any]:
    """Top-1 parity between a reference and a tier prediction set:
    ``agreement`` (fraction of examples whose argmax matches — the
    quantity ``quant.parity_epsilon`` gates) plus per-arm accuracy
    when labels are given."""
    top_ref = np.argmax(probs_ref, axis=-1)
    top_tier = np.argmax(probs_tier, axis=-1)
    out: dict[str, Any] = {
        "examples": int(top_ref.shape[0]),
        "agreement": round(float(np.mean(top_ref == top_tier)), 4),
        "max_abs_prob_delta": round(
            float(np.max(np.abs(probs_ref - probs_tier))), 5),
    }
    if labels is not None:
        labels = np.asarray(labels)
        out["top1_ref"] = round(float(np.mean(top_ref == labels)), 4)
        out["top1_tier"] = round(float(np.mean(top_tier == labels)), 4)
    return out


def calibrate_tiers(model, template_params: Any, params_sd: Any,
                    tiers: dict[str, Any], calib_inputs: np.ndarray,
                    calib_labels: np.ndarray | None = None,
                    predict_cache: dict | None = None) -> dict[str, Any]:
    """Run the held-out calibration batch through the fp32 graph and
    every tier's graph; returns ``{tier: parity_report, "input_amax":
    observed range}``. ``predict_cache`` (tier → jitted fn) amortizes
    the compiles across publishes."""
    cache = predict_cache if predict_cache is not None else {}

    def fn(tier):
        if tier not in cache:
            cache[tier] = jax.jit(
                build_tier_predict(model, template_params, tier))
        return cache[tier]

    x = calib_inputs
    ref = np.asarray(jax.device_get(fn("fp32")(params_sd, x)))
    out: dict[str, Any] = {"examples": int(x.shape[0])}
    if np.issubdtype(np.asarray(x).dtype, np.floating):
        out["input_amax"] = round(float(np.max(np.abs(x))), 6)
    for tier, tree in tiers.items():
        probs = np.asarray(jax.device_get(fn(tier)(tree, x)))
        out[tier] = parity_report(ref, probs, calib_labels)
    return out


# ---------------------------------------------------------------------------
# the publish-time pass
# ---------------------------------------------------------------------------

class QuantPublisher:
    """The checkpoint-publish hook (``quant.publish_tiers``): quantize
    the just-saved canonical params and write the sidecar next to the
    artifact. Thread-agnostic — the Trainer calls :meth:`publish`
    inline after a synchronous save, or hands it to the
    ``AsyncCheckpointer`` worker as the post-write callback (so on the
    async path the whole pass stays off the step loop's critical
    path). Per-tier jitted predicts are built once and reused across
    publishes."""

    def __init__(self, model, cfg, template_params: Any,
                 calib_inputs: np.ndarray | None,
                 calib_labels: np.ndarray | None = None):
        self.model = model
        self.qcfg = cfg.quant
        self.tiers = self.qcfg.resolved_publish_tiers()  # validates
        self.template_params = template_params
        n = self.qcfg.calibration_examples
        self.calib_inputs = (None if calib_inputs is None or n <= 0
                             else np.asarray(calib_inputs[:n]))
        self.calib_labels = (None if calib_labels is None or n <= 0
                             else np.asarray(calib_labels[:n]))
        self._predict_cache: dict[str, Any] = {}
        self.published = 0     # sidecars written (telemetry/tests)
        self.refused: list[tuple[int, str]] = []  # (step, tier) parity refusals

    def _params_from_snapshot(self, state: Any) -> Any | None:
        """The canonical params state dict out of whatever the save
        path holds: a ``("full", state_dict)`` snapshot (the async
        worker's shape), or a live/host state with a ``params``
        field. None for the per-host sharded layout — like the
        artifact digests, the pass needs the whole params here."""
        if (isinstance(state, tuple) and state
                and state[0] in ("full", "sharded")):
            if state[0] != "full":
                return None
            sd = state[1]
            return sd.get("params") if isinstance(sd, dict) else None
        sd = serialization.to_state_dict(state)
        if isinstance(sd, dict) and "params" in sd:
            return jax.tree.map(lambda a: np.asarray(jax.device_get(a)),
                                sd["params"])
        return None

    def publish(self, train_dir, state: Any, step: int) -> dict | None:
        """Quantize + calibrate + write the sidecar for ``step``.
        Returns the sidecar meta, or None when nothing was published
        (no tiers configured, sharded layout, or every tier refused).
        Never raises into the save path — a failed sidecar must not
        cost a checkpoint (logged instead; the serving tier falls back
        to fp32)."""
        if not self.tiers:
            return None
        try:
            return self._publish(train_dir, state, step)
        except Exception as e:  # additive artifact: degrade, don't fail
            logger.warning("quant sidecar publish for step=%d failed "
                           "(%s: %s) — serving falls back to fp32",
                           step, type(e).__name__, e)
            return None

    def _publish(self, train_dir, state: Any, step: int) -> dict | None:
        from ..train import checkpoint as ckpt
        params_sd = self._params_from_snapshot(state)
        if params_sd is None:
            logger.warning("quant tiers skipped at step=%d: per-host "
                           "sharded layout (quantize from a restored "
                           "template instead)", step)
            return None
        src_digest = tree_params_digest(params_sd)
        try:
            # idempotent per (step, source digest, tier set): the
            # final save at max_steps re-triggers the cadence step's
            # publish when the async writer drained between the two
            # enqueues — identical params must not pay the pass (or
            # bump the telemetry) twice. A different digest (same-step
            # re-save after a rollback) OR a tier the existing sidecar
            # lacks (re-publish under a widened quant.publish_tiers)
            # still republishes.
            existing = ckpt.read_quant_sidecar(train_dir, step)
            meta = existing.get("meta") or {}
            if (meta.get("source_params_digest") == src_digest
                    and set(self.tiers) <= set(meta.get("tiers") or ())):
                logger.info("quant sidecar step=%d already published "
                            "for this source digest + tiers; skipping",
                            step)
                return meta
        except (OSError, ValueError, KeyError):
            pass  # absent/torn sidecar: publish (re-)writes it
        t0 = time.perf_counter()
        built: dict[str, Any] = {}
        for tier in self.tiers:
            built[tier] = (quantize_tree_int8(params_sd) if tier == "int8"
                           else cast_tree_bf16(params_sd))
        meta: dict[str, Any] = {
            "step": step,
            "tiers": list(built),
            "source_params_digest": src_digest,
            "parity_epsilon": self.qcfg.parity_epsilon,
            "param_bytes": {"fp32": tier_param_bytes(params_sd),
                            **{t: tier_param_bytes(tr)
                               for t, tr in built.items()}},
        }
        if self.calib_inputs is not None:
            calib = calibrate_tiers(self.model, self.template_params,
                                    params_sd, built, self.calib_inputs,
                                    self.calib_labels,
                                    predict_cache=self._predict_cache)
            meta["calibration"] = calib
            floor = 1.0 - self.qcfg.parity_epsilon
            for tier in list(built):
                agreement = calib[tier]["agreement"]
                if agreement < floor:
                    # speed must never silently buy wrongness: the
                    # tier is NOT published; the serving replica's
                    # sidecar preference falls back to fp32
                    logger.warning(
                        "quant tier %s REFUSED at step=%d: calibration "
                        "top-1 agreement %.4f < %.4f (epsilon %.3f)",
                        tier, step, agreement, floor,
                        self.qcfg.parity_epsilon)
                    self.refused.append((step, tier))
                    del built[tier]
            meta["tiers"] = list(built)
        if not built:
            return None
        meta["publish_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
        ckpt.write_quant_sidecar(train_dir, step, built, meta)
        self.published += 1
        logger.info("published quant sidecar step=%d tiers=%s (%.0f ms)",
                    step, ",".join(built), meta["publish_ms"])
        return meta
