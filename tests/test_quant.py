"""Quantized serving path: the publish-time PTQ pass, the sidecar
artifact contract (digest-verified, additive, byte-unchanged fp32
artifacts), the parity guard, and the serving-side tier machinery's
journal/invariant extensions."""

import json
from pathlib import Path

import numpy as np
import pytest

from conftest import base_config


# ---------------------------------------------------------------------------
# the quantizer itself
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_int8_per_channel_quantization_math():
    from distributedmnist_tpu.quant.ptq import (dequantize_tree_int8,
                                                quantize_leaf_int8,
                                                quantize_tree_int8)
    rng = np.random.default_rng(0)
    w = rng.normal(size=(5, 5, 3, 8)).astype(np.float32)
    got = quantize_leaf_int8(w)
    assert got["q"].dtype == np.int8 and got["q"].shape == w.shape
    # per LAST-axis channel: one scale per output channel
    assert got["scale"].shape == (1, 1, 1, 8)
    # straight-line reference for channel 0
    absmax = np.abs(w[..., 0]).max()
    assert np.isclose(got["scale"][0, 0, 0, 0], absmax / 127.0)
    # dequantize error bounded by half a quantization step per element
    deq = np.asarray(dequantize_tree_int8(got))
    assert np.max(np.abs(deq - w) / got["scale"]) <= 0.5 + 1e-6

    tree = {"fc": {"w": w[0, 0], "b": np.ones(8, np.float32)},
            "emb": np.arange(4, dtype=np.int32)}
    q = quantize_tree_int8(tree)
    assert set(q["fc"]["w"]) == {"q", "scale"}     # 2-D: quantized
    assert q["fc"]["b"].dtype == np.float32        # 1-D float: passthrough
    assert q["emb"].dtype == np.int32              # integer: untouched
    back = dequantize_tree_int8(q)
    assert np.asarray(back["fc"]["b"]).dtype == np.float32
    assert np.allclose(np.asarray(back["fc"]["w"]), w[0, 0], atol=2e-2)


@pytest.mark.tier1
def test_bf16_tier_cast_and_input_fake_quant():
    import ml_dtypes

    from distributedmnist_tpu.quant.ptq import (cast_tree_bf16,
                                                dynamic_input_fake_quant)
    tree = {"w": np.ones((2, 2), np.float32), "ids": np.zeros(2, np.int32)}
    b = cast_tree_bf16(tree)
    assert b["w"].dtype == ml_dtypes.bfloat16 and b["ids"].dtype == np.int32
    x = np.linspace(-0.5, 0.5, 64, dtype=np.float32)
    xq = np.asarray(dynamic_input_fake_quant(x))
    # round-trip lands on the per-tensor int8 grid: ≤ half-step error
    assert np.max(np.abs(xq - x)) <= 0.5 / 127 / 2 + 1e-6


@pytest.mark.tier1
def test_publish_tier_validation_is_typed():
    from distributedmnist_tpu.core.config import ConfigError, QuantConfig
    assert QuantConfig().resolved_publish_tiers() == ()
    assert QuantConfig(
        publish_tiers="int8,bf16").resolved_publish_tiers() == ("int8",
                                                                "bf16")
    with pytest.raises(ConfigError, match="int4.*valid tiers"):
        QuantConfig(publish_tiers="int4").resolved_publish_tiers()
    # fp32 is the artifact, never a sidecar tier
    with pytest.raises(ConfigError, match="fp32"):
        QuantConfig(publish_tiers="fp32").resolved_publish_tiers()


@pytest.mark.tier1
def test_serve_compute_dtype_through_effective_model_config():
    from distributedmnist_tpu.core.config import (ConfigError,
                                                  ExperimentConfig,
                                                  effective_model_config)
    cfg = ExperimentConfig.from_dict({
        "model": {"compute_dtype": "float32"},
        "precision": {"compute_dtype": "bfloat16"},
        "serve": {"compute_dtype": "float16"}})
    # training-side resolution ignores the serve section
    assert effective_model_config(cfg).compute_dtype == "bfloat16"
    # serving-side: serve.compute_dtype wins, then precision, then model
    assert effective_model_config(cfg, serving=True).compute_dtype == \
        "float16"
    cfg2 = cfg.override({"serve.compute_dtype": ""})
    assert effective_model_config(cfg2, serving=True).compute_dtype == \
        "bfloat16"
    with pytest.raises(ConfigError, match="serve.compute_dtype.*valid"):
        effective_model_config(
            cfg.override({"serve.compute_dtype": "float8_e4m3"}),
            serving=True)
    with pytest.raises(ConfigError, match="precision.compute_dtype"):
        effective_model_config(
            cfg2.override({"precision.compute_dtype": "int7"}))


@pytest.mark.tier1
def test_serve_precision_tier_validation_is_typed(tmp_path):
    from distributedmnist_tpu.core.config import ConfigError, ServeConfig
    from distributedmnist_tpu.servesvc.server import ServingReplica
    with pytest.raises(ConfigError, match="precision_tier.*valid tiers"):
        ServingReplica(tmp_path, serve_dir=tmp_path / "r",
                       scfg=ServeConfig(precision_tier="int4"),
                       cfg=base_config())


# ---------------------------------------------------------------------------
# sidecar artifact contract
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_quant_sidecar_write_read_digest_and_torn(tmp_path):
    from distributedmnist_tpu.train import checkpoint as ckpt
    tiers = {"int8": {"w": {"q": np.ones((2, 2), np.int8),
                            "scale": np.ones((1, 2), np.float32)}}}
    path = ckpt.write_quant_sidecar(tmp_path, 7, tiers,
                                    {"step": 7, "tiers": ["int8"]})
    assert path.name == "ckpt-00000007.quant.msgpack"
    assert ckpt.quant_sidecar_digest(tmp_path, 7)
    got = ckpt.read_quant_sidecar(tmp_path, 7)
    assert got["meta"]["step"] == 7
    assert got["tiers"]["int8"]["w"]["q"].dtype == np.int8
    # a sidecar never makes a step loadable on its own
    assert ckpt.loadable_steps(tmp_path) == []
    assert ckpt.latest_checkpoint_step(tmp_path) is None
    # torn bytes against the intact digest sidecar: refused, typed
    data = path.read_bytes()
    path.write_bytes(data[: len(data) // 2])
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.read_quant_sidecar(tmp_path, 7)
    with pytest.raises(FileNotFoundError):
        ckpt.read_quant_sidecar(tmp_path, 8)


@pytest.mark.tier1
def test_enospc_sidecar_publish_never_costs_a_checkpoint(tmp_path):
    """ISSUE 20 pin: the quant sidecar is an ADDITIVE artifact — a
    disk that fills up mid-publish (storage-shim ENOSPC across the
    whole retry budget) is logged by the publisher and journaled by
    the injector, the fp32 checkpoint stays durable and loadable, and
    a serving replica configured for the tier falls back to fp32 with
    a journaled ``follow_quant_sidecar_fallback`` — never a crash,
    never a checkpoint failure."""
    from distributedmnist_tpu.quant.ptq import QuantPublisher
    from distributedmnist_tpu.train import checkpoint as ckpt
    from distributedmnist_tpu.train import storage
    state = {"params": {"w": np.full((4, 3), 3.0, np.float32)},
             "step": np.int32(3)}
    ckpt.save_checkpoint(tmp_path, state, 3)
    state_sd, _ = ckpt._checkpoint_state_dict(tmp_path, 3)
    journal = tmp_path / "storage_faults.jsonl"
    storage.arm_faults(0, [{"kind": "enospc_after_bytes", "bytes": 0,
                            "match": ".quant.",
                            "times": ckpt._IO_ATTEMPTS}], journal)
    try:
        cfg = base_config(quant={"publish_tiers": "int8",
                                 "calibration_examples": 0})
        pub = QuantPublisher(None, cfg, None, calib_inputs=None)
        meta = pub.publish(tmp_path, ("full", state_sd), 3)
        assert meta is None and pub.published == 0  # logged, swallowed
        assert not ckpt.quant_sidecar_path(tmp_path, 3).exists()
        # the fp32 artifact the save already landed is untouched
        ckpt.verify_artifact(tmp_path / "ckpt-00000003.msgpack")
        got = ckpt.restore_checkpoint(tmp_path, state)
        assert got is not None and got[2] == 3
        # every firing journaled — invariant 14's license survives
        from distributedmnist_tpu.obsv.report import load_jsonl
        recs = load_jsonl(journal)
        assert [r["action"] for r in recs] == \
            ["disk_enospc"] * ckpt._IO_ATTEMPTS
        assert all(".quant." in r["path"] for r in recs)
    finally:
        storage.clear_faults()
    # the serving half: tier configured, sidecar absent → journaled
    # fp32 fallback, not an error
    from distributedmnist_tpu.core.config import ServeConfig
    from distributedmnist_tpu.servesvc.server import ServingReplica
    r = ServingReplica(tmp_path, serve_dir=tmp_path / "replica",
                       scfg=ServeConfig(precision_tier="int8"),
                       cfg=base_config())
    assert r._read_quant_tier(3, 0.0) is None
    r._serve_log.close()
    from distributedmnist_tpu.obsv.report import load_jsonl as _lj
    swaps = _lj(tmp_path / "replica" / "serve_log.jsonl")
    fb = [x for x in swaps
          if x.get("action") == "follow_quant_sidecar_fallback"]
    assert len(fb) == 1 and fb[0]["reason"] == "sidecar_absent"
    assert fb[0]["step"] == 3 and fb[0]["tier"] == "int8"


# ---------------------------------------------------------------------------
# publish-time pass on a real Trainer (shared run: publish on)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def quant_run(tmp_path_factory, synthetic_datasets):
    """One 20-step run publishing int8+bf16 sidecars at steps 10/20,
    plus a QUANT-LESS same-seed twin — the byte-unchanged-artifact
    comparison baseline."""
    from distributedmnist_tpu.train.loop import Trainer
    with_q = tmp_path_factory.mktemp("with_quant")
    without_q = tmp_path_factory.mktemp("without_quant")
    mk = lambda d, tiers: base_config(  # noqa: E731
        train={"train_dir": str(d), "max_steps": 20,
               "log_every_steps": 10, "save_interval_steps": 10},
        quant={"publish_tiers": tiers, "calibration_examples": 64})
    t = Trainer(mk(with_q, "int8,bf16"), datasets=synthetic_datasets)
    t.run()
    Trainer(mk(without_q, ""), datasets=synthetic_datasets).run()
    return {"with": with_q, "without": without_q,
            "cfg": mk(with_q, "int8,bf16"),
            "published": t._quant_publisher.published}


def test_fp32_artifact_byte_unchanged_by_quant_pass(quant_run):
    """The acceptance pin: sidecars are ADDITIVE. (a) The quant-less
    same-seed twin trains BITWISE-identical params (publishing never
    touches the train state); (b) the with-quant artifacts still pass
    their own digest verification AFTER the sidecars were published
    (publishing never rewrote artifact bytes — the digest sidecar was
    written before the pass ran); (c) re-running the pass over an
    existing dir leaves the artifact's bytes byte-identical."""
    import hashlib

    from distributedmnist_tpu.train import checkpoint as ckpt
    assert quant_run["published"] == 2  # steps 10 and 20
    for step in (10, 20):
        # (a) bitwise params parity across the publish knob
        pw = ckpt.checkpoint_params_digest(quant_run["with"], step)
        po = ckpt.checkpoint_params_digest(quant_run["without"], step)
        assert pw[0] == po[0], f"step {step} params diverged"
        # (b) digest verification still passes post-publish
        ckpt.verify_artifact(quant_run["with"]
                             / f"ckpt-{step:08d}.msgpack")
        assert (quant_run["with"]
                / f"ckpt-{step:08d}.quant.msgpack").exists()
        assert not (quant_run["without"]
                    / f"ckpt-{step:08d}.quant.msgpack").exists()
    # (c) the pass over an EXISTING dir: artifact bytes untouched
    artifact = quant_run["without"] / "ckpt-00000020.msgpack"
    before = hashlib.sha256(artifact.read_bytes()).hexdigest()
    from distributedmnist_tpu.quant.ptq import QuantPublisher
    state_sd, _ = ckpt._checkpoint_state_dict(quant_run["without"], 20)
    pub = QuantPublisher(None, quant_run["cfg"], None,
                         calib_inputs=None)  # no calibration: pure write
    meta = pub.publish(quant_run["without"], ("full", state_sd), 20)
    assert meta is not None and pub.published == 1
    assert hashlib.sha256(artifact.read_bytes()).hexdigest() == before
    # the sidecar's recorded source digest IS the artifact's canonical
    # params digest — a verifiable cross-artifact identity
    meta = ckpt.read_quant_sidecar(quant_run["with"], 20)["meta"]
    got = ckpt.checkpoint_params_digest(quant_run["with"], 20)
    assert meta["source_params_digest"] == got[0]


def test_quant_publish_idempotent_per_source_digest(quant_run):
    """Re-publishing a step whose sidecar already records the SAME
    source params digest is a skip, not a second pass — the final save
    at max_steps re-triggers the cadence step's publish whenever the
    async writer drained between the two enqueues, and the duplicate
    must not pay the quantize work, rewrite bytes, or bump the
    telemetry the tests gate on (the published==2 race this pins)."""
    import hashlib

    from distributedmnist_tpu.quant.ptq import QuantPublisher
    from distributedmnist_tpu.train import checkpoint as ckpt
    sidecar = quant_run["with"] / "ckpt-00000020.quant.msgpack"
    before = hashlib.sha256(sidecar.read_bytes()).hexdigest()
    state_sd, _ = ckpt._checkpoint_state_dict(quant_run["with"], 20)
    pub = QuantPublisher(None, quant_run["cfg"], None, calib_inputs=None)
    meta = pub.publish(quant_run["with"], ("full", state_sd), 20)
    assert meta is not None  # the existing sidecar's meta, returned
    assert pub.published == 0  # skipped — no second pass
    assert hashlib.sha256(sidecar.read_bytes()).hexdigest() == before


def test_cross_knob_restore_ignores_sidecars(quant_run, synthetic_datasets):
    """A dir full of sidecars restores into a quant-less config (and
    the restored step/params match) — the sidecar can never poison the
    training resume path."""
    from distributedmnist_tpu.train.loop import Trainer
    cfg = base_config(train={"train_dir": str(quant_run["with"]),
                             "max_steps": 20, "log_every_steps": 10,
                             "save_interval_steps": 0})
    t = Trainer(cfg, datasets=synthetic_datasets)  # resume=True default
    assert t._start_step == 20
    assert t._quant_publisher is None


def test_quant_sidecar_gc_with_step(quant_run, tmp_path,
                                    synthetic_datasets):
    """Sidecars garbage-collect with their step (keep=1 leaves only
    the newest step's artifact + sidecar families)."""
    from distributedmnist_tpu.train.loop import Trainer
    d = tmp_path / "gc"
    cfg = base_config(
        train={"train_dir": str(d), "max_steps": 20,
               "log_every_steps": 10, "save_interval_steps": 10,
               "keep_checkpoints": 1},
        quant={"publish_tiers": "int8", "calibration_examples": 0})
    Trainer(cfg, datasets=synthetic_datasets).run()
    steps = {int(p.name[5:13]) for p in d.glob("ckpt-*")}
    assert steps == {20}, sorted(p.name for p in d.glob("ckpt-*"))
    assert (d / "ckpt-00000020.quant.msgpack").exists()


def test_parity_refusal_blocks_publish(tmp_path, synthetic_datasets,
                                       monkeypatch):
    """A tier whose calibration agreement misses the epsilon floor is
    NOT published — speed never silently buys wrongness."""
    from distributedmnist_tpu.quant import ptq
    from distributedmnist_tpu.train import checkpoint as ckpt
    from distributedmnist_tpu.train.loop import Trainer

    def bad_calibration(model, template, params_sd, tiers, x, labels=None,
                        predict_cache=None):
        return {"examples": 4,
                **{t: {"agreement": 0.5, "examples": 4} for t in tiers}}

    monkeypatch.setattr(ptq, "calibrate_tiers", bad_calibration)
    d = tmp_path / "refused"
    cfg = base_config(
        train={"train_dir": str(d), "max_steps": 10,
               "log_every_steps": 5, "save_interval_steps": 0},
        quant={"publish_tiers": "int8", "calibration_examples": 8})
    t = Trainer(cfg, datasets=synthetic_datasets)
    t.run()
    assert (d / "ckpt-00000010.msgpack").exists()  # checkpoint fine
    assert t._quant_publisher.published == 0
    assert (10, "int8") in t._quant_publisher.refused
    with pytest.raises(FileNotFoundError):
        ckpt.read_quant_sidecar(d, 10)


@pytest.mark.parametrize("tier, resident", [("int8", 0.35), ("bf16", 0.55)])
def test_tier_predict_parity_on_eval_split(quant_run, synthetic_datasets,
                                           tier, resident):
    """The accuracy-parity oracle in unit form: the dequantize-in-graph
    predicts (the exact fns the replica serves) agree with fp32 top-1
    on the full eval split within the published epsilon. And the other
    half of the trade, from the same sidecar: the tier's resident
    weight bytes as a share of fp32's (per-channel int8 + f32 scales +
    f32 1-D leaves lands near 0.25; the bound catches a quantizer that
    silently stopped quantizing)."""
    import jax

    from distributedmnist_tpu.core.config import effective_model_config
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.quant.ptq import (build_tier_predict,
                                                parity_report,
                                                tier_param_bytes)
    from distributedmnist_tpu.train import checkpoint as ckpt
    cfg = quant_run["cfg"]
    model = get_model(effective_model_config(cfg))
    template = model.init(jax.random.PRNGKey(0))
    payload = ckpt.read_quant_sidecar(quant_run["with"], 20)
    state_sd, _ = ckpt._checkpoint_state_dict(quant_run["with"], 20)
    params_sd = state_sd["params"]
    x = synthetic_datasets.test.images
    labels = synthetic_datasets.test.labels
    ref = np.asarray(jax.jit(build_tier_predict(model, template, "fp32"))(
        params_sd, x))
    probs = np.asarray(
        jax.jit(build_tier_predict(model, template, tier))(
            payload["tiers"][tier], x))
    rep = parity_report(ref, probs, labels)
    eps = cfg.quant.parity_epsilon
    assert rep["agreement"] >= 1.0 - eps, (tier, rep)
    assert rep["top1_tier"] >= rep["top1_ref"] - eps, (tier, rep)
    nbytes = payload["meta"]["param_bytes"]
    assert nbytes[tier] == tier_param_bytes(payload["tiers"][tier])
    assert nbytes["fp32"] == tier_param_bytes(params_sd)
    assert nbytes[tier] <= resident * nbytes["fp32"], nbytes


# ---------------------------------------------------------------------------
# journal + invariant extensions
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_summarize_serving_swaps_defaults_legacy_to_fp32():
    from distributedmnist_tpu.obsv.journal import summarize_serving_swaps
    records = [
        {"action": "weight_swap", "step": 10},            # legacy: no tier
        {"action": "weight_swap", "step": 20, "tier": "int8"},
        {"action": "weight_swap", "step": 30, "tier": None},
        {"action": "follow_quant_sidecar_fallback", "step": 20},
        {"action": "respond", "id": 1},
    ]
    got = summarize_serving_swaps(records)
    assert got == {"swaps": 3, "by_tier": {"fp32": 2, "int8": 1},
                   "quant_sidecar_fallbacks": 1}


@pytest.mark.tier1
def test_summarize_chaos_serving_counts_tierless_trials_as_fp32(tmp_path):
    """The chaos aggregate replays PRE-quantization trial records (no
    serve_swaps/by_tier at all) without a KeyError, counting their
    swaps as fp32."""
    from distributedmnist_tpu.obsv.journal import summarize_chaos
    legacy = {"event": "chaos_trial", "trial": 0, "outcome": "completed",
              "serving": {"issued": 10, "dropped": 0, "responses": 10,
                          "rejected": 0, "errors": 0, "reject_rate": 0.0,
                          "model_steps_served": [10]},
              "serve_swaps": {"swaps": 3}}   # pre-tier record: no by_tier
    modern = {"event": "chaos_trial", "trial": 1, "outcome": "completed",
              "serving": {"issued": 5, "dropped": 0, "responses": 5,
                          "rejected": 0, "errors": 0, "reject_rate": 0.0,
                          "model_steps_served": [20],
                          "tiers_served": ["int8"]},
              "serve_swaps": {"swaps": 2, "by_tier": {"int8": 2},
                              "quant_sidecar_fallbacks": 1}}
    p = tmp_path / "chaos_report.jsonl"
    p.write_text(json.dumps(legacy) + "\n" + json.dumps(modern) + "\n")
    got = summarize_chaos(p)["serving"]
    assert got["swaps_by_tier"] == {"fp32": 3, "int8": 2}
    assert got["quant_sidecar_fallbacks"] == 1


@pytest.mark.tier1
def test_serve_digest_invariant_matches_torn_artifact_by_name(tmp_path):
    """A swap that read the INTACT quant sidecar after the fp32
    artifact was torn (or vice versa) is digest verification working —
    only a swap sourced from the torn artifact itself violates."""
    from distributedmnist_tpu.obsv.invariants import check_serving

    def trial(swap_source, torn):
        d = tmp_path / f"t_{swap_source[-20:]}_{torn[-20:]}"
        (d / "worker1").mkdir(parents=True)
        (d / "worker1" / "train_log.jsonl").write_text("")
        (d / "worker1" / "serve_log.jsonl").write_text("".join(
            json.dumps(r) + "\n" for r in [
                {"event": "serve", "action": "weight_swap", "step": 20,
                 "tier": "int8", "digest": "d", "time": 101.0,
                 "source_artifact": swap_source}]))
        journal = [{"event": "fault",
                    "action": "corrupt_latest_checkpoint",
                    "worker": 0, "target": torn, "ts": 100.0}]
        violations, applicable, _, _ = check_serving(
            d, {"serve_workers": [1]}, journal)
        assert applicable
        return {v.invariant for v in violations}

    quant = "ckpt-00000020.quant.msgpack"
    fp32 = "ckpt-00000020.msgpack"
    assert "serve_digest" not in trial(swap_source=quant, torn=fp32)
    assert "serve_digest" in trial(swap_source=quant, torn=quant)
    assert "serve_digest" in trial(swap_source=fp32, torn=fp32)
