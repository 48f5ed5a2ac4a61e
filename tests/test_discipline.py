"""Online straggler-discipline controller (train/discipline.py).

Covers the pure decision core (dead band, cooldown, bounds — the
broker decide() contract), the controller's journaled begin/complete
licensing, the rolling-CDF gauges it reads, the ``discipline`` replay
invariant (including the pinned doctored-unlicensed-change failure),
and the epoch-spliced determinism comparison in check_run.
"""

import json

import pytest

import numpy as np

from distributedmnist_tpu.core.config import ConfigError, SyncConfig
from distributedmnist_tpu.obsv import invariants as inv
from distributedmnist_tpu.obsv import schema
from distributedmnist_tpu.obsv.journal import summarize_discipline
from distributedmnist_tpu.obsv.timing import StepTimeCollector
from distributedmnist_tpu.train import discipline as disc
from distributedmnist_tpu.train.discipline import (DisciplineController,
                                                   DisciplineParams,
                                                   WindowStats, decide,
                                                   discipline_trace,
                                                   quorum_floor,
                                                   static_params,
                                                   threshold_holds)

pytestmark = pytest.mark.tier1

N = 8


def _cfg(**kw) -> SyncConfig:
    base = dict(mode="quorum", adaptive=True, adaptive_window_steps=4,
                adaptive_cooldown_steps=4)
    base.update(kw)
    return SyncConfig(**base)


def _ws(ratio: float, base: float = 50.0, n: int = 4) -> WindowStats:
    return WindowStats(p50_ms=base, p90_ms=base, p99_ms=base * ratio,
                       n_samples=n, fast_p50_ms=base)


def _params(cfg: SyncConfig, k: int | None = None) -> DisciplineParams:
    p = static_params(cfg, N)
    return p if k is None else DisciplineParams(
        k=k, timeout_ms=p.timeout_ms, interval_ms=p.interval_ms,
        num_replicas=N)


# ---------------------------------------------------------------------------
# decide(): the pure core
# ---------------------------------------------------------------------------

def test_decide_requires_adaptive_and_full_window():
    cfg = _cfg()
    cur = _params(cfg)
    off = SyncConfig(mode="quorum")
    assert decide(off, _ws(9.0), cur, None, 10) is None
    assert decide(cfg, None, cur, None, 10) is None
    assert decide(cfg, _ws(9.0, n=3), cur, None, 10) is None  # short


def test_decide_dead_band_is_hysteresis():
    cfg = _cfg(adaptive_tail_high=2.0, adaptive_tail_low=1.3)
    cur = _params(cfg, k=6)
    # between the marks: nothing, in BOTH directions
    assert decide(cfg, _ws(1.6), cur, None, 10) is None
    d = decide(cfg, _ws(2.0), cur, None, 10)
    assert d is not None and d.decision == "tighten" and d.new_k == 5
    d = decide(cfg, _ws(1.3), cur, None, 10)
    assert d is not None and d.decision == "relax" and d.new_k == 7


def test_decide_cooldown_suppresses_everything():
    cfg = _cfg(adaptive_cooldown_steps=10, adaptive_window_steps=4)
    cur = _params(cfg, k=6)
    assert decide(cfg, _ws(9.0), cur, last_change_t=5, now=14) is None
    assert decide(cfg, _ws(9.0), cur, last_change_t=5, now=15) is not None


def test_decide_quorum_bounds_floor_and_static_ceiling():
    cfg = _cfg(adaptive_min_quorum_frac=0.5)
    floor = quorum_floor(cfg, N)
    assert floor == 4
    # at the floor, a blown tail is a no-op, not a change
    assert decide(cfg, _ws(9.0), _params(cfg, k=floor), None, 10) is None
    # at the static ceiling, a calm tail is a no-op
    assert decide(cfg, _ws(1.0), _params(cfg), None, 10) is None


def test_decide_timeout_retargets_from_cohort_pace():
    cfg = _cfg(mode="timeout", timeout_ms=1000.0,
               adaptive_timeout_factor=1.5, adaptive_timeout_floor_ms=1.0)
    cur = _params(cfg)
    d = decide(cfg, _ws(9.0, base=50.0), cur, None, 10)
    assert d is not None and d.decision == "tighten"
    assert d.new_timeout_ms == pytest.approx(75.0)
    # sub-percent retarget sits in the dead band
    tight = DisciplineParams(k=cur.k, timeout_ms=75.2, interval_ms=0.0,
                             num_replicas=N)
    assert decide(cfg, _ws(9.0, base=50.0), tight, None, 10) is None
    # relax restores the configured deadline, never past it
    d = decide(cfg, _ws(1.0), tight, None, 10)
    assert d is not None and d.new_timeout_ms == pytest.approx(1000.0)
    assert decide(cfg, _ws(1.0), cur, None, 10) is None  # already static


def test_decide_property_k_stays_bounded_no_change_in_cooldown():
    import random
    rng = random.Random(0)
    cfg = _cfg()
    floor, static_k = quorum_floor(cfg, N), static_params(cfg, N).k
    cur, last = _params(cfg), None
    for step in range(5, 400):
        d = decide(cfg, _ws(rng.choice([0.5, 1.0, 1.6, 3.0, 9.0])),
                   cur, last, step)
        if d is not None:
            assert floor <= d.new_k <= static_k
            assert abs(d.new_k - cur.k) == 1  # one notch at a time
            if last is not None:
                assert step - last >= cfg.adaptive_cooldown_steps
            cur = DisciplineParams(k=d.new_k,
                                   timeout_ms=d.new_timeout_ms,
                                   interval_ms=cur.interval_ms,
                                   num_replicas=N)
            last = step


def test_window_stats_prefers_cohort_pace_over_pooled_p50():
    # two 8x stragglers of four drag the POOLED median to the midpoint;
    # the fastest replica's median keeps the signal out of the dead band
    s = WindowStats(p50_ms=225.0, p90_ms=400.0, p99_ms=400.0,
                    n_samples=6, fast_p50_ms=50.0)
    assert s.tail_ratio == pytest.approx(8.0)
    no_fast = WindowStats(p50_ms=225.0, p90_ms=400.0, p99_ms=400.0,
                          n_samples=6)
    assert no_fast.tail_ratio == pytest.approx(400.0 / 225.0)
    assert WindowStats(0.0, 0.0, 0.0, 6).tail_ratio == 0.0


def test_threshold_holds_matches_invariant_semantics():
    assert threshold_holds(2.0, ">=", 2.0)
    assert not threshold_holds(1.9, ">=", 2.0)
    assert threshold_holds(1.3, "<=", 1.3)
    assert not threshold_holds(1.4, "<=", 1.3)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_adaptive_knob_validation():
    with pytest.raises(ConfigError, match="maskable"):
        SyncConfig(mode="sync", adaptive=True).validate()
    with pytest.raises(ConfigError, match="window"):
        _cfg(adaptive_window_steps=1).validate()
    with pytest.raises(ConfigError, match="cooldown"):
        _cfg(adaptive_window_steps=8,
             adaptive_cooldown_steps=4).validate()
    with pytest.raises(ConfigError, match="high > low"):
        _cfg(adaptive_tail_high=1.2, adaptive_tail_low=1.3).validate()
    with pytest.raises(ConfigError, match="min_quorum_frac"):
        _cfg(adaptive_min_quorum_frac=0.0).validate()
    with pytest.raises(ConfigError, match="timeout_factor"):
        _cfg(adaptive_timeout_factor=0.5).validate()
    with pytest.raises(ConfigError, match="floor"):
        _cfg(adaptive_timeout_floor_ms=0.0).validate()
    # a starting quorum below the adaptive floor is a contradiction
    with pytest.raises(ConfigError, match="floor"):
        _cfg(num_replicas_to_aggregate=2).validate(num_replicas=8)
    _cfg().validate(num_replicas=8)  # defaults are coherent


# ---------------------------------------------------------------------------
# the controller: journaling + the traced-vector swap
# ---------------------------------------------------------------------------

def _run_controller(ratios, cfg=None):
    cfg = cfg or _cfg()
    journal: list[dict] = []
    vectors: list[tuple] = []
    ctrl = DisciplineController(
        cfg, N, journal.append,
        lambda k, t, i: (vectors.append((k, t, i)) or (k, t, i)))
    for step, r in enumerate(ratios, start=1):
        ctrl.maybe_adapt(step, _ws(r))
    return ctrl, journal, vectors


def test_controller_journals_licensed_pairs_and_swaps_vector():
    ratios = [1.0] * 4 + [9.0] * 10 + [1.0] * 10
    ctrl, journal, vectors = _run_controller(ratios)
    begins = [r for r in journal if r["action"] == "begin"]
    completes = [r for r in journal if r["action"] == "complete"]
    assert len(begins) == len(completes) == ctrl.changes >= 2
    for r in journal:  # every record passes the declared schema
        assert schema.validate_event(r, source="test") == []
    for b, c in zip(begins, completes):
        assert threshold_holds(b["value"], b["op"], b["threshold"])
        assert c["effective_step"] == b["at_step"] + 1
        assert c["k"] == b["new_k"]
    # one staged vector per change, plus the initial one
    assert len(vectors) == ctrl.changes + 1
    assert ctrl.trace == discipline_trace(journal)
    assert ctrl.summary()["changes"] == ctrl.changes


def _modeled_time_to_target(times, window):
    """The controller on a [steps, n] matrix of step times, no mesh: a
    step's barrier costs what its k-th fastest replica took. Returns
    (modeled ms, journal)."""
    n = times.shape[1]
    cfg = _cfg(adaptive_window_steps=window,
               adaptive_cooldown_steps=window)
    journal: list[dict] = []
    ctrl = DisciplineController(cfg, n, journal.append,
                                lambda k, t, i: (k, t, i))
    cost = 0.0
    for i in range(len(times)):
        cost += float(np.sort(times[i])[int(ctrl.current.k) - 1])
        stats = None
        if i + 1 >= window:
            tail = times[i + 1 - window:i + 1]
            p50, p90, p99 = np.percentile(tail, (50.0, 90.0, 99.0))
            stats = WindowStats(
                p50_ms=float(p50), p90_ms=float(p90), p99_ms=float(p99),
                n_samples=window,
                fast_p50_ms=float(np.median(tail, axis=0).min()))
        ctrl.maybe_adapt(i + 1, stats)
    return cost, journal


@pytest.mark.parametrize("fed", ["tail_ratios", "phased_step_times"])
def test_controller_tightens_to_floor_then_relaxes_to_static(fed):
    if fed == "phased_step_times":
        # calm, then two of four replicas 8x slow, then all four 3x slow
        # (healthy but slow: the phase that breaks a fixed deadline).
        # What the adaptive discipline is for, on modeled time: it
        # reaches the target sooner than any static choice an operator
        # could have made beforehand, and a deadline tuned on the calm
        # phase's tail never gets there.
        n, base, rng = 4, 50.0, np.random.default_rng(0)
        times = np.stack([
            base * mult + rng.uniform(0.0, 1.5, n)
            for steps, mult in ((25, np.ones(n)),
                                (30, np.array([1.0, 1.0, 8.0, 8.0])),
                                (25, np.full(n, 3.0)))
            for _ in range(steps)])
        adaptive, journal = _modeled_time_to_target(times, window=6)
        s = summarize_discipline(journal)
        assert s["by_direction"].get("tighten", 0) >= 1
        assert s["by_direction"].get("relax", 0) >= 1
        assert s["flaps"] == 0 and s["completed"] == s["changes"]
        ordered = np.sort(times, axis=1)
        statics = {k: float(ordered[:, k - 1].sum()) for k in (n, n - 1)}
        assert adaptive <= 0.9 * min(statics.values()), (adaptive, statics)
        deadline = 1.5 * float(np.percentile(times[:25], 99))
        assert (times[-25:] > deadline).all()   # no contributor, no update
        return
    cfg = _cfg()
    ctrl, journal, _ = _run_controller([9.0] * 40, cfg)
    assert ctrl.current.k == quorum_floor(cfg, N)
    ctrl2, j2, _ = _run_controller([9.0] * 20 + [1.0] * 40, cfg)
    assert ctrl2.current.k == static_params(cfg, N).k
    s = summarize_discipline(j2)
    assert s["by_direction"].get("tighten", 0) >= 1
    assert s["by_direction"].get("relax", 0) >= 1
    assert s["flaps"] == 0  # cooldown-spaced reversals are not flaps
    assert s["completed"] == s["changes"]
    assert s["reaction_s"]["p50"] >= 0


def test_controller_refuses_non_adaptive_config():
    with pytest.raises(ValueError, match="adaptive"):
        DisciplineController(SyncConfig(mode="quorum"), N,
                             lambda r: None, lambda k, t, i: None)


def test_summarize_discipline_counts_tight_reversal_as_flap():
    def begin(step, decision):
        return {"event": "discipline", "action": "begin",
                "decision": decision, "at_step": step,
                "cooldown_steps": 4}
    flappy = [begin(10, "tighten"), begin(14, "relax")]
    assert summarize_discipline(flappy)["flaps"] == 1
    spaced = [begin(10, "tighten"), begin(30, "relax")]
    assert summarize_discipline(spaced)["flaps"] == 0


# ---------------------------------------------------------------------------
# rolling CDF gauges (obsv/timing.py)
# ---------------------------------------------------------------------------

def test_rolling_cdf_gauges():
    c = StepTimeCollector(num_replicas=4)
    c.enable_rolling_cdf(4)
    c.add(np.array([50.0, 50.0, 400.0, 400.0]), 0.05)
    assert c.rolling_cdf() is None  # never decide on a half window
    for _ in range(4):
        c.add(np.array([50.0, 50.0, 400.0, 400.0]), 0.05)
    r = c.rolling_cdf()
    assert r is not None and r["window_steps"] == 4
    assert r["fast_p50_ms"] == pytest.approx(50.0)
    assert r["p99_ms"] == pytest.approx(400.0, rel=0.01)
    assert r["tail_ratio"] == pytest.approx(8.0, rel=0.01)
    assert len(r["per_replica"]) == 4
    assert "rolling_cdf" in c.report()  # armed → gauges in the report
    plain = StepTimeCollector(num_replicas=4)
    plain.add(np.array([1.0, 1.0, 1.0, 1.0]), 0.01)
    assert "rolling_cdf" not in plain.report()  # present iff armed
    with pytest.raises(ValueError):
        plain.enable_rolling_cdf(0)


# ---------------------------------------------------------------------------
# the replay invariant
# ---------------------------------------------------------------------------

def _begin(step, new_k, old_k, value=8.0, op=">=", thr=2.0,
           decision="tighten"):
    return {"event": "discipline", "action": "begin", "time": 1.0,
            "decision": decision, "trigger": "tail_ratio",
            "value": value, "threshold": thr, "op": op,
            "old_k": old_k, "new_k": new_k,
            "old_timeout_ms": 1000.0, "new_timeout_ms": 1000.0,
            "at_step": step}


def _complete(step, k, decision="tighten"):
    return {"event": "discipline", "action": "complete", "time": 1.1,
            "decision": decision, "trigger": "tail_ratio",
            "reaction_s": 0.01, "k": k, "timeout_ms": 1000.0,
            "effective_step": step + 1}


def _step(step, k):
    return {"event": "step", "step": step, "loss": 1.0,
            "discipline": [float(k), 1000.0]}


def _licensed_log(change_at=2, old_k=4, new_k=3, steps=4):
    recs = []
    for s in range(1, steps + 1):
        recs.append(_step(s, new_k if s > change_at else old_k))
        if s == change_at:
            recs += [_begin(s, new_k, old_k), _complete(s, new_k)]
    return recs


def test_check_discipline_green_and_not_applicable():
    log = _licensed_log()
    steps = [r for r in log if r.get("event") == "step"]
    violations, applicable = inv.check_discipline(steps, log)
    assert applicable and violations == []
    v, app = inv.check_discipline([{"event": "step", "step": 1}],
                                  [{"event": "step", "step": 1}])
    assert not app and v == []


def test_check_discipline_pins_doctored_unlicensed_change():
    """Acceptance: a step record whose [k, timeout] pair changed with
    no licensing begin/complete at that boundary MUST fail replay."""
    log = _licensed_log()
    steps = [dict(r) for r in log if r.get("event") == "step"]
    steps[2]["discipline"] = [2.0, 1000.0]  # doctor step 3's pair
    violations, _ = inv.check_discipline(steps, log)
    assert any("unlicensed" in v.detail or "licensing complete"
               in v.detail for v in violations)
    # deleting the begin breaks the pairing too
    no_begin = [r for r in log if r.get("action") != "begin"]
    v2, _ = inv.check_discipline(
        [r for r in log if r.get("event") == "step"], no_begin)
    assert any("no open begin" in v.detail for v in v2)


def test_check_discipline_rejects_fabricated_license():
    bad = [_begin(2, 3, 4, value=1.5, op=">=", thr=2.0), _complete(2, 3)]
    v, app = inv.check_discipline([], bad)
    assert app and any("does not hold" in x.detail for x in v)
    malformed = [_begin(2, 3, 4, value=None), _complete(2, 3)]
    v2, _ = inv.check_discipline([], malformed)
    assert any("malformed license" in x.detail for x in v2)


def test_check_discipline_single_flight_and_boundary():
    dangling = [_begin(2, 3, 4)]
    v, _ = inv.check_discipline([], dangling)
    assert any("never closed" in x.detail for x in v)
    overlapping = [_begin(2, 3, 4), _begin(6, 2, 3), _complete(6, 2)]
    v2, _ = inv.check_discipline([], overlapping)
    assert any("single-flight" in x.detail for x in v2)
    # complete landing on the wrong pair / wrong boundary
    mismatch = [_begin(2, 3, 4), _complete(2, 2)]
    v3, _ = inv.check_discipline([], mismatch)
    assert any("begin declared" in x.detail for x in v3)
    off = [_begin(2, 3, 4),
           dict(_complete(2, 3), effective_step=5)]
    v4, _ = inv.check_discipline([], off)
    assert any("epoch boundary" in x.detail for x in v4)


def test_discipline_trace_skips_malformed_completes():
    log = _licensed_log() + [{"event": "discipline",
                              "action": "complete", "k": "junk"}]
    assert discipline_trace(log) == [(3, 3.0, 1000.0)]


# ---------------------------------------------------------------------------
# epoch-spliced determinism (check_run)
# ---------------------------------------------------------------------------

def _write_log(path, records):
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as fh:
        for r in records:
            fh.write(json.dumps(r) + "\n")


def _trial_with_checkpoint(root, state, log_records, steps=4):
    from distributedmnist_tpu.train import checkpoint as ckpt
    w0 = root / "worker0"
    _write_log(w0 / "train_log.jsonl", log_records)
    ckpt.save_checkpoint(w0, ("full", state), step=steps)
    (root / "command_journal.jsonl").write_text("")
    return {"outcome": "completed", "step": steps, "target": steps,
            "supervisor": {"quorum": 1}}


def test_check_run_splices_determinism_at_epoch_divergence(tmp_path):
    """Invariant 3 under the controller: equal discipline traces →
    the bitwise digest comparison runs (and a doctored state FAILS);
    divergent traces → the comparison is spliced out for that worker
    (skip with the splice counted), while the discipline licensing
    invariant still replays."""
    from distributedmnist_tpu.train import checkpoint as ckpt

    state_a = {"params": {"w": np.arange(8, dtype=np.float32)},
               "momentum": {"w": np.zeros(8, dtype=np.float32)},
               "step": np.int32(4)}
    state_b = {"params": {"w": np.arange(8, dtype=np.float32) + 1.0},
               "momentum": {"w": np.zeros(8, dtype=np.float32)},
               "step": np.int32(4)}
    ref = tmp_path / "reference" / "worker0"
    _write_log(ref / "train_log.jsonl", _licensed_log())
    ckpt.save_checkpoint(ref, ("full", state_a), step=4)

    # same trace, different state: the bitwise claim applies and fails
    t1 = tmp_path / "trial1"
    outcome = _trial_with_checkpoint(t1, state_b, _licensed_log())
    got = inv.check_run(t1, outcome=outcome, reference_dir=ref)
    assert got["verdicts"]["discipline"] == "pass"
    assert got["verdicts"]["determinism"] == "fail"
    assert got["determinism_workers_spliced"] == 0

    # divergent trace (an extra licensed change): spliced out, skipped
    diverged = _licensed_log() + [_begin(4, 2, 3), _complete(4, 2)]
    t2 = tmp_path / "trial2"
    outcome2 = _trial_with_checkpoint(t2, state_b, diverged)
    got2 = inv.check_run(t2, outcome=outcome2, reference_dir=ref)
    assert got2["verdicts"]["discipline"] == "pass"
    assert got2["verdicts"]["determinism"] == "skipped"
    assert got2["determinism_workers_spliced"] == 1
    assert not any(v["invariant"] == "determinism"
                   for v in got2["violations"])

    # the licensing invariant is NOT relaxed by the splice
    t3 = tmp_path / "trial3"
    doctored = [dict(r) for r in diverged]
    for r in doctored:
        if r.get("event") == "step" and r["step"] == 2:
            r["discipline"] = [2.0, 1000.0]  # unlicensed early change
    outcome3 = _trial_with_checkpoint(t3, state_b, doctored)
    got3 = inv.check_run(t3, outcome=outcome3, reference_dir=ref)
    assert got3["verdicts"]["discipline"] == "fail"


def test_check_run_discipline_skipped_when_never_armed(tmp_path):
    w0 = tmp_path / "worker0"
    _write_log(w0 / "train_log.jsonl",
               [{"step": s, "loss": 1.0} for s in range(1, 5)])
    (tmp_path / "command_journal.jsonl").write_text("")
    got = inv.check_run(tmp_path, outcome={
        "outcome": "completed", "step": 4, "target": 4,
        "supervisor": {"quorum": 1}})
    assert got["verdicts"]["discipline"] == "skipped"


# ---------------------------------------------------------------------------
# end to end: the trainer under a seeded spike profile
# ---------------------------------------------------------------------------

def test_trainer_adapts_quorum_under_spike_profile(tmp_train_dir,
                                                   synthetic_datasets):
    """The whole loop on 8 virtual devices: spike stragglers blow the
    rolling tail ratio, the controller tightens the traced quorum, the
    step records observe the change, and the artifact set replays green
    against the discipline invariant."""
    from pathlib import Path

    from conftest import base_config
    from distributedmnist_tpu.obsv.report import load_jsonl
    from distributedmnist_tpu.train.loop import Trainer

    cfg = base_config(
        sync={"mode": "quorum", "adaptive": True,
              "adaptive_window_steps": 4, "adaptive_cooldown_steps": 4,
              "straggler_profile": "spike",
              "straggler_spike_prob": 0.25,
              "straggler_spike_scale": 8.0},
        train={"max_steps": 14, "log_every_steps": 1,
               "train_dir": tmp_train_dir})
    trainer = Trainer(cfg, datasets=synthetic_datasets)
    run_summary = trainer.run()
    summary = run_summary["discipline"]
    assert summary["changes"] >= 1
    assert summary["current_k"] < 8  # tightened off the static quorum
    # the change was a new operand of the one executable compiled before
    # step 1: nothing fell through to a second compile
    assert trainer.step_fn.jitted._cache_size() == 0

    log = load_jsonl(Path(tmp_train_dir) / "train_log.jsonl")
    steps = [r for r in log if r.get("event") == "step"
             and isinstance(r.get("step"), int)]
    assert all("discipline" in r for r in steps)  # armed → observed
    pairs = {tuple(r["discipline"]) for r in steps}
    assert len(pairs) >= 2  # the change is visible in the series
    violations, applicable = inv.check_discipline(steps, log)
    assert applicable and violations == []
    assert discipline_trace(log) == [tuple(t) for t in summary["trace"]]
