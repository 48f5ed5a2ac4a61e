"""The flagship experiment campaign — the deliverable the reference
exists to produce (tools/benchmark.py:265-292 drove the same grids on
EC2 and plotted the curves).

Runs the full configs/ grid on the simulated 8-device mesh:

* quorum sweep  k ∈ {1,2,4,6,7,8}-of-8   (≙ cfg/50_workers/*_aggregate_sync)
* interval sweep {3000..7000} ms          (≙ cfg/50_workers/*_interval)
* worker-time-CDF grid, 4 straggler profiles (≙ cfg/time_cdf_cfgs/*)
* extras: fashion-mnist timeout drop, CIFAR ResNet-20 (scaled for the
  1-core CPU budget — overrides recorded in the result records),
  synthetic-LM transformer
* repro_mnist99: the one-command 99% config (configs/repro/
  mnist_99.json) end-to-end, evaluator oracle live against it

with the continuous evaluator (evalsvc) live against the quorum k=8 run
— the reference's oracle (src/nn_eval.py:117-140) watching an actual
training run.

Data: the idx fixture (data/fixtures.py) is materialized first so every
mnist/fashion_mnist config exercises the REAL ingest path — idx.gz
parse → normalization → sharding — not the in-memory synthetic
fallback.

Entry points: ``python run_campaign.py`` at the repo root (forces the
8-device CPU mesh first) or ``python -m distributedmnist_tpu.launch
campaign``; ``--finalize-only`` regenerates reports from disk.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from ..core.config import ExperimentConfig
from ..core.log import JsonlSink, get_logger
from .sweep import run_experiment, write_report

logger = get_logger("campaign")

GROUPS = {
    "quorum": [f"quorum_k{k}_of_8" for k in (1, 2, 4, 6, 7, 8)],
    "interval": [f"interval_{ms}ms" for ms in (3000, 4000, 5000, 6000, 7000)],
    "cdf": ["cdf_uniform", "cdf_lognormal_mild", "cdf_lognormal_heavy",
            "cdf_spike"],
    "extras": ["fashion_mnist_timeout", "cifar10_resnet20_sync",
               "synthetic_lm_transformer"],
    # the one-command 99% repro (configs/repro/mnist_99.json) run through
    # the same harness, with the evaluator oracle live against it — the
    # reference's headline result (99%+ MNIST, src/nn_eval.py:95-103)
    "repro_mnist99": ["mnist_99"],
    # Experiment A at the reference's TRUE topology: 50 workers,
    # replicas_to_aggregate ∈ {1,10,20,30,40,49,50}
    # (cfg/50_workers/*_aggregate_sync:10). The configs force a
    # 50-virtual-device mesh (mesh.simulate_devices; run_experiment's
    # ensure_mesh restores the ambient mesh afterwards):
    #   python run_campaign.py --groups quorum50
    "quorum50": [f"quorum50_k{k}_of_50" for k in (1, 10, 20, 30, 40, 49, 50)],
    # Experiment C at the true topology: the four worker-time CDF
    # profiles on the 50-device mesh (full-barrier mode, per-replica
    # timing all-gather at the reference's actual worker count)
    "cdf50": ["cdf50_uniform", "cdf50_lognormal_mild",
              "cdf50_lognormal_heavy", "cdf50_spike"],
    # Convergence proofs for the two disciplines the grids leave short
    # (the grids bound steps on wall-clock): one interval-mode run and
    # one 50-replica cdf-mode run trained until the live evaluator's
    # 99% oracle passes — ≙ the reference driving every discipline to
    # comparable convergence (tools/benchmark.py:265-279,
    # cfg/50_workers/*_interval):
    #   python run_campaign.py --groups long
    "long": ["interval_long", "cdf50_long"],
}

# Groups a plain `python run_campaign.py` runs. The 50-device groups
# are excluded on wall-clock grounds only (300-step runs at 50-way
# SPMD, hours on one core) — launch them separately:
#   python run_campaign.py --groups quorum50
#   python run_campaign.py --groups cdf50
DEFAULT_GROUPS = [g for g in GROUPS if g not in ("quorum50", "cdf50", "long")]

# CPU-budget scale-downs, recorded verbatim into each result record.
# (Note: the 8-replica quorum/interval configs carry the reference's
# experiment batch size 128 — cfg/50_workers/*:63. The quorum50 configs
# are the exception: they BAKE IN a 16/replica batch — global 800 vs
# the reference's 128/worker = 6400 (cfg/50_workers/*:63) — as a CPU
# scale-down of their own, in the config file rather than here. Only
# the items below are campaign-local deviations.)
OVERRIDES = {
    "cifar10_resnet20_sync": {"train.max_steps": 150, "data.batch_size": 256,
                              "train.log_every_steps": 10},
    "synthetic_lm_transformer": {"train.max_steps": 200},
    # wall-clock checkpoint cadence (≙ Supervisor save_model_secs=20,
    # src/distributed_train.py:76-77) so the live evaluator sees a
    # stream of checkpoints, not just the final one
    "quorum_k8_of_8": {"train.save_interval_secs": 15.0},
    # same, and also: at this run's CPU step rate the config's step-based
    # cadence (save_interval_steps=500 ≈ 13 min) outlives the
    # evaluator's 600 s first-checkpoint timeout — wall-clock saves keep
    # the oracle fed from the start
    "mnist_99": {"train.save_interval_secs": 60.0},
    # cdf50 keeps the cdf grid's per-replica batch (128 → global 6400
    # over 50 replicas) so the timing CDFs are comparable; the step
    # budget is what yields to the 1-core clock — 100 steps is 100
    # timing samples per replica, plenty for the percentile curves
    **{f"cdf50_{p}": {"train.max_steps": 100}
       for p in ("uniform", "lognormal_mild", "lognormal_heavy", "spike")},
    # Interval sweep: UPDATE-count-matched step budgets. The configs
    # keep the reference's fixed 300-iteration benchmark convention
    # (tools/benchmark.py:265 n_iters), but a fixed step count gives
    # slower pacings fewer applied updates (300 steps at the modeled
    # ~840 ms step = 84 updates at 3000 ms but only 36 at 7000 ms), so
    # a final-accuracy column misreads as "slower pacing is broken".
    # steps ∝ interval_ms equalizes applied updates (measured 681-746
    # across the sweep — the ~680 count long/interval_long converged
    # at), so the sweep's accuracy column compares pacings at equal,
    # convergence-sufficient update budgets.
    **{f"interval_{ms}ms": {"train.max_steps": 800 * ms // 1000}
       for ms in (3000, 4000, 5000, 6000, 7000)},
}

EVALUATED_RUN = "quorum_k8_of_8"  # kept for callers that import it
# the runs the live evaluator watches (one per group that has one)
EVALUATED_RUNS = {EVALUATED_RUN, "mnist_99", "interval_long", "cdf50_long"}


def resolve_config_path(configs_dir: Path, name: str) -> Path:
    """Grid configs sit in configs/; repro configs one level down."""
    candidates = [configs_dir / f"{name}.json",
                  configs_dir / "repro" / f"{name}.json"]
    for path in candidates:
        if path.exists():
            return path
    raise FileNotFoundError(
        f"no config named {name!r}; tried "
        + " and ".join(str(p) for p in candidates))


def run_group(group: str, names: list[str], results_dir: Path,
              configs_dir: Path, data_dir: Path, quick: bool) -> list[dict]:
    gdir = results_dir / group
    gdir.mkdir(parents=True, exist_ok=True)
    records = []
    with JsonlSink(gdir / "sweep_results.jsonl") as sink:
        for name in names:
            cfg = ExperimentConfig.from_file(
                resolve_config_path(configs_dir, name))
            ov = {"data.data_dir": str(data_dir / cfg.data.dataset),
                  "data.download": False}
            ov.update(OVERRIDES.get(name, {}))
            if quick:
                ov["train.max_steps"] = 20
            cfg = cfg.override(ov)
            # Campaign semantics are RUN, not resume —
            # run_experiment's fresh default (train.resume=False)
            # guarantees it without deleting the previous artifacts
            # up front (a pre-run wipe would destroy the committed
            # evidence of a multi-hour run if the replacement crashed
            # mid-flight). History lives in sweep_results.jsonl.
            ev = None
            if name in EVALUATED_RUNS and not quick:
                ev = start_evaluator(gdir / name)
            t0 = time.time()
            try:
                rec = run_experiment(cfg, gdir)
            finally:
                if ev is not None:
                    stop_evaluator(ev, gdir / name)
                    # redraw this run's report with the evaluator's log
                    # so precision-vs-time (the oracle curve) lands
                    from ..obsv.report import generate_report
                    generate_report(gdir / name / "train",
                                    gdir / name / "eval",
                                    gdir / name / "figures", name=name)
            rec["overrides"] = ov
            rec["group"] = group
            logger.info("[%s] %s done in %.0fs", group, name, time.time() - t0)
            sink.write(rec)
            records.append(rec)
    write_report(records, gdir)
    return records


def start_evaluator(run_dir: Path) -> subprocess.Popen:
    """Launch the continuous evaluator against a run's train dir — the
    reference's separate evaluator machine (tools/tf_ec2.py:130-146).

    Runs --single_device under ``nice -n 5``: on a shared host the
    trainer's N-device collectives abort hard (XLA's 40 s rendezvous
    termination) if another full-mesh process starves them — measured
    twice on the 1-core box before this. A one-device evaluator has no
    collectives of its own and cannot starve the trainer's (one
    runnable thread against the trainer's N at higher weight), while
    nice 19 was measured to starve the EVALUATOR into uselessness
    (~5% of the core: 25 min to merely boot against a 50-device
    trainer) — 5 is the balance. (``nice`` as a command prefix, NOT
    preexec_fn: forking this multithreaded JAX parent and running
    Python pre-exec can deadlock the child.)

    The child's env is scrubbed of the parent's forced-mesh settings
    (simulate_devices mutates XLA_FLAGS/JAX_PLATFORMS process-wide) so
    the evaluator boots one device, not N virtual CPU devices it would
    immediately discard — and it is pinned to the CPU platform: a chip
    belongs to one process, the training parent holds it, and the
    evaluator is an accuracy oracle, not a device measurement."""
    from ..core.mesh import strip_forced_platform_env
    run_dir.mkdir(parents=True, exist_ok=True)
    eval_dir = run_dir / "eval"
    env = strip_forced_platform_env(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    with open(run_dir / "evaluator_stdout.log", "w") as log:
        proc = subprocess.Popen(
            ["nice", "-n", "5",
             sys.executable, "-m", "distributedmnist_tpu.launch", "eval",
             "--train_dir", str(run_dir / "train"),
             "--eval_dir", str(eval_dir),
             "--eval_interval_secs", "2.0",
             "--single_device"],
            stdout=log, stderr=subprocess.STDOUT,  # child keeps its dup
            env=env)
    logger.info("evaluator pid %d watching %s", proc.pid, run_dir / "train")
    return proc


def stop_evaluator(proc: subprocess.Popen, run_dir: Path) -> None:
    # give it one last poll cycle to evaluate the final checkpoint
    time.sleep(8.0)
    proc.terminate()
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
    logger.info("evaluator stopped (rc=%s)", proc.returncode)


def prune_heavy_artifacts(results_dir: Path) -> None:
    """Drop checkpoint payloads before committing: fully reproducible
    from config + seed, and tens of MB each."""
    for p in results_dir.rglob("ckpt-*.msgpack"):
        p.unlink()
    for p in results_dir.rglob("CHECKPOINT"):
        p.unlink()


# Self-description for the summary JSON: groups whose accuracy columns
# are step-budget-bounded by design carry a pointer to the long-run
# convergence proof, so the summary cannot be misread on its own. Each
# note is keyed (group, proof-run name) and only emitted when the cited
# proof run actually exists in the same results dir.
SUMMARY_NOTES = {
    ("interval", "interval_long"): (
        "budgets are update-count-matched: steps scale with interval_ms "
        "(campaign OVERRIDES) so every pacing applies ~680-750 updates "
        "— the count long/interval_long converged at — and the accuracy "
        "column compares pacings at equal, convergence-sufficient "
        "update budgets rather than penalizing slow pacings for a "
        "fixed step count."),
    ("cdf50", "cdf50_long"): (
        "accuracies are a 100-step-budget artifact: this grid measures "
        "barrier timing, not convergence. Convergence proof: "
        "long/cdf50_long (full-barrier at n=50, 400 updates, "
        "test_accuracy 1.0)."),
}


def finalize(results_dir: Path) -> None:
    """Regenerate every group's report.md/figures from its
    sweep_results.jsonl with the CURRENT analysis code, rebuild the
    top-level summary from what's on disk, and prune checkpoint
    payloads — idempotent, safe to run after partial/rerun campaigns."""
    summary = {}
    for gdir in sorted(p for p in results_dir.iterdir() if p.is_dir()):
        f = gdir / "sweep_results.jsonl"
        if not f.exists():
            continue
        records = [json.loads(l) for l in f.read_text().splitlines()
                   if l.strip()]
        # a rerun APPENDS to the group's jsonl (the full history stays
        # on disk); reports and the summary reflect each experiment's
        # LATEST record only
        records = list({r.get("name"): r for r in records}.values())
        write_report(records, gdir)
        summary[gdir.name] = [{k: r.get(k) for k in
                               ("name", "test_accuracy", "examples_per_sec",
                                "updates_applied")} for r in records]
        logger.info("finalized %s (%d experiments)", gdir.name, len(records))
    long_names = {r.get("name") for r in summary.get("long", ())}
    notes = {g: note for (g, proof), note in SUMMARY_NOTES.items()
             if g in summary and proof in long_names}
    (results_dir / "campaign_summary.json").write_text(
        json.dumps({"groups": summary, "notes": notes}, indent=2))
    prune_heavy_artifacts(results_dir)


def main(argv=None, root: Path | None = None) -> int:
    root = root or Path.cwd()
    ap = argparse.ArgumentParser(prog="campaign")
    ap.add_argument("--results", default=str(root / "results"))
    ap.add_argument("--configs", default=str(root / "configs"))
    ap.add_argument("--data-cache", default=str(root / "data_cache"))
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--groups", default=",".join(DEFAULT_GROUPS))
    ap.add_argument("--finalize-only", action="store_true")
    args = ap.parse_args(argv)
    groups = args.groups.split(",")
    unknown = [g for g in groups if g not in GROUPS]
    if unknown:
        ap.error(f"unknown groups {unknown}; choose from {sorted(GROUPS)}")
    results_dir = Path(args.results)
    results_dir.mkdir(parents=True, exist_ok=True)
    if args.finalize_only:
        finalize(results_dir)
        return 0

    from ..data.fixtures import (materialize_cifar10_fixture,
                                 materialize_idx_fixture)
    data_dir = Path(args.data_cache)
    for ds in ("mnist", "fashion_mnist"):
        materialize_idx_fixture(data_dir / ds, ds)
    materialize_cifar10_fixture(data_dir / "cifar10")
    logger.info("idx + cifar10 fixtures ready under %s", data_dir)

    t0 = time.time()
    for group in groups:
        run_group(group, GROUPS[group], results_dir, Path(args.configs),
                  data_dir, args.quick)
    # Rebuild the summary from EVERYTHING on disk (not just the groups
    # this invocation ran) — a partial run, e.g. --groups repro_mnist99,
    # must merge into, not clobber, the committed campaign summary.
    finalize(results_dir)
    logger.info("campaign complete in %.0fs", time.time() - t0)
    return 0
