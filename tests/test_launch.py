"""Sweep runner + CLI tests (≙ tools/benchmark.py / tools/tf_ec2.py roles)."""

import json

import pytest

from conftest import base_config


def test_run_experiment_produces_record(tmp_path, synthetic_datasets):
    from distributedmnist_tpu.launch.sweep import run_experiment
    cfg = base_config(name="exp_sync",
                      sync={"mode": "quorum", "num_replicas_to_aggregate": 4,
                            "straggler_profile": "lognormal"},
                      train={"max_steps": 15, "log_every_steps": 5})
    rec = run_experiment(cfg, tmp_path, datasets=synthetic_datasets)
    assert rec["name"] == "exp_sync"
    assert rec["steps"] == 15
    assert 0.0 <= rec["test_accuracy"] <= 1.0
    assert (tmp_path / "exp_sync" / "result.json").exists()
    assert (tmp_path / "exp_sync" / "config.json").exists()


def test_run_experiment_is_fresh_not_resumed(tmp_path, synthetic_datasets):
    """A re-run into an existing results dir must train from step 0,
    not silently resume from the previous attempt's checkpoint: a
    resume reports steps=final_step while the timing arrays cover only
    the post-resume tail (two interval-sweep rows shipped that way).
    ``steps == timing.num_steps`` is the consistency invariant."""
    from distributedmnist_tpu.launch.sweep import run_experiment
    cfg = base_config(name="fresh_check",
                      train={"max_steps": 6, "log_every_steps": 3,
                             "save_interval_steps": 3})
    first = run_experiment(cfg, tmp_path, datasets=synthetic_datasets)
    assert first["steps"] == first["timing"]["num_steps"] == 6
    # second run with a RAISED budget over the same dir (the leftover
    # step-6 checkpoint is the trap)
    cfg2 = base_config(name="fresh_check",
                       train={"max_steps": 10, "log_every_steps": 5,
                              "save_interval_steps": 5})
    rec = run_experiment(cfg2, tmp_path, datasets=synthetic_datasets)
    assert rec["steps"] == rec["timing"]["num_steps"] == 10


def test_run_sweep_report(tmp_path, synthetic_datasets):
    from distributedmnist_tpu.launch.sweep import run_sweep
    cfgs = [base_config(name=f"s{k}",
                        sync={"mode": "quorum", "num_replicas_to_aggregate": k,
                              "straggler_profile": "lognormal"},
                        train={"max_steps": 8, "log_every_steps": 4})
            for k in (2, 8)]
    records = run_sweep(cfgs, tmp_path, datasets=synthetic_datasets)
    assert len(records) == 2
    report = (tmp_path / "report.md").read_text()
    assert "s2" in report and "s8" in report
    lines = (tmp_path / "sweep_results.jsonl").read_text().strip().split("\n")
    assert len(lines) == 2
    assert (tmp_path / "step_time_cdf.png").exists()


def test_campaign_finalize_regenerates_reports(tmp_path, synthetic_datasets):
    """run_campaign.finalize rebuilds every group report + the summary
    from sweep_results.jsonl on disk, prunes checkpoint payloads, and
    is idempotent — the recovery path when analysis code improves after
    a multi-hour campaign already ran."""
    import run_campaign
    from distributedmnist_tpu.launch.sweep import run_sweep

    gdir = tmp_path / "groupA"
    cfgs = [base_config(name=f"s{k}",
                        sync={"mode": "quorum", "num_replicas_to_aggregate": k,
                              "straggler_profile": "lognormal"},
                        train={"max_steps": 8, "log_every_steps": 4})
            for k in (2, 8)]
    run_sweep(cfgs, gdir, datasets=synthetic_datasets)
    (gdir / "report.md").unlink()  # simulate stale/missing analysis
    assert list(gdir.rglob("ckpt-*.msgpack"))

    run_campaign.finalize(tmp_path)
    report = (gdir / "report.md").read_text()
    assert "modeled" in report and "s2" in report
    summary = json.loads((tmp_path / "campaign_summary.json").read_text())
    assert [r["name"] for r in summary["groups"]["groupA"]] == ["s2", "s8"]
    assert not list(gdir.rglob("ckpt-*.msgpack"))  # pruned
    run_campaign.finalize(tmp_path)  # idempotent
    assert (gdir / "report.md").exists()


def test_load_sweep_configs_rejects_duplicates(tmp_path):
    from distributedmnist_tpu.launch.sweep import load_sweep_configs
    (tmp_path / "a.json").write_text(json.dumps({"name": "dup"}))
    (tmp_path / "b.json").write_text(json.dumps({"name": "dup"}))
    with pytest.raises(ValueError):
        load_sweep_configs(tmp_path)


def test_repo_sweep_configs_all_parse():
    """Every shipped config must load cleanly — the grid in configs/
    AND every config in subdirectories (configs/repro/…), so a broken
    repro config can't hide from CI behind the non-recursive sweep
    loader."""
    from pathlib import Path
    from distributedmnist_tpu.launch.sweep import load_sweep_configs
    root = Path(__file__).resolve().parent.parent / "configs"
    cfgs = load_sweep_configs(root)
    assert len(cfgs) >= 15
    modes = {c.sync.mode for c in cfgs}
    assert {"quorum", "interval", "cdf", "sync", "timeout"} <= modes
    # configs/cluster/ holds LocalClusterConfig / FaultPlan JSONs, not
    # experiment configs — their parse coverage lives in
    # test_cluster_exec.py::test_repo_cluster_configs_parse
    subdir_cfgs = [load_sweep_configs(f)[0]
                   for sub in sorted(p for p in root.iterdir()
                                     if p.is_dir() and p.name != "cluster")
                   for f in sorted(sub.glob("*.json"))]
    names = {c.name for c in subdir_cfgs}
    assert "mnist_99" in names  # the one-command 99% repro config


def test_sweep_restores_ambient_mesh(tmp_path):
    """A sweep mixing a simulated-mesh config with ambient-mesh ones
    must run each on ITS mesh: the 4-device config forces 4 virtual
    devices, and the following plain config gets the ambient 8 back
    (ensure_mesh). Without the restore, every config after a
    quorum50-style entry silently runs (and records) wide experiments
    under its narrow name. Subprocess: clear_backends would invalidate
    this session's device handles."""
    import subprocess
    import sys
    script = f"""
import json
from distributedmnist_tpu.core.mesh import simulate_devices
simulate_devices(8)  # the ambient mesh (what conftest does)
from distributedmnist_tpu.core.config import ExperimentConfig
from distributedmnist_tpu.launch.sweep import run_sweep
base = {{"data": {{"dataset": "synthetic", "batch_size": 64,
                   "synthetic_train_size": 256, "synthetic_test_size": 128,
                   "use_native_pipeline": False}},
         "model": {{"compute_dtype": "float32"}},
         "train": {{"max_steps": 2, "log_every_steps": 1,
                    "save_interval_steps": 0, "save_results_period": 0}}}}
cfgs = [ExperimentConfig.from_dict(dict(base, name="sim4",
                                        mesh={{"simulate_devices": 4}})),
        ExperimentConfig.from_dict(dict(base, name="ambient"))]
recs = run_sweep(cfgs, r"{tmp_path}")
print(json.dumps([[r["name"], r["num_replicas"]] for r in recs]))
"""
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == [["sim4", 4], ["ambient", 8]], got


def test_ensure_mesh_noop_and_nonrestorable():
    """ensure_mesh: matching device set → no backend teardown (device
    objects stay valid); ambient-non-CPU + mismatch → loud error, never
    a silent wrong-mesh run."""
    import jax
    from distributedmnist_tpu.core import mesh as mesh_mod

    devs_before = jax.devices()
    mesh_mod.ensure_mesh(8)   # conftest mesh is already 8 CPU devices
    mesh_mod.ensure_mesh(0)   # ambient == current → noop
    assert jax.devices() == devs_before  # no clear_backends happened

    saved = mesh_mod._ambient_mesh
    try:
        # simulate a process whose ambient backend was a real TPU: a
        # restore to ambient cannot re-force an accelerator
        mesh_mod._ambient_mesh = (1, "tpu")
        with pytest.raises(RuntimeError, match="own process"):
            mesh_mod.ensure_mesh(0)
    finally:
        mesh_mod._ambient_mesh = saved


def test_simulated_mesh_refuses_to_replace_a_live_accelerator(monkeypatch):
    """A config asking for MORE virtual devices than are visible tears
    the backend down and forces a CPU mesh — only when the live backend
    IS the CPU. On a live accelerator both entry points refuse loudly:
    a run that believes it is on the chip never lands on a CPU mesh."""
    import jax
    from distributedmnist_tpu.core import mesh as mesh_mod
    from distributedmnist_tpu.core.config import MeshConfig

    devs_before = jax.devices()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="live backend is 'tpu'"):
        mesh_mod.make_topology(MeshConfig(simulate_devices=16))
    saved = mesh_mod._ambient_mesh
    try:
        mesh_mod._ambient_mesh = (8, "tpu")
        with pytest.raises(RuntimeError, match="live backend is 'tpu'"):
            mesh_mod.ensure_mesh(16)
    finally:
        mesh_mod._ambient_mesh = saved
    assert jax.devices() == devs_before  # nothing was torn down
    # a count the visible devices already cover never reaches the branch
    assert mesh_mod.make_topology(
        MeshConfig(simulate_devices=8)).num_replicas == 8


def test_campaign_groups_resolve_to_configs():
    """Every name the campaign driver would run must resolve to a
    loadable config — including repro_mnist99, whose config lives in
    configs/repro/ (the same fallback run_group applies)."""
    from pathlib import Path
    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.launch.campaign import (EVALUATED_RUNS, GROUPS,
                                                      resolve_config_path)
    root = Path(__file__).resolve().parent.parent / "configs"
    all_names = set()
    for names in GROUPS.values():
        for name in names:
            cfg = ExperimentConfig.from_file(resolve_config_path(root, name))
            assert cfg.name == name
            all_names.add(name)
    assert "mnist_99" in all_names
    assert EVALUATED_RUNS <= all_names  # evaluator targets are real runs


def test_cli_devices(capsys):
    from distributedmnist_tpu.launch.__main__ import main
    main(["devices"])
    out = json.loads(capsys.readouterr().out)
    assert out["process_count"] == 1
    assert len(out["devices"]) == 8


def test_cli_train_with_overrides(tmp_path, capsys):
    from distributedmnist_tpu.launch.__main__ import main
    main(["train",
          "data.dataset=synthetic", "data.batch_size=64",
          "data.synthetic_train_size=512", "data.synthetic_test_size=128",
          "model.compute_dtype=float32",
          "train.max_steps=6", "train.log_every_steps=3",
          f"train.train_dir={tmp_path}/t", "train.save_interval_steps=0"])
    out = json.loads(capsys.readouterr().out.strip().split("\n")[-1])
    assert out["summary"]["final_step"] == 6
    assert "accuracy" in out["test"]


def test_fetch_dry_run_plans_without_network(tmp_path, capsys):
    """`launch fetch --dry-run` prints the full verify/fetch plan —
    files, mirrors, pinned digests, cache status — with zero network or
    cache mutation (the real-data readiness check, ≙ the reference's
    maybe_download at src/mnist_data.py:176-187)."""
    import json as _json
    from distributedmnist_tpu.data.fixtures import materialize_idx_fixture
    from distributedmnist_tpu.launch.__main__ import main

    d = tmp_path / "cache"
    materialize_idx_fixture(d, num_train=64, num_test=32)
    before = sorted(p.name for p in d.iterdir())
    main(["fetch", "--dataset", "mnist", "--data-dir", str(d), "--dry-run"])
    plan = _json.loads(capsys.readouterr().out)
    assert plan["dataset"] == "mnist"
    assert len(plan["plan"]) == 4
    for entry in plan["plan"]:
        assert entry["pinned_sha256"]          # all four MNIST pins exist
        assert entry["mirrors"]
        # the fixture cache is either uncompressed (not verifiable) or
        # a .gz whose digest differs from the real pins - both non-verified
        assert entry["status"] != "verified"
    assert sorted(p.name for p in d.iterdir()) == before   # untouched


def test_fetch_offline_leaves_fixture_cache_intact(tmp_path, capsys):
    """Without egress, `fetch --verify` must fail loudly (exit 1) and
    restore the quarantined fixture files — fixture runs keep working."""
    import json as _json
    import pytest as _pytest
    from distributedmnist_tpu.data import datasets as DS
    from distributedmnist_tpu.data.fixtures import materialize_idx_fixture
    from distributedmnist_tpu.launch.__main__ import main

    d = tmp_path / "cache"
    materialize_idx_fixture(d, num_train=64, num_test=32)
    before = sorted(p.name for p in d.iterdir())
    # point the mirrors somewhere unreachable without touching the net
    orig = DS._IDX_MIRRORS["mnist"]
    DS._IDX_MIRRORS["mnist"] = [str(tmp_path / "nonexistent") + "/"]
    try:
        with _pytest.raises(SystemExit) as e:
            main(["fetch", "--dataset", "mnist", "--data-dir", str(d),
                  "--verify"])
        assert e.value.code == 1
    finally:
        DS._IDX_MIRRORS["mnist"] = orig
    out = _json.loads(capsys.readouterr().out)
    assert out["ok"] is False
    assert sorted(p.name for p in d.iterdir()) == before
    assert "Fixture dataset" in (d / "PROVENANCE.md").read_text()


def test_fetch_verify_upgrades_fixture_to_real(tmp_path, capsys):
    """The full upgrade flow against a hermetic file:// mirror: fetch
    replaces the fixture with digest-verified archives and rewrites
    PROVENANCE.md to say REAL — the one-command path the day egress
    exists."""
    import gzip
    import hashlib
    import json as _json
    from distributedmnist_tpu.data import datasets as DS
    from distributedmnist_tpu.data.fixtures import materialize_idx_fixture
    from distributedmnist_tpu.launch.__main__ import main

    # the "real" archives: a second fixture, gzipped, served via file://
    mirror = tmp_path / "mirror"
    materialize_idx_fixture(mirror, num_train=96, num_test=48)
    del gzip  # the fixture already writes .gz archives
    pins = {gz.name: hashlib.sha256(gz.read_bytes()).hexdigest()
            for gz in sorted(mirror.glob("*.gz"))}
    assert len(pins) == 4

    d = tmp_path / "cache"
    materialize_idx_fixture(d, num_train=64, num_test=32)
    orig_m, orig_p = DS._IDX_MIRRORS["mnist"], DS._PINNED_SHA256["mnist"]
    DS._IDX_MIRRORS["mnist"] = [mirror.as_uri() + "/"]
    DS._PINNED_SHA256["mnist"] = pins
    try:
        main(["fetch", "--dataset", "mnist", "--data-dir", str(d),
              "--verify"])
    finally:
        DS._IDX_MIRRORS["mnist"] = orig_m
        DS._PINNED_SHA256["mnist"] = orig_p
    out = _json.loads(capsys.readouterr().out)
    assert out["ok"] is True
    assert len(out["verified"]) == 4
    prov = (d / "PROVENANCE.md").read_text()
    assert "Real dataset" in prov and "sha256" in prov
    # the installed archives are the mirror's, digest-verified
    for name, digest in pins.items():
        got = hashlib.sha256((d / name).read_bytes()).hexdigest()
        assert got == digest


def test_fetch_rolls_back_downloads_into_empty_slots(tmp_path, capsys):
    """A failed fetch must also delete archives it downloaded into
    slots that were EMPTY beforehand (no quarantine entry to displace)
    — otherwise a real 96-row train-images coexists with the 64-row
    fixture labels and the next fixture run crashes on count mismatch."""
    import json as _json
    import pytest as _pytest
    from distributedmnist_tpu.data import datasets as DS
    from distributedmnist_tpu.data.fixtures import materialize_idx_fixture
    from distributedmnist_tpu.launch.__main__ import main
    import hashlib

    mirror = tmp_path / "mirror"
    materialize_idx_fixture(mirror, num_train=96, num_test=48)
    pins = {gz.name: hashlib.sha256(gz.read_bytes()).hexdigest()
            for gz in sorted(mirror.glob("*.gz"))}
    (mirror / "train-labels-idx1-ubyte.gz").unlink()  # mirror 404s labels

    d = tmp_path / "cache"
    materialize_idx_fixture(d, num_train=64, num_test=32)
    (d / "train-images-idx3-ubyte.gz").unlink()  # empty slot pre-fetch
    before = {p.name: p.read_bytes() for p in d.iterdir()}
    orig_m, orig_p = DS._IDX_MIRRORS["mnist"], DS._PINNED_SHA256["mnist"]
    DS._IDX_MIRRORS["mnist"] = [mirror.as_uri() + "/"]
    DS._PINNED_SHA256["mnist"] = pins
    try:
        with _pytest.raises(SystemExit):
            main(["fetch", "--dataset", "mnist", "--data-dir", str(d),
                  "--verify"])
    finally:
        DS._IDX_MIRRORS["mnist"] = orig_m
        DS._PINNED_SHA256["mnist"] = orig_p
    assert _json.loads(capsys.readouterr().out)["ok"] is False
    after = {p.name: p.read_bytes() for p in d.iterdir()}
    assert after == before  # the downloaded train-images is GONE


def test_fetch_does_not_relabel_unverified_cache_as_real(tmp_path, capsys):
    """`fetch` (no --verify) over a cache of unpinnable idx files must
    not rewrite PROVENANCE.md: nothing was downloaded or verified, so
    claiming 'Real dataset / Downloaded and installed' would let the
    99% oracle run on synthetic pixels labeled as real."""
    import json as _json
    from distributedmnist_tpu.data.fixtures import materialize_idx_fixture
    from distributedmnist_tpu.launch.__main__ import main

    d = tmp_path / "cache"
    materialize_idx_fixture(d, num_train=64, num_test=32, gzip_files=False)
    prov_before = (d / "PROVENANCE.md").read_text()
    assert "Fixture dataset" in prov_before
    main(["fetch", "--dataset", "mnist", "--data-dir", str(d)])
    out = _json.loads(capsys.readouterr().out)
    assert out["ok"] is True
    assert out["downloaded"] == []
    assert out["provenance_updated"] is False
    assert (d / "PROVENANCE.md").read_text() == prov_before


def test_fetch_recovers_stranded_quarantine(tmp_path, capsys):
    """A crash between quarantine and restore leaves *.quarantine files
    behind; the next fetch must put them back (slot empty) or discard
    them (slot re-filled) before planning — an offline box must never
    need manual renames to get its fixture cache working again."""
    import json as _json
    import pytest as _pytest
    from distributedmnist_tpu.data import datasets as DS
    from distributedmnist_tpu.data.fixtures import materialize_idx_fixture
    from distributedmnist_tpu.launch.__main__ import main

    d = tmp_path / "cache"
    materialize_idx_fixture(d, num_train=64, num_test=32)
    before = sorted(p.name for p in d.iterdir())
    # simulate the interrupted run: one slot stranded mid-quarantine
    gz = d / "train-images-idx3-ubyte.gz"
    gz.rename(gz.with_name(gz.name + ".quarantine"))

    # dry-run only REPORTS (no mutation promised) — and its plan must
    # say the slot will be recovered, not claim a download is needed
    main(["fetch", "--dataset", "mnist", "--data-dir", str(d), "--dry-run"])
    plan = _json.loads(capsys.readouterr().out)
    assert plan["stranded_quarantine"] == [gz.name + ".quarantine"]
    assert (d / (gz.name + ".quarantine")).exists()
    by_file = {e["file"]: e["status"] for e in plan["plan"]}
    assert "stranded quarantine" in by_file["train-images-idx3-ubyte.gz"]
    assert "missing" not in by_file["train-images-idx3-ubyte.gz"]

    # a real (offline, failing) fetch first repairs the cache
    orig = DS._IDX_MIRRORS["mnist"]
    DS._IDX_MIRRORS["mnist"] = [str(tmp_path / "nonexistent") + "/"]
    try:
        with _pytest.raises(SystemExit):
            main(["fetch", "--dataset", "mnist", "--data-dir", str(d),
                  "--verify"])
    finally:
        DS._IDX_MIRRORS["mnist"] = orig
    capsys.readouterr()
    assert sorted(p.name for p in d.iterdir()) == before  # fully restored


def test_fetch_partial_mirror_failure_is_transactional(tmp_path, capsys):
    """If only some archives download, fetch --verify must roll the
    cache back EXACTLY to its pre-fetch state (no mixed real/fixture
    cache that would crash the loader on count mismatches)."""
    import hashlib
    import json as _json
    import pytest as _pytest
    from distributedmnist_tpu.data import datasets as DS
    from distributedmnist_tpu.data.fixtures import materialize_idx_fixture
    from distributedmnist_tpu.launch.__main__ import main

    mirror = tmp_path / "mirror"
    materialize_idx_fixture(mirror, num_train=96, num_test=48)
    pins = {gz.name: hashlib.sha256(gz.read_bytes()).hexdigest()
            for gz in sorted(mirror.glob("*.gz"))}
    # the mirror can only serve half the archives
    (mirror / "train-labels-idx1-ubyte.gz").unlink()
    (mirror / "t10k-labels-idx1-ubyte.gz").unlink()

    d = tmp_path / "cache"
    materialize_idx_fixture(d, num_train=64, num_test=32)
    before = {p.name: p.read_bytes() for p in d.iterdir()}
    orig_m, orig_p = DS._IDX_MIRRORS["mnist"], DS._PINNED_SHA256["mnist"]
    DS._IDX_MIRRORS["mnist"] = [mirror.as_uri() + "/"]
    DS._PINNED_SHA256["mnist"] = pins
    try:
        with _pytest.raises(SystemExit):
            main(["fetch", "--dataset", "mnist", "--data-dir", str(d),
                  "--verify"])
    finally:
        DS._IDX_MIRRORS["mnist"] = orig_m
        DS._PINNED_SHA256["mnist"] = orig_p
    out = _json.loads(capsys.readouterr().out)
    assert out["ok"] is False
    after = {p.name: p.read_bytes() for p in d.iterdir()}
    assert after == before      # byte-identical rollback
