"""Cluster execution engine: CommandExecutor + backends, exercised with
REAL subprocesses (no mocks of subprocess) — the executed-process
evidence the argv-level pod tests never had (VERDICT gap #1; ≙ the
reference orchestrator actually driving clusters,
tools/tf_ec2.py:237-271, :536-569)."""

import json
import shlex
import time
from pathlib import Path

import pytest

from distributedmnist_tpu.launch.cluster import (LocalClusterConfig,
                                                 LocalProcessCluster,
                                                 make_backend,
                                                 parse_poll_output)
from distributedmnist_tpu.launch.exec import (CommandExecutor, ExecError,
                                              FaultPlan, RetryPolicy)
from distributedmnist_tpu.obsv.journal import load_journal, summarize_journal

pytestmark = pytest.mark.tier1


# ---------------------------------------------------------------------------
# CommandExecutor
# ---------------------------------------------------------------------------

def test_run_real_command_journals_result(tmp_path):
    journal = tmp_path / "journal.jsonl"
    with CommandExecutor(journal=journal) as ex:
        res = ex.run(["sh", "-c", "echo out; echo err >&2"], verb="probe")
    assert res.ok and res.returncode == 0 and res.attempts == 1
    assert res.stdout == "out\n" and res.stderr == "err\n"
    (rec,) = load_journal(journal)
    assert rec["verb"] == "probe" and rec["rc"] == 0
    assert rec["stdout_tail"] == "out\n" and rec["stderr_tail"] == "err\n"
    assert rec["duration_ms"] > 0 and rec["attempt"] == 1
    assert rec["will_retry"] is False


def test_nonzero_rc_raises_with_check_and_not_without(tmp_path):
    ex = CommandExecutor(retry=RetryPolicy(max_attempts=1))
    res = ex.run(["sh", "-c", "echo boom >&2; exit 3"], check=False)
    assert not res.ok and res.returncode == 3
    with pytest.raises(ExecError, match=r"rc=3"):
        ex.run(["sh", "-c", "exit 3"])


def test_timeout_is_a_failure(tmp_path):
    ex = CommandExecutor(retry=RetryPolicy(max_attempts=1), timeout_s=0.2)
    t0 = time.monotonic()
    res = ex.run(["sh", "-c", "sleep 30"], check=False)
    assert time.monotonic() - t0 < 10  # the hung command did not hang us
    assert res.timed_out and res.returncode is None and not res.ok
    with pytest.raises(ExecError, match="timed out"):
        ex.run(["sh", "-c", "sleep 30"])


def test_missing_binary_is_permanent_no_retries(tmp_path):
    journal = tmp_path / "journal.jsonl"
    ex = CommandExecutor(journal=journal, retry=RetryPolicy(max_attempts=5))
    with pytest.raises(ExecError, match="not found"):
        ex.run(["dmt-no-such-binary-for-test"])
    recs = load_journal(journal)
    assert len(recs) == 1 and recs[0]["error"] == "binary not found"


def test_retry_backoff_recovers_transient_failure(tmp_path):
    """(a) of the fault-injection acceptance: first n attempts of a verb
    fail (synthesized by the plan), the retry/backoff budget absorbs
    them, and the REAL command then runs and succeeds."""
    journal = tmp_path / "journal.jsonl"
    delays: list[float] = []
    ex = CommandExecutor(
        journal=journal,
        retry=RetryPolicy(max_attempts=3, backoff_s=0.05, multiplier=2.0,
                          jitter_frac=0.25, seed=0),
        fault_plan=FaultPlan(fail_first={"flaky": 2}),
        sleep=delays.append)
    res = ex.run(["echo", "recovered"], verb="flaky")
    assert res.ok and res.attempts == 3 and res.stdout == "recovered\n"
    # exponential backoff with ±25% jitter: two retry sleeps
    assert len(delays) == 2
    assert 0.05 * 0.75 <= delays[0] <= 0.05 * 1.25
    assert 0.10 * 0.75 <= delays[1] <= 0.10 * 1.25
    recs = load_journal(journal)
    assert [r["attempt"] for r in recs] == [1, 2, 3]
    assert [r["will_retry"] for r in recs] == [True, True, False]
    assert recs[0]["injected"] and recs[1]["injected"] and not recs[2]["injected"]
    s = summarize_journal(journal)
    assert s["commands"] == 1 and s["attempts"] == 3
    assert s["retries"] == 2 and s["failures"] == 0 and s["injected"] == 2


def test_retry_budget_exhausted_raises(tmp_path):
    journal = tmp_path / "journal.jsonl"
    ex = CommandExecutor(
        journal=journal,
        retry=RetryPolicy(max_attempts=2, backoff_s=0.0, jitter_frac=0.0),
        fault_plan=FaultPlan(fail_first={"flaky": 99}))
    with pytest.raises(ExecError, match=r"after 2 attempt"):
        ex.run(["echo", "never"], verb="flaky")
    s = summarize_journal(journal)
    assert s["failures"] == 1 and s["retries"] == 1


def test_fault_delay_applies_to_command_class():
    slept: list[float] = []
    ex = CommandExecutor(fault_plan=FaultPlan(delay_ms={"probe": 40.0}),
                         sleep=slept.append)
    ex.run(["true"], verb="probe")
    ex.run(["true"], verb="other")
    assert slept == [0.04]  # only the targeted class is delayed


def test_dry_run_records_and_journals_without_executing(tmp_path):
    journal = tmp_path / "journal.jsonl"
    ex = CommandExecutor(journal=journal, dry_run=True)
    assert ex.run(["definitely-not-a-binary", "--flag"]) is None
    assert ex.recorded == [["definitely-not-a-binary", "--flag"]]
    recs = json.loads(journal.read_text().splitlines()[0])
    assert recs["dry_run"] is True
    assert summarize_journal(journal)["dry_run"] == 1


def test_fault_plan_file_roundtrip(tmp_path):
    p = tmp_path / "plan.json"
    p.write_text(json.dumps({"fail_first": {"create": 1},
                             "delay_ms": {"poll": 5},
                             "kill_worker_at_step": {"1": 7}}))
    plan = FaultPlan.from_file(p)
    assert plan.should_fail("create", 1) and not plan.should_fail("create", 2)
    assert plan.command_delay_s("poll") == 0.005
    assert plan.kill_worker_at_step == {1: 7}  # JSON str keys → int
    p.write_text(json.dumps({"kill_wroker": {}}))
    with pytest.raises(ExecError, match="kill_wroker"):
        FaultPlan.from_file(p)


def test_parse_poll_output_torn_and_empty():
    assert parse_poll_output(None) == {"step": -1, "record": None}
    assert parse_poll_output("") == {"step": -1, "record": None}
    assert parse_poll_output('{"step": 8, "loss"') == {"step": -1,
                                                      "record": None}
    got = parse_poll_output('{"step": 12, "loss": 0.5}\n')
    assert got["step"] == 12 and got["record"]["loss"] == 0.5


def test_parse_poll_output_scans_back_past_torn_tail():
    """A torn final line (the writer mid-append) must not make live
    progress look stalled for a whole poll tick: the parser scans
    backwards to the last INTACT record in the tail window."""
    got = parse_poll_output('{"step": 11, "loss": 0.7}\n'
                            '{"step": 12, "loss": 0.5}\n'
                            '{"step": 13, "lo')
    assert got["step"] == 12 and got["record"]["loss"] == 0.5
    # nothing intact in the window at all → still -1
    assert parse_poll_output('garbage\n{"step": 9,')["step"] == -1


# ---------------------------------------------------------------------------
# LocalProcessCluster verbs (each one a real subprocess)
# ---------------------------------------------------------------------------

def _local(tmp_path, **cfg_kw) -> LocalProcessCluster:
    cfg_kw.setdefault("num_workers", 2)
    cfg = LocalClusterConfig(name="t", workdir=str(tmp_path / "cl"), **cfg_kw)
    return LocalProcessCluster(cfg)


def test_create_makes_worker_dirs_and_state(tmp_path):
    c = _local(tmp_path)
    c.create()
    assert c.cfg.worker_dir(0).is_dir() and c.cfg.worker_dir(1).is_dir()
    state = json.loads(c.state_path.read_text())
    assert state["phase"] == "created"
    assert [w["worker"] for w in state["workers"]] == [0, 1]
    got = c.status()
    assert got["state"] == "CREATED" and got["idle"] is True
    assert all(not w["alive"] for w in got["workers"])


def test_exec_all_runs_in_each_worker_dir(tmp_path):
    c = _local(tmp_path)
    c.create()
    c.exec_all("echo payload-$DMT_WORKER_INDEX > touched.txt")
    for k in range(2):
        assert (c.cfg.worker_dir(k) / "touched.txt").read_text().strip() \
            == f"payload-{k}"
    c.exec_all("rm touched.txt", worker="1")
    assert (c.cfg.worker_dir(0) / "touched.txt").exists()
    assert not (c.cfg.worker_dir(1) / "touched.txt").exists()


def test_worker_env_passes_the_compile_cache_variable_through(
        tmp_path, monkeypatch):
    """core/compile_cache.py's one rule on the worker side: a set
    JAX_COMPILATION_CACHE_DIR reaches every worker untouched (jax reads
    it itself), nothing else is planted over it, and unset stays unset
    (each worker then resolves the same fixed in-checkout path)."""
    c = _local(tmp_path)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    env = c._worker_env(0)
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/placed/from/outside"
    assert "DMT_COMPILE_CACHE_DIR" not in env
    assert env["JAX_PLATFORMS"] == "cpu"  # the cluster tier stays off chips
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert "JAX_COMPILATION_CACHE_DIR" not in c._worker_env(1)


def test_poll_reads_worker0_structured_log(tmp_path):
    c = _local(tmp_path)
    c.create()
    assert c.poll() == {"step": -1, "record": None}  # log not there yet
    (c.cfg.worker_dir(0) / "train_log.jsonl").write_text(
        json.dumps({"step": 3}) + "\n" + json.dumps({"step": 7}) + "\n")
    assert c.poll()["step"] == 7


def test_download_copies_worker_dir(tmp_path):
    c = _local(tmp_path)
    c.create()
    (c.cfg.worker_dir(0) / "train_log.jsonl").write_text('{"step": 1}\n')
    dest = tmp_path / "dl"
    c.download(dest)
    assert (dest / "worker0" / "train_log.jsonl").exists()


def test_delete_marks_state_and_journal_parses(tmp_path):
    c = _local(tmp_path)
    c.create()
    c.delete()
    assert c.status()["state"] == "DELETED"
    s = summarize_journal(c.exec.journal_path)
    assert s["failures"] == 0 and s["commands"] >= 1
    assert "create" in s["by_verb"]


def test_make_backend_pluggability(tmp_path):
    from distributedmnist_tpu.launch.cluster import (ClusterError,
                                                     GcloudTpuBackend)
    ex = CommandExecutor(dry_run=True)
    assert isinstance(make_backend("local", None, ex), LocalProcessCluster)
    assert isinstance(make_backend("gcloud", None, ex), GcloudTpuBackend)
    with pytest.raises(ClusterError, match="unknown backend"):
        make_backend("k8s", None, ex)


def test_cluster_config_file_roundtrip_and_unknown_key(tmp_path):
    from distributedmnist_tpu.launch.cluster import ClusterError
    p = tmp_path / "cluster.json"
    p.write_text(json.dumps({"name": "x", "num_workers": 3}))
    cfg = LocalClusterConfig.from_file(p)
    assert (cfg.name, cfg.num_workers) == ("x", 3)
    p.write_text(json.dumps({"num_wrokers": 3}))
    with pytest.raises(ClusterError, match="num_wrokers"):
        LocalClusterConfig.from_file(p)


def test_repo_cluster_configs_parse():
    """The committed cluster/fault JSONs must load via the same safe
    parsers the CLI uses."""
    root = Path(__file__).resolve().parents[1] / "configs" / "cluster"
    cfg = LocalClusterConfig.from_file(root / "local_2w.json")
    assert cfg.num_workers == 2
    plan = FaultPlan.from_file(root / "fault_kill_worker1_at_step10.json")
    assert plan.kill_worker_at_step == {1: 10}


def test_cluster_cli_dry_run_prints_commands(tmp_path, capsys, monkeypatch):
    from distributedmnist_tpu.launch.cluster import main
    monkeypatch.chdir(tmp_path)
    cfgp = tmp_path / "c.json"
    cfgp.write_text(json.dumps({"workdir": str(tmp_path / "w")}))
    main(["create", "--backend", "local", "--config", str(cfgp), "--dry-run"])
    cmds = json.loads(capsys.readouterr().out)
    assert any(c.startswith("sh -c") and "mkdir -p" in c for c in cmds)


def test_launch_cli_delegates_cluster(tmp_path, capsys):
    from distributedmnist_tpu.launch.__main__ import main
    cfgp = tmp_path / "c.json"
    cfgp.write_text(json.dumps({"workdir": str(tmp_path / "w")}))
    main(["cluster", "create", "--backend", "local",
          "--config", str(cfgp), "--dry-run"])
    assert "mkdir" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# full lifecycle with the REAL `launch train` payload (slow: boots jax
# in each worker) — the executed-process closure of VERDICT gap #1
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_lifecycle_smoke_real_train(tmp_path):
    # no PYTHONPATH in env: the backend itself must make this package
    # importable from the workers' logdir cwds (the README CLI recipe
    # runs exactly this way, with nothing pip-installed)
    cfg = LocalClusterConfig(
        name="smoke", num_workers=2, workdir=str(tmp_path / "cl"),
        train_command=(
            "python -m distributedmnist_tpu.launch train "
            "train.train_dir=. data.dataset=synthetic data.batch_size=16 "
            "data.synthetic_train_size=64 data.synthetic_test_size=32 "
            "model.compute_dtype=float32 train.max_steps=8 "
            "train.log_every_steps=1 train.save_interval_steps=0"))
    c = LocalProcessCluster(cfg)
    from distributedmnist_tpu.launch.cluster import run_until_step
    c.create()
    got = run_until_step(c, target=4, poll_secs=1.0, timeout_secs=600.0)
    assert got["step"] >= 4 and got["record"] is not None
    dest = tmp_path / "dl"
    c.download(dest)
    assert (dest / "worker0" / "train_log.jsonl").exists()
    c.delete()
    assert c.status()["state"] == "DELETED" and c.status()["idle"]
    recs = load_journal(c.exec.journal_path)
    verbs = {r["verb"] for r in recs}
    assert {"create", "poll", "download"} <= verbs
