"""``program_trace.py`` and the nine readers built on it, on a recorded
run: ``data/v5e_chat_run/`` is laid out as ``runtime.WORK_ROOT`` is, with
one cell's work directory in it. Its trace is 0.68 s (four whole decode
steps and one prefill) cut with ``trace_reduce.cut`` from the traced part
of ``opt-1.3b.serve_chat_open`` on a TPU v5 lite (PR 23, seed 301) after
``program_trace.load`` had kept each event's facts; its journal is that
run's ``admit``, ``prefill`` and ``decode_finish`` records, its
``load.json`` the window's two bounds. The numbers pinned here were
measured on the chip; the tests check the arithmetic that reads them."""

import copy
import glob
import json
import shutil
from pathlib import Path

import pytest

from benchmark import run as run_mod
from benchmark.lib import (cell as cell_lib, program_trace as pt, runtime,
                           trace_reduce as tr)

RUN_ROOT = Path(__file__).parent / "data" / "v5e_chat_run"
CHAT = "opt-1.3b.serve_chat_open"
BENCH = cell_lib.load_json(cell_lib.ROOT / "BENCHMARK.json")
#: what the chip run's cut reads (my chip run, PR 23)
RECORDED = {
    "admit_wait_ms_p50": 87.479,
    "admit_wait_ms_p90": 148.798,
    "decode_sample_ms_per_iter": 20.544,
    "decode_stream_ms_per_iter": 0.8475,
    "decode_slots_live_p50": 9.0,
    "serve_idle_sample_share": 7.4104,
    "serve_idle_unattributed_share": 1.5015,
    "prefill_cache_write_share_of_busy": 5.0876,
    "decode_attention_ms_per_step": 117.2676,
}
#: the nine in the order BENCHMARK.json lists them (PR 23)
NINE = ["admit_wait_ms_p50", "admit_wait_ms_p90",
        "decode_sample_ms_per_iter", "decode_stream_ms_per_iter",
        "decode_slots_live_p50", "serve_idle_sample_share",
        "serve_idle_unattributed_share",
        "prefill_cache_write_share_of_busy", "decode_attention_ms_per_step"]


def _recorded_trace() -> dict:
    [path] = glob.glob(str(RUN_ROOT / CHAT / "trace" / "*.json.gz"))
    return pt.load(path)


@pytest.fixture()
def recorded(monkeypatch):
    """The recorded run where the readers look for this run's files."""
    monkeypatch.setattr(runtime, "WORK_ROOT", RUN_ROOT)
    pt._RUNS.clear()
    return tr.reduce(_recorded_trace())


def _copy_of_the_run(tmp_path, monkeypatch, trace=None, journal=None):
    """The recorded run with another trace or journal, as work root."""
    root = tmp_path / "work"
    shutil.copytree(RUN_ROOT, root)
    if trace is not None:
        [path] = glob.glob(str(root / CHAT / "trace" / "*.json.gz"))
        import gzip
        with gzip.open(path, "wt") as f:
            json.dump(trace, f)
    if journal is not None:
        (root / CHAT / "serve" / "serve_log.jsonl").write_text(
            "".join(json.dumps(r) + "\n" for r in journal))
    monkeypatch.setattr(runtime, "WORK_ROOT", root)
    pt._RUNS.clear()
    return root


def _read(metric: str, reduced: dict):
    return cell_lib.load_reader(metric).read(reduced, {})


# -- the readers on the recorded run ---------------------------------------

@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_each_new_reader_reads_the_recorded_run(metric, recorded):
    assert _read(metric, recorded) == pytest.approx(RECORDED[metric],
                                                    rel=1e-4)


def test_the_chat_cell_reports_the_old_metrics_and_the_new(recorded):
    cell = cell_lib.load_cell(CHAT)
    got = run_mod.per_layer_metrics(
        cell, recorded,
        {"setup_compile_s": 3.0, "weights_ready_s": 30.0,
         "prefill_ms_p50": 39.1, "itl_ms_p50": 163.0, "itl_ms_p99": 248.0,
         "ttft_ms_p50": 122.0, "ttft_ms_p90": 171.0,
         "loadgen_late_ms_p99": 3.2, "decode_bytes_per_step": 3.57e9,
         "peak_hbm_bytes_per_s": 819e9})
    # the 13 that were there and the nine; a later PR's may stand beside
    assert len(got) >= 22 and set(RECORDED) <= set(got)
    assert [n for n in got if n in RECORDED] == NINE
    # the old readers on the same cut: the step, and what the host adds
    assert got["decode_step_device_ms"]["value"] == pytest.approx(143.07,
                                                                  abs=0.01)
    assert got["decode_iter_ms_p50"]["value"] == pytest.approx(161.7,
                                                               abs=0.1)
    # the first idle gap is the program's span now, not the runtime's
    assert recorded["idle_gaps"][0][0] == "dml.serve.sample"


def test_the_new_entries_are_well_formed_and_for_the_chat_cell_alone():
    # found by name, not by place: a later PR appends metrics of its own
    new = [m for m in BENCH["per_layer"] if m["name"] in RECORDED]
    assert [m["name"] for m in new] == NINE
    layers = {m["layer"] for m in BENCH["per_layer"]
              if m["name"] not in RECORDED}
    for m in new:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # of PR 22's four cells the chat cell alone; a cell a later PR
        # adds may list itself beside it
        assert CHAT in m["workloads"] and m["moves"] == "itl_ms_p90"
        assert not set(m["workloads"]) & {
            "opt-6.7b.train_sync_1chip", "opt-6.7b.train_quorum3of4_4chip",
            "opt-1.3b.serve_decode_closed"}
        assert m["layer"] in layers          # names PERF.md §3 has
        assert m["source"] in ("program_span", "program_counter",
                               "device_trace")
        doc = " ".join(cell_lib.load_reader(m["name"]).__doc__.split())
        assert f"Layer: {m['layer']}." in doc and "itl_ms_p90" in doc
    # no cell's list of metrics has lost one
    for name, count in (("opt-6.7b.train_sync_1chip", 8),
                        ("opt-6.7b.train_quorum3of4_4chip", 10),
                        ("opt-1.3b.serve_decode_closed", 10), (CHAT, 22)):
        assert len(cell_lib.load_cell(name).per_layer) >= count


# -- this run's files, and no other's --------------------------------------

def test_the_helper_finds_the_run_the_numbers_were_reduced_from(recorded):
    run = pt.this_run(recorded)
    assert run["workdir"] == RUN_ROOT / CHAT
    assert pt.this_run(recorded) is run          # loaded once
    records, lo, hi = pt.journal(run)
    assert hi - lo == pytest.approx(40.0, abs=0.01)
    waits = pt.admit_waits_ms(run)
    assert len(waits) == 48 and min(waits) >= 0


def test_the_helper_refuses_a_trace_of_another_run(recorded, tmp_path):
    other = copy.deepcopy(recorded)
    for m in other["modules"].values():
        m["starts_ms"] = [s + 0.5 for s in m["starts_ms"]]
    with pytest.raises(tr.TraceError, match="not the run"):
        pt.this_run(other)
    with pytest.raises(tr.TraceError, match="not the run"):
        pt.this_run({**recorded, "modules": {}})
    with pytest.raises(tr.TraceError, match="no trace of any run"):
        pt.this_run(recorded, root=tmp_path)
    # and a reader says so instead of reading it
    with pytest.raises(tr.TraceError):
        _read("decode_slots_live_p50", other)


# -- a program that predates its spans (what the driver runs as parent) ----

def _without_the_programs_names(trace: dict) -> dict:
    """The recorded trace as PR 23's parent would have made it: no span
    of the program, no scope, the partials' programs ``jit__unknown``."""
    out = copy.deepcopy(trace)
    for plane in out["planes"]:
        for line in plane["lines"]:
            line["events"] = [
                [e[0].replace("jit_decode_step", "jit__unknown")
                 .replace("jit_write_prompt_kv", "jit__unknown"),
                 e[1], e[2], e[3],
                 {**e[4], "op_name": ""} if "op_name" in e[4] else {}]
                for e in line["events"]
                if not e[0].startswith(pt.SPAN_PREFIX)]
    return out


def test_an_uninstrumented_program_reads_nothing_attributed(
        tmp_path, monkeypatch):
    parent_journal = [
        {k: v for k, v in r.items() if k not in ("queue_ms", "prefill_ms")}
        for r in map(json.loads, (RUN_ROOT / CHAT / "serve"
                                  / "serve_log.jsonl").read_text()
                     .splitlines())]
    trace = _without_the_programs_names(_recorded_trace())
    _copy_of_the_run(tmp_path, monkeypatch, trace, parent_journal)
    reduced = tr.reduce(trace)
    assert not pt.instrumented(pt.this_run(reduced)["trace"])
    got = {m: _read(m, reduced) for m in RECORDED}
    # the journal's three older numbers give the same waits to 0.1 ms
    assert got["admit_wait_ms_p50"] == pytest.approx(87.479, abs=0.1)
    assert got["admit_wait_ms_p90"] == pytest.approx(148.798, abs=0.1)
    # occupancy over the whole window, from prefill and finish records
    assert got["decode_slots_live_p50"] == 11.0
    assert got["serve_idle_unattributed_share"] == 100.0
    assert {got[m] for m in (
        "decode_sample_ms_per_iter", "decode_stream_ms_per_iter",
        "serve_idle_sample_share", "prefill_cache_write_share_of_busy",
        "decode_attention_ms_per_step")} == {0.0}
    # so the harness, which fails a run on a reading of nothing, passes
    assert all(v is not None for v in got.values())


@pytest.mark.parametrize("metric, lost", [
    ("decode_sample_ms_per_iter", "dml.serve.sample"),
    ("decode_stream_ms_per_iter", "dml.serve.stream"),
    ("serve_idle_sample_share", "dml.serve.sample"),
    ("decode_slots_live_p50", "dml.serve.step.dispatch"),
    ("decode_attention_ms_per_step", "jit_decode_step")])
def test_a_lost_span_is_nothing_to_read_not_a_zero(metric, lost, tmp_path,
                                                   monkeypatch):
    trace = _recorded_trace()
    for plane in trace["planes"]:
        for line in plane["lines"]:
            line["events"] = [
                [e[0].replace("jit_decode_step", "jit_step"), *e[1:]]
                if lost.startswith("jit_") else e
                for e in line["events"] if e[0] != lost]
    _copy_of_the_run(tmp_path, monkeypatch, trace)
    assert _read(metric, tr.reduce(trace)) is None


# -- names, tables, and the wire format ------------------------------------

@pytest.mark.parametrize("op_name, path, which", [
    ("jit(shard_fn)/jvp(attention)/dot_general", ("attention",), "forward"),
    ("jit(shard_fn)/transpose(jvp(head))/dot_general", ("head",),
     "backward"),
    ("jit(shard_fn)/transpose(jvp(jvp()))/checkpoint/ffn/mul", ("ffn",),
     "backward"),
    ("jit(shard_fn)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "attention/exp", ("attention",), "recomputed"),
    ("jit(decode_step)/attention/cache_gather/gather",
     ("attention", "cache_gather"), "forward"),
    ("jit(shard_fn)/update/update/sub", ("update", "update"), "forward"),
    ("jit(shard_fn)/jit(_threefry_fold_in)/add", (), "forward"),
    ("k_cache", (), "forward"), ("", (), "forward")])
def test_an_operations_name_gives_its_scopes_and_its_pass(op_name, path,
                                                          which):
    assert pt.scope_path(op_name) == path
    assert pt.pass_of(op_name) == which


def test_scope_and_span_tables_of_the_recorded_run(capsys):
    trace = _recorded_trace()
    table = pt.scope_table(trace, pt.DECODE_STEP)
    assert table["executions"] == 4
    assert table["total_ms"] == pytest.approx(143.07, abs=0.01)
    by = table["by_scope"]
    # the float32 views the compiler made between gather and scores
    # carry no name and are attention's by where they run
    assert by[("attention", "unnamed")] == pytest.approx(61.36, abs=0.01)
    assert by[("attention/cache_gather", "forward")] == pytest.approx(
        24.15, abs=0.01)
    # the copies of the whole cache before the first layer stay outside
    assert (by[("(unscoped)", "forward")] + by[("(unscoped)", "unnamed")]
            == pytest.approx(23.34, abs=0.01))
    assert table["longest_unscoped_op_ms"] == pytest.approx(8.206, abs=0.001)
    prefill = pt.scope_table(trace, "jit_decode_prefill")
    assert prefill["by_kernel"] == {"flash_fwd": pytest.approx(1.022,
                                                               abs=0.001)}
    spans = pt.span_table(trace)
    assert spans["dml.serve.step.dispatch"]["count"] == 4
    assert spans["dml.serve.sample"]["count"] == 37
    # a parent's self time is its duration less its children's
    assert spans["dml.serve.prefill"]["total_ms"] == pytest.approx(38.95,
                                                                   abs=0.01)
    assert spans["dml.serve.prefill"]["self_ms"] == pytest.approx(1.363,
                                                                  abs=0.001)
    with pytest.raises(tr.TraceError, match="no whole execution"):
        pt.scope_table(trace, "jit_shard_fn")
    # the profiler stops recording operations before it stops recording
    # programs: an execution with some of its operations missing is left
    # out, not averaged in
    ops = tr._line(tr.device_planes(trace)[0], tr.OPS_LINE)
    last = max(e[1] for e in tr._line(tr.device_planes(trace)[0],
                                      tr.MODULES_LINE)
               if e[0].startswith(pt.DECODE_STEP))
    ops[:] = [e for e in ops if e[1] < last + 100e6]
    cut = pt.scope_table(trace, pt.DECODE_STEP)
    assert cut["executions"] == 3
    assert cut["total_ms"] == pytest.approx(table["total_ms"], abs=0.01)
    [path] = glob.glob(str(RUN_ROOT / CHAT / "trace" / "*.json.gz"))
    pt.describe(path)
    said = capsys.readouterr().out
    assert "dml.serve.sample" in said and "program jit_decode_step" in said
    assert "kernel flash_fwd" in said


def test_the_wire_reader_agrees_with_profile_data(tmp_path):
    """A profile taken here (CPU: host plane only), read field by field
    and by ``jax.profiler.ProfileData``: the same spans at the same
    times, and the facts ProfileData also shows."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation(tr.WINDOW_ANNOTATION):
        with jax.profiler.TraceAnnotation("dml.serve.step.dispatch", live=3,
                                          waiting=0, version=-7):
            with jax.profiler.TraceAnnotation("dml.serve.sample", id="r1",
                                              slot=2):
                float(jnp.ones(4).sum())
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    mine = {e[0]: e for e in tr.host_events(pt.load(path))}
    theirs = {ev.name: ev
              for plane in ProfileData.from_file(path).planes
              if plane.name == tr.HOST_PLANE
              for line in plane.lines for ev in line.events}
    assert mine["dml.serve.step.dispatch"][4] == {"live": 3, "waiting": 0,
                                                  "version": -7}
    assert mine["dml.serve.sample"][4] == {"id": "r1", "slot": 2}
    for name in ("dml.serve.sample", "dml.serve.step.dispatch",
                 tr.WINDOW_ANNOTATION):
        assert mine[name][1] == pytest.approx(theirs[name].start_ns, abs=1)
        assert mine[name][2] == pytest.approx(theirs[name].duration_ns,
                                              abs=1)
    trace = pt.load(path)
    assert list(pt.span_table(trace)) == ["dml.serve.step.dispatch",
                                          "dml.serve.sample"]
    # no device plane on the CPU: the device readings refuse, the tool
    # still prints the spans
    with pytest.raises(tr.TraceError, match="no /device:TPU"):
        pt.device_idle(trace)
    pt.describe(str(tmp_path))
