"""``decode_table_blocks_p50`` (PR 31) on the recorded chat run
(``data/v5e_chat_run/``, PR 23's program on a TPU v5 lite): such a run is
a parent of PR 31, its dispatch spans say nothing of a width and its
journal was cut to ``admit``, ``prefill`` and ``decode_finish``, and the
reader has to give the one width that program had, never nothing. What
the change's spans would hold is written into a copy of the trace."""

import copy
import glob
import gzip
import json
import shutil
from pathlib import Path

import pytest

from benchmark import run as run_mod
from benchmark.lib import (cell as cell_lib, program_trace as pt, runtime,
                           trace_reduce as tr)

RUN_ROOT = Path(__file__).parent / "data" / "v5e_chat_run"
CHAT = "opt-1.3b.serve_chat_open"
METRIC = "decode_table_blocks_p50"
BENCH = cell_lib.load_json(cell_lib.ROOT / "BENCHMARK.json")
#: ceil((1024 + 768) / 16): opt-1.3b's full table
FULL = 112.0


def _recorded_trace() -> dict:
    [path] = glob.glob(str(RUN_ROOT / CHAT / "trace" / "*.json.gz"))
    return pt.load(path)


def _journal() -> list[dict]:
    return [json.loads(line) for line in (
        RUN_ROOT / CHAT / "serve" / "serve_log.jsonl").read_text()
        .splitlines()]


def _run_with(tmp_path, monkeypatch, trace=None, journal=None) -> dict:
    """The recorded run, or a copy with another trace or journal, where
    the readers look for this run's files; returns the reduced trace."""
    root = RUN_ROOT
    if trace is not None or journal is not None:
        root = tmp_path / "work"
        shutil.copytree(RUN_ROOT, root)
    if trace is not None:
        [path] = glob.glob(str(root / CHAT / "trace" / "*.json.gz"))
        with gzip.open(path, "wt") as f:
            json.dump(trace, f)
    if journal is not None:
        (root / CHAT / "serve" / "serve_log.jsonl").write_text(
            "".join(json.dumps(r) + "\n" for r in journal))
    monkeypatch.setattr(runtime, "WORK_ROOT", root)
    pt._RUNS.clear()
    return tr.reduce(trace if trace is not None else _recorded_trace())


def _read(reduced: dict):
    return cell_lib.load_reader(METRIC).read(reduced, {})


def _with_blocks(widths) -> dict:
    """The recorded trace as PR 31's program would have made it: each
    dispatch span says the width it handed the step."""
    trace, widths = _recorded_trace(), iter(widths)
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for e in sorted(line["events"], key=lambda e: e[1]):
                if e[0] == pt.SPAN_DISPATCH:
                    e[4]["blocks"] = next(widths)
    return trace


def test_a_parent_reads_its_one_width_from_the_cells_configuration(
        tmp_path, monkeypatch):
    reduced = _run_with(tmp_path, monkeypatch)
    run = pt.this_run(reduced)
    assert not any(r.get("action") == "decode_start"
                   for r in pt.journal(run)[0])
    dispatches = [e for events in pt.spans_by_thread(run["trace"]).values()
                  for e in events if e[0] == pt.SPAN_DISPATCH]
    assert len(dispatches) == 4 and not any("blocks" in e[4]
                                            for e in dispatches)
    assert _read(reduced) == FULL


def test_a_parents_journal_says_its_width_where_it_has_the_record(
        tmp_path, monkeypatch):
    # a whole journal opens with the replica's geometry; another one
    # than the cell's file, to see which is read
    started = {"event": "serve", "action": "decode_start", "slots": 16,
               "block_size": 16, "num_blocks": 769, "max_prompt_len": 512,
               "max_new_tokens": 250, "swap_policy": "pin", "model_step": 0,
               "time": _journal()[0]["time"] - 1.0}
    reduced = _run_with(tmp_path, monkeypatch,
                        journal=[started] + _journal())
    assert _read(reduced) == 48.0          # ceil(762 / 16)


def test_a_program_without_any_span_reads_its_one_width(tmp_path,
                                                        monkeypatch):
    trace = _recorded_trace()
    for plane in trace["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if not e[0].startswith(pt.SPAN_PREFIX)]
    reduced = _run_with(tmp_path, monkeypatch, trace=trace)
    assert not pt.instrumented(pt.this_run(reduced)["trace"])
    assert _read(reduced) == FULL


@pytest.mark.parametrize("widths, median", [
    ([56, 56, 56, 56], 56.0),        # one rung: the closed cell's case
    ([28, 56, 84, 56], 56.0),        # moving between three
    ([84, 84, 56, 112], 84.0),       # nearest rank: a width that was used
    ([112, 112, 112, 112], 112.0),   # sequences that long: the parent's
])
def test_the_change_reads_the_median_of_blocks(tmp_path, monkeypatch,
                                               widths, median):
    reduced = _run_with(tmp_path, monkeypatch, trace=_with_blocks(widths))
    assert _read(reduced) == median


def test_a_lost_dispatch_span_is_nothing_to_read_not_a_width(tmp_path,
                                                             monkeypatch):
    trace = _recorded_trace()
    for plane in trace["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if e[0] != pt.SPAN_DISPATCH]
    reduced = _run_with(tmp_path, monkeypatch, trace=trace)
    assert pt.instrumented(pt.this_run(reduced)["trace"])
    assert _read(reduced) is None


def test_the_reader_reads_no_other_runs_files(tmp_path, monkeypatch):
    reduced = _run_with(tmp_path, monkeypatch)
    other = copy.deepcopy(reduced)
    for m in other["modules"].values():
        m["starts_ms"] = [s + 0.5 for s in m["starts_ms"]]
    with pytest.raises(tr.TraceError, match="not the run"):
        _read(other)


def test_the_entry_is_well_formed_and_the_chat_cell_reports_it(
        tmp_path, monkeypatch):
    # found by name, wherever it stands: later PRs append metrics
    [entry] = [m for m in BENCH["per_layer"] if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "blocks", "better": "lower",
                     "source": "program_counter", "layer": "decode_loop",
                     "moves": "itl_ms_p90", "workloads": [CHAT]}
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"]
                              if m["name"] != METRIC}
    doc = " ".join(cell_lib.load_reader(METRIC).__doc__.split())
    assert "Layer: decode_loop." in doc and "itl_ms_p90" in doc
    cell = cell_lib.load_cell(CHAT)
    assert METRIC in [m["name"] for m in cell.per_layer]
    reduced = _run_with(tmp_path, monkeypatch)
    got = run_mod.per_layer_metrics(
        cell, reduced,
        {"setup_compile_s": 3.0, "weights_ready_s": 30.0,
         "prefill_ms_p50": 39.1, "itl_ms_p50": 163.0, "itl_ms_p99": 248.0,
         "ttft_ms_p50": 122.0, "ttft_ms_p90": 171.0,
         "loadgen_late_ms_p99": 3.2, "decode_bytes_per_step": 3.57e9,
         "peak_hbm_bytes_per_s": 819e9})
    assert got[METRIC] == {"value": FULL, "unit": "blocks"}
    assert len(got) >= 23
