"""``benchmark/lib/host_gaps.py`` and the eleven readers built on it
(PR 40), on the two recorded v5e traces that hold the program's spans
and on cut-down and altered copies of them: ``data/
v5e_paged_decode_two_steps.json.gz`` (PR 39's program: two steps, the
second dispatch without its fetch) and ``data/v5e_chat_run/`` (PR 23's:
four steps, one prefill, a token sampled a slot, laid out as
``runtime.WORK_ROOT`` is). The numbers pinned here were measured on the
chip; the tests check the arithmetic that reads them, and that no
number but the offset's own moves when one plane is moved against the
other."""

import copy
import glob
import gzip
import json
from pathlib import Path

import pytest

from benchmark import run as run_mod
from benchmark.lib import (cell as cell_lib, host_gaps as hg,
                           program_trace as pt, runtime, trace_reduce as tr)

DATA = Path(__file__).parent / "data"
CLOSED, CHAT = "opt-1.3b.serve_decode_closed", "opt-1.3b.serve_chat_open"
LATENT = "openpangu-ultra-moe-718b.serve_reason_closed"
BENCH = cell_lib.load_json(cell_lib.ROOT / "BENCHMARK.json")
#: the eleven, in ``host_gaps.READERS``' order: name -> (unit, source,
#: layer) of its entry
ELEVEN = {
    "decode_gap_ms_p50": ("ms", "device_trace", "decode_loop"),
    "decode_gap_ms_p90": ("ms", "device_trace", "decode_loop"),
    "decode_gap_beneath_ms": ("ms", "program_span", "decode_loop"),
    "decode_gap_emit_ms": ("ms", "program_span", "decode_loop"),
    "decode_gap_inputs_ms": ("ms", "program_span", "decode_loop"),
    "decode_gap_rest_ms": ("ms", "program_span", "decode_loop"),
    "trace_clock_offset_ms": ("ms", "device_trace", "device"),
    "serve_idle_no_request_share": ("%", "program_span", "device"),
    "decode_loop_host_share": ("%", "program_counter", "decode_loop"),
    "decode_cache_write_ms_per_step": ("ms", "device_trace", "model_step"),
    "decode_paged_kernel_ms_per_step": ("ms", "device_trace",
                                        "attention_kernels"),
}
#: what the recorded chat run reads (PR 23's program on the chip)
CHAT_READS = {
    "decode_gap_ms_p50": 15.1232, "decode_gap_ms_p90": 18.6157,
    "decode_gap_beneath_ms": 2.4308, "decode_gap_emit_ms": 13.1569,
    "decode_gap_inputs_ms": 1.0094, "decode_gap_rest_ms": 0.2723,
    "trace_clock_offset_ms": 0.7015, "serve_idle_no_request_share": 0.0,
    "decode_loop_host_share": 14.7071,
    "decode_cache_write_ms_per_step": 0.1450,
    "decode_paged_kernel_ms_per_step": 0.0,
}
MS = 1e6          # ns


def _paged() -> dict:
    return pt.load(str(DATA / "v5e_paged_decode_two_steps.json.gz"))


def _chat() -> dict:
    [path] = glob.glob(str(DATA / "v5e_chat_run" / CHAT / "trace"
                           / "*.json.gz"))
    return pt.load(path)


def _moved(trace: dict, ns: float, device: bool, after: float = 0.0) -> dict:
    """The trace with one side's events that start after ``after`` moved
    later by ``ns``: the device planes, or the host plane less the
    benchmark's own window annotation."""
    out = copy.deepcopy(trace)
    for plane in out["planes"]:
        if (plane["name"] != tr.HOST_PLANE) != device:
            continue
        for line in plane["lines"]:
            for e in line["events"]:
                if e[0] != tr.WINDOW_ANNOTATION and e[1] >= after:
                    e[1] += ns
    return out


def _without(trace: dict, *names: str) -> dict:
    out = copy.deepcopy(trace)
    for plane in out["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if e[0] not in names]
    return out


def _with_span(trace: dict, name: str, start_ms: float, dur_ms: float,
               beside: str = pt.SPAN_DISPATCH) -> dict:
    """One more span on the batcher thread's line."""
    out = copy.deepcopy(trace)
    for plane in out["planes"]:
        for line in plane["lines"]:
            if any(e[0] == beside for e in line["events"]):
                line["events"].append([name, start_ms * MS, dur_ms * MS,
                                       "", {}])
    return out


def _as_run(tmp_path, monkeypatch, trace: dict, cell: str = CHAT) -> dict:
    """The trace where the readers look for this run's files; what they
    are handed."""
    (tmp_path / cell / "trace").mkdir(parents=True)
    with gzip.open(tmp_path / cell / "trace" / "t.json.gz", "wt") as f:
        json.dump(trace, f)
    monkeypatch.setattr(runtime, "WORK_ROOT", tmp_path)
    pt._RUNS.clear()
    return tr.reduce(trace)


def _read_all(reduced: dict) -> dict:
    return {name: cell_lib.load_reader(name).read(reduced, {})
            for name in ELEVEN}


# -- the gap and its parts on the recorded traces ---------------------------

def test_the_recorded_paged_trace_reads_what_the_chip_run_did():
    found = hg.join(_paged())
    assert (found["steps"], found["calls"], found["matched"]) == (2, 2, 2)
    # the recording ends on a dispatch whose fetch the window cut
    assert found["cut"] == {"steps_before": 0, "calls_before": 0,
                            "steps_after": 0, "calls_after": 0,
                            "last_call_without_fetch": 1}
    [row] = found["iterations"]
    assert row["plain"]
    ms = {k: round(v / MS, 3) for k, v in row.items() if k != "plain"}
    assert (ms["gap"], ms["between"], ms["beneath"]) == (3.238, 1.554, 1.684)
    assert (ms["emit"], ms["inputs"], ms["rest"]) == (0.771, 0.651, 0.132)
    assert round(found["offset_lo"] / MS, 3) == 0.601
    assert round(found["offset_hi"] / MS, 3) == 2.121


@pytest.mark.parametrize("trace", [_paged, _chat])
def test_the_four_parts_add_up_to_each_gap(trace):
    found = hg.join(trace())
    assert found["iterations"]
    for row in found["iterations"]:
        assert sum(row[k] for k in hg.PARTS) == pytest.approx(row["gap"],
                                                              abs=1e-3)
        assert row["rest"] >= row["admit"] + row["heartbeat"] - 1e-3
        assert min(row[k] for k in hg.PARTS) >= 0.0
    assert found["offset_lo"] <= found["offset_hi"]


def test_an_iteration_that_holds_a_prefill_is_not_plain():
    found = hg.join(_chat())
    assert [r["plain"] for r in found["iterations"]] == [False, True, True]
    assert [round(r["gap"] / MS, 1) for r in found["iterations"]] == [
        55.6, 18.6, 15.1]
    # the device's plane alone says the same, from `reduce`'s modules
    assert [round(g, 1) for g in hg.device_gaps_ms(tr.reduce(_chat()))] == [
        18.6, 15.1]
    # ... and with the prefill's two programs gone, the gap is plain
    bare = _without(_chat(), *[
        e[0] for e in tr._line(tr.device_planes(_chat())[0], tr.MODULES_LINE)
        if pt.program_name(e[0]) in hg.PREFILL_PROGRAMS])
    assert len(hg.device_gaps_ms(tr.reduce(bare))) == 3


@pytest.mark.parametrize("steps, others, want", [
    ([(0, 10), (13, 10), (26, 10)], [], [(3, True), (3, True)]),
    # a prefill between the second and the third; a park is one too,
    # since the next step follows the arrival's prefill
    ([(0, 10), (13, 10), (60, 10)], [30], [(3, True), (37, False)]),
    # given in any order; one that began before the first step ended or
    # after the last began touches no gap
    ([(26, 10), (0, 10), (13, 10)], [40, -5], [(3, True), (3, True)]),
    ([(0, 10)], [5], []),
])
def test_gaps_between_neighbours(steps, others, want):
    assert hg.gaps_between(steps, others) == want


def test_two_fingerprints_of_the_step_join_as_one_sequence():
    trace = _chat()
    modules = tr._line(tr.device_planes(trace)[0], tr.MODULES_LINE)
    mine = sorted((e for e in modules
                   if pt.program_name(e[0]) == pt.DECODE_STEP),
                  key=lambda e: e[1])
    for e in mine[2:]:                   # another table width from here
        e[0] = f"{pt.DECODE_STEP}(4242)"
    assert len(pt.executions(trace, pt.DECODE_STEP)[1]) == 2
    found, whole = hg.join(trace), hg.join(_chat())
    assert found["matched"] == 4
    assert found["iterations"] == whole["iterations"]
    assert hg.device_gaps_ms(tr.reduce(trace)) == hg.device_gaps_ms(
        tr.reduce(_chat()))


# -- the window's edges -----------------------------------------------------

def test_a_step_cut_at_the_front_leaves_its_call_without_one():
    trace = _chat()
    # between the first execution's start (326.786) and its dispatch's
    # (327.436): the device plane lies early, so the edge cuts the step
    small = tr.cut(trace, 327.0 * MS, 1000.0 * MS)
    found = hg.join(small)
    assert (found["steps"], found["calls"], found["matched"]) == (3, 4, 3)
    assert found["cut"]["calls_before"] == 1
    assert [round(r["gap"] / MS, 1) for r in found["iterations"]] == [
        18.6, 15.1]


def test_a_fetch_cut_at_the_back_ends_the_iterations_before_it():
    # inside the last fetch (846.398..991.329) and the last step
    small = tr.cut(_chat(), 320.0 * MS, 900.0 * MS)
    found = hg.join(small)
    assert (found["steps"], found["calls"], found["matched"]) == (3, 4, 3)
    assert found["cut"]["calls_after"] == 1
    assert [r["plain"] for r in found["iterations"]] == [False, True]


def test_steps_and_calls_that_cannot_be_aligned_raise():
    # every host span 40 ms late: no step within 5 ms of a call
    with pytest.raises(tr.TraceError, match="cannot be aligned"):
        hg.join(_moved(_chat(), 40 * MS, device=False))


def test_too_many_dispatches_without_their_fetch_raise():
    trace = _chat()
    for plane in trace["planes"]:
        for line in plane["lines"]:
            fetches = [e for e in line["events"] if e[0] == hg.SPAN_FETCH]
            line["events"] = [e for e in line["events"]
                              if e not in fetches[:2]]
    with pytest.raises(tr.TraceError, match="have no dml.serve.step.fetch"):
        hg.join(trace)


# -- the two clocks ---------------------------------------------------------

def test_a_device_plane_moved_past_offset_hi_raises():
    # the recording's bracket is 0.601..2.121 ms: moved later by more,
    # a step ends after the fetch that waited for it has returned
    for later in (2.2, 5.0):
        with pytest.raises(tr.TraceError, match="more than a constant"):
            hg.join(_moved(_paged(), later * MS, device=True))
    # the chat recording's is 0.701..2.771: moved 2 ms later it is
    # 0..0.771, and no other number of the join knows, all of them
    # differences within a clock
    inside, as_recorded = (hg.join(_moved(_chat(), 2.0 * MS, device=True)),
                           hg.join(_chat()))
    assert inside["offset_lo"] == 0.0
    assert inside["offset_hi"] == pytest.approx(0.771 * MS, abs=1e3)
    for a, b in zip(inside["iterations"], as_recorded["iterations"],
                    strict=True):
        # but those that say so: where the step began, and either
        # clock's raw reading of the other's instant
        for raw, by in (("at", 2.0), ("launch_raw", 2.0),
                        ("pickup_raw", -2.0)):
            assert a.pop(raw) == pytest.approx(b.pop(raw) + by * MS,
                                               abs=1e-3)
        assert a == pytest.approx(b, abs=1e-3)


def test_a_host_slower_between_two_calls_than_the_gap_raises():
    trace = _paged()
    found = hg.join(trace)
    fetch_end = hg._end(found["pairs"][0][2])
    slower = found["iterations"][0]["beneath"] + 1 * MS
    with pytest.raises(tr.TraceError, match="the join is wrong"):
        hg.join(_moved(trace, slower, device=False, after=fetch_end))
    # up to the gap itself it is a reading, with nothing beneath
    level = hg.join(_moved(trace, slower - 1 * MS, device=False,
                           after=fetch_end))
    assert level["iterations"][0]["beneath"] == pytest.approx(0.0, abs=1.0)


def _parked(trace: dict) -> dict:
    """The chat recording with a park on the queue that straddles the
    edge of one of the chip's idle intervals (synthetic: the recorded
    window never idles)."""
    return _with_span(trace, hg.SPAN_IDLE, 485.45, 1.2)


def test_moving_one_plane_moves_the_offset_and_no_other_number(
        tmp_path, monkeypatch):
    base = _read_all(_as_run(tmp_path / "a", monkeypatch,
                             _parked(_chat())))
    assert base["serve_idle_no_request_share"] > 0.0
    early = _read_all(_as_run(tmp_path / "b", monkeypatch,
                              _moved(_parked(_chat()), -0.3 * MS,
                                     device=True)))
    assert (early["trace_clock_offset_ms"] - base["trace_clock_offset_ms"]
            == pytest.approx(0.3, abs=1e-6))
    for name in ELEVEN:
        if name != "trace_clock_offset_ms":
            assert early[name] == pytest.approx(base[name], abs=1e-6), name
    # the accepted readers that intersect the planes as recorded do move
    read = cell_lib.load_reader("serve_idle_sample_share").read
    pt._RUNS.clear()
    there = read(_as_run(tmp_path / "c", monkeypatch, _chat()), {})
    moved = read(_as_run(tmp_path / "d", monkeypatch,
                         _moved(_chat(), -0.3 * MS, device=True)), {})
    assert abs(moved - there) > 1e-3


# -- what is missing ---------------------------------------------------------

def test_a_dropped_dispatch_span_reads_nothing(tmp_path, monkeypatch):
    trace = _without(_chat(), pt.SPAN_DISPATCH)
    assert hg.join(trace) is None
    got = _read_all(_as_run(tmp_path, monkeypatch, trace))
    # the device's plane alone still has its gaps, the step its scopes
    for name in ("decode_gap_ms_p50", "decode_gap_ms_p90",
                 "decode_cache_write_ms_per_step",
                 "decode_paged_kernel_ms_per_step"):
        assert got.pop(name) is not None, name
    assert set(got.values()) == {None}


def test_a_trace_without_the_step_reads_nothing(tmp_path, monkeypatch):
    trace = _chat()
    gone = [e[0] for e in tr._line(tr.device_planes(trace)[0],
                                   tr.MODULES_LINE)
            if pt.program_name(e[0]) == pt.DECODE_STEP]
    trace = _without(trace, *gone)
    assert hg.join(trace) is None
    reduced = _as_run(tmp_path, monkeypatch, trace)
    for name in ELEVEN:
        if not name.startswith("decode_gap_ms_p"):    # another program's
            assert cell_lib.load_reader(name).read(reduced, {}) is None, name


def test_a_program_without_spans_reads_nothing_attributed(tmp_path,
                                                          monkeypatch):
    """PR 23's parent, which the driver also runs with these readers:
    no run directory is looked for (there is none here)."""
    monkeypatch.setattr(runtime, "WORK_ROOT", tmp_path)
    pt._RUNS.clear()
    bare = _chat()
    for plane in bare["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if not e[0].startswith(pt.SPAN_PREFIX)]
    reduced = tr.reduce(bare)
    assert not hg.instrumented(reduced)
    got = _read_all(reduced)
    assert got.pop("decode_gap_ms_p50") == pytest.approx(15.1232, abs=1e-3)
    assert got.pop("decode_gap_ms_p90") == pytest.approx(18.6157, abs=1e-3)
    assert set(got.values()) == {0.0}


def test_a_trace_without_the_heartbeat_span_reads_as_one_with_it():
    # in the paged recording's one gap, under no other span
    with_it = hg.join(_with_span(_paged(), hg.SPAN_HEARTBEAT, 1090.0, 0.2))
    without = hg.join(_paged())
    [a], [b] = with_it["iterations"], without["iterations"]
    assert a["heartbeat"] == pytest.approx(0.2 * MS) and b["heartbeat"] == 0
    assert {k: a[k] for k in hg.PARTS + ("gap", "between")} == {
        k: b[k] for k in hg.PARTS + ("gap", "between")}


# -- the loop's own clock -----------------------------------------------------

def _beat(t, steps, wall, **phases):
    loop = {"idle": 0.0, "admit": 0.0, "prefill": 0.0, "inputs": 0.0,
            "dispatch": 0.0, "fetch": 0.0, "emit": 0.0, **phases}
    return {"event": "heartbeat", "step": steps, "time": t,
            "decode_steps": steps, "loop_s": loop, "loop_wall_s": wall}


def _workdir(tmp_path, beats) -> Path:
    (tmp_path / "serve").mkdir(parents=True)
    (tmp_path / "load.json").write_text(json.dumps(
        {"window_start": 100.0, "window_end": 140.0}))
    (tmp_path / "serve" / "train_log.jsonl").write_text(
        "".join(json.dumps(b) + "\n" for b in beats))
    return tmp_path


def test_two_heartbeats_give_ms_an_iteration_by_phase(tmp_path):
    beats = [_beat(90.0, 10, 1.0, fetch=0.5),           # before the window
             _beat(101.0, 100, 10.0, idle=2.0, fetch=6.0, emit=1.0),
             {"event": "heartbeat", "step": 7, "time": 110.0},   # no clock
             _beat(120.0, 600, 18.0, idle=4.0, fetch=10.0, emit=1.5,
                   inputs=0.5),
             _beat(130.0, 1100, 26.0, idle=4.0, fetch=16.0, emit=2.0,
                   inputs=1.0, dispatch=0.5),
             _beat(150.0, 9999, 99.0, fetch=99.0)]      # after it
    inside = hg.heartbeats(_workdir(tmp_path, beats))
    assert [b["time"] for b in inside] == [101.0, 120.0, 130.0]
    clock = hg.loop_clock(inside)
    assert clock["steps"] == 1000 and clock["wall_s"] == 16.0
    assert clock["phases_s"] == pytest.approx(
        {"idle": 2.0, "admit": 0.0, "prefill": 0.0, "inputs": 1.0,
         "dispatch": 0.5, "fetch": 10.0, "emit": 1.0, "other": 1.5})
    # 10 s of fetch in the 14 s that had a request
    assert hg.host_share(10.0, 16.0, 2.0) == pytest.approx(100 * 4 / 14)
    assert hg.host_share(0.0, 2.0, 2.0) is None
    assert hg.loop_clock(inside[:1]) is None
    assert hg.loop_clock([inside[0], inside[0]]) is None    # no step


def test_the_host_share_reads_the_heartbeats_and_else_the_spans(
        tmp_path, monkeypatch):
    reduced = _as_run(tmp_path / "root", monkeypatch, _chat())
    read = cell_lib.load_reader("decode_loop_host_share").read
    from_spans = read(reduced, {})
    assert from_spans == pytest.approx(CHAT_READS["decode_loop_host_share"],
                                       abs=1e-3)
    # a parent's heartbeats carry no clock: still the spans
    _workdir(tmp_path / "root" / CHAT,
             [{"event": "heartbeat", "step": 1, "time": 105.0},
              {"event": "heartbeat", "step": 2, "time": 125.0}])
    assert read(reduced, {}) == from_spans
    (tmp_path / "root" / CHAT / "serve" / "train_log.jsonl").write_text(
        "".join(json.dumps(b) + "\n" for b in (
            _beat(101.0, 100, 10.0, idle=2.0, fetch=6.0),
            _beat(130.0, 1100, 26.0, idle=4.0, fetch=16.0))))
    assert read(reduced, {}) == pytest.approx(100 * 4 / 14)


# -- the readers as files and entries, and the cells beside them -------------
# ``BENCHMARK.json`` lists the eleven since PR 42, appended after the
# accepted entries (the driver's check reads an entry put before the
# last as a change to the last); each is found by name.

@pytest.mark.parametrize("name", list(ELEVEN))
def test_each_reader_is_a_file_that_names_its_layer_and_is_listed(name):
    unit, source, layer = ELEVEN[name]
    [entry] = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert (entry["unit"], entry["source"], entry["layer"]) == (
        unit, source, layer)
    assert entry["moves"] == "itl_ms_p90" and entry["better"] == "lower"
    # both ``opt-1.3b`` serving cells; the latent cell where its step
    # has what the reader reads (no ``paged_decode`` runs there)
    assert {CLOSED, CHAT} <= set(entry["workloads"]) <= {CLOSED, CHAT, LATENT}
    assert (LATENT in entry["workloads"]) == (
        name != "decode_paged_kernel_ms_per_step")
    others = [m for m in BENCH["per_layer"] if m["name"] not in ELEVEN]
    assert layer in {m["layer"] for m in others}
    doc = " ".join(cell_lib.load_reader(name).__doc__.split())
    assert f"Layer: {layer}." in doc and "itl_ms_p90" in doc


def test_the_printer_knows_the_eleven_and_the_programs_names():
    assert hg.READERS == tuple(ELEVEN)
    # listed in the printer's order, whatever a later PR appends
    assert [m["name"] for m in BENCH["per_layer"]
            if m["name"] in ELEVEN] == list(ELEVEN)
    # what a replica's heartbeat and spans.py call the phases and spans
    from distributedmnist_tpu.obsv import spans
    from distributedmnist_tpu.servesvc.decode import LOOP_PHASES
    assert (hg.SPAN_FETCH, hg.SPAN_INPUTS, hg.SPAN_FINISH, hg.SPAN_ADMIT,
            hg.SPAN_IDLE, hg.SPAN_HEARTBEAT) == (
        spans.SERVE_STEP_FETCH, spans.SERVE_STEP_INPUTS, spans.SERVE_FINISH,
        spans.SERVE_ADMIT, spans.SERVE_IDLE, spans.SERVE_HEARTBEAT)
    assert {"fetch", "idle"} <= set(LOOP_PHASES)


@pytest.mark.parametrize("name", list(ELEVEN))
def test_each_reader_reads_the_recorded_chat_run(name, monkeypatch):
    monkeypatch.setattr(runtime, "WORK_ROOT", DATA / "v5e_chat_run")
    pt._RUNS.clear()
    got = cell_lib.load_reader(name).read(tr.reduce(_chat()), {})
    assert got == pytest.approx(CHAT_READS[name], abs=1e-3)


def test_the_eleven_read_beside_both_cells_accepted_ones(monkeypatch):
    counters = {"setup_compile_s": 3.0, "weights_ready_s": 30.0,
                "prefill_ms_p50": 39.1, "itl_ms_p50": 163.0,
                "itl_ms_p99": 248.0, "ttft_ms_p50": 122.0,
                "ttft_ms_p90": 171.0, "loadgen_late_ms_p99": 3.2,
                "tokens_in_trace": 48, "decode_bytes_per_step": 3.57e9,
                "peak_hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(runtime, "WORK_ROOT", DATA / "v5e_chat_run")
    pt._RUNS.clear()
    reduced = tr.reduce(_chat())
    chat = run_mod.per_layer_metrics(cell_lib.load_cell(CHAT), reduced,
                                     counters)
    assert len(set(chat) - set(ELEVEN)) >= 23      # none lost
    assert _read_all(reduced)["decode_gap_beneath_ms"] == pytest.approx(
        2.4308, abs=1e-3)
    # the closed cell's readers get a reduced trace and no run: PR 22's
    # recording, whose program opens no span and calls its step
    # jit__unknown
    reduced = tr.reduce(pt.load(str(DATA / "v5e_decode_three_steps.json.gz")))
    closed = run_mod.per_layer_metrics(cell_lib.load_cell(CLOSED), reduced,
                                       counters)
    assert len(set(closed) - set(ELEVEN)) >= 10
    eleven = _read_all(reduced)
    assert eleven["decode_gap_ms_p50"] == pytest.approx(27.712, abs=1e-3)
    assert {eleven[n] for n in ELEVEN
            if not n.startswith("decode_gap_ms_p")} == {0.0}
    # the iteration less the step, where those read one rung as here
    assert (closed["decode_iter_ms_p50"]["value"]
            - closed["decode_step_device_ms"]["value"]) == pytest.approx(
                eleven["decode_gap_ms_p50"], abs=0.1)
    latent = {m["name"] for m in cell_lib.load_cell(LATENT).per_layer}
    assert set(ELEVEN) - latent == {"decode_paged_kernel_ms_per_step"}


def test_the_printer_prints_the_split_of_any_trace(capsys):
    hg.describe(str(DATA / "v5e_paged_decode_two_steps.json.gz"))
    out = capsys.readouterr().out
    assert "0.601 ms early at least (offset_lo), 2.121 at most" in out
    assert "beneath          n    1  mean   1.684" in out
    assert "TpuLoadedExecutable::ExecuteLaunch" in out
    assert "no heartbeat with a loop clock beside this trace" in out
    assert "decode_gap_ms_p50" not in out      # a bare trace is no run


def test_the_printer_prints_the_eleven_of_the_newest_run(capsys,
                                                         monkeypatch):
    monkeypatch.setattr(runtime, "WORK_ROOT", DATA / "v5e_chat_run")
    pt._RUNS.clear()
    hg.describe()
    lines = capsys.readouterr().out.splitlines()
    got = {name: float(value) for name, value in
           (line.split() for line in lines[-len(ELEVEN):])}
    assert got == pytest.approx(CHAT_READS, abs=1e-3)
