"""``trace_reduce.py`` on small recorded traces: two train steps of
``opt-6.7b.train_sync_1chip``, three decode steps of
``opt-1.3b.serve_decode_closed`` and one step of
``opt-6.7b.train_quorum3of4_4chip`` on its four chips, cut with ``trace_reduce.cut`` from the
traces of PR 22's first chip runs (TPU v5 lite) after ``trace_reduce.load``
had shortened the instruction texts. The numbers pinned here were
measured on the chip; the test checks the arithmetic that reads them.

Since PR 38 the arithmetic between the device's idle gaps and the host's
events is one walk over sorted lists (a trace of 170 decode steps of 14
ms has 150,000 gaps and 22,000 host events, and gap by event took 43
minutes): the plain form it had is kept here as the oracle, and the walk
must give what the oracle gives with ``==``, on every recorded trace and
on seeded random ones. ``v5e_paged_decode_two_steps.json.gz`` is two
whole steps of ``opt-1.3b.serve_decode_closed`` with a paged Mosaic
kernel and the cache write's ``while`` loops (PR 37's refused change,
that builder's chip run on a TPU v5 lite, seed 2147480731), cut from the
63.9 MB trace after ``program_trace.load`` had kept each event's facts."""

import gzip
import json
import random
import time
from pathlib import Path

import pytest

from benchmark import run as run_mod
from benchmark.lib import (cell as cell_lib, program_trace as pt,
                           trace_reduce as tr)

DATA = Path(__file__).parent / "data"
CHAT_TRACE = ("v5e_chat_run/opt-1.3b.serve_chat_open/trace/"
              "v5e_chat_four_steps_one_prefill.json.gz")
RECORDED = ["v5e_train_two_steps.json.gz", "v5e_decode_three_steps.json.gz",
            "v5e_quorum_one_step_four_chips.json.gz", CHAT_TRACE,
            "v5e_paged_decode_two_steps.json.gz"]


def _trace(name: str) -> dict:
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


def test_train_steps_busy_kernels_and_program():
    trace = _trace("v5e_train_two_steps.json.gz")
    ops = tr._line(tr.device_planes(trace)[0], tr.OPS_LINE)
    r = tr.reduce(trace)
    assert r["devices"] == 1
    # the device never waits for the host inside a train step
    assert r["busy_s"] == pytest.approx(0.659383, rel=1e-5)
    assert 1 - r["busy_s"] / r["window_s"] < 1e-3
    # busy is a union: the sum of the events counts nested ones twice
    assert r["busy_s"] * 1e9 <= sum(e[2] for e in ops)
    name, module = tr.main_module(r)
    assert name == "jit_shard_fn"
    assert module["durations_ms"] == pytest.approx([329.69, 329.71], abs=0.02)
    # 12 Mosaic calls a step (3 layers: forward, the forward again under
    # remat, two backward kernels), 27.5 ms of 329.7
    kernels = [e for e in ops if e[3] == tr.PALLAS]
    assert len(kernels) == 24
    assert all("flash_attention" in e[0] for e in kernels)
    assert r["pallas_s"] == pytest.approx(0.054922, rel=1e-4)
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    # the head: logits, their gradient and the embedding's gradient
    assert r["device_ops"][0][0].startswith("%fusion.")
    assert "50272" in r["device_ops"][1][0]
    assert len(r["device_ops"]) == 10
    assert all(a[1] >= b[1] for a, b in zip(r["device_ops"],
                                            r["device_ops"][1:]))


def test_decode_steps_idle_gaps_and_their_host_labels():
    trace = _trace("v5e_decode_three_steps.json.gz")
    r = tr.reduce(trace)
    name, module = tr.main_module(r)
    assert name == "jit__unknown"         # a jitted functools.partial
    assert module["durations_ms"] == pytest.approx([143.2] * 3, abs=0.1)
    starts = module["starts_ms"]
    assert [b - a for a, b in zip(starts, starts[1:])] == pytest.approx(
        [171.0, 171.0], abs=1.0)
    # between two steps the device waits while the host fetches each
    # slot's sampled token: 11.5% idle in this cut
    idle = 1 - r["busy_s"] / r["window_s"]
    assert idle == pytest.approx(0.115, abs=0.002)
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    label, seconds = r["idle_gaps"][0]
    assert label == "np.asarray(jax.Array)"
    assert seconds / (r["window_s"] - r["busy_s"]) > 0.99
    # the whole cache is copied to another layout and back, every token
    assert r["device_ops"][0][0] == "%copy.76 = bf16[24,769,16,32,64] copy"
    assert r["pallas_s"] == 0


def test_collectives_across_four_chips_and_their_exposed_part():
    trace = _trace("v5e_quorum_one_step_four_chips.json.gz")
    assert [p["name"] for p in tr.device_planes(trace)] == [
        f"/device:TPU:{n}" for n in range(4)]
    r = tr.reduce(trace)
    assert r["devices"] == 4 and len(r["per_device"]) == 4
    name, module = tr.main_module(r)
    assert name == "jit_shard_fn"
    assert module["durations_ms"] == pytest.approx([389.3], abs=0.05)
    # 17 synchronous f32 all-reduces a step, one per parameter leaf: the
    # masked psum of 3.27 GB of gradient. Nothing else runs on a chip
    # while one does, so all of it is exposed.
    ops = tr._line(tr.device_planes(trace)[0], tr.OPS_LINE)
    reduces = [e for e in ops if tr.is_collective(e[3])]
    assert len(reduces) == 17 and {e[3] for e in reduces} == {"all-reduce"}
    assert max(reduces, key=lambda e: e[2])[0] == (
        "%psum_invariant.201 = f32[50272,4096] all-reduce")
    assert r["collective_s"] == pytest.approx(0.057616, rel=1e-4)
    assert r["collective_exposed_s"] == pytest.approx(r["collective_s"])
    # the averages are over the chips: each is as busy as the others
    assert all(d["busy_s"] == pytest.approx(r["busy_s"], rel=1e-3)
               for d in r["per_device"])
    cell = cell_lib.load_cell("opt-6.7b.train_quorum3of4_4chip")
    got = run_mod.per_layer_metrics(
        cell, r, {"setup_compile_s": 1.25, "host_step_ms_p50": 3.1,
                  "prefetch_depth_p50": 2.0, "tokens_per_s": 83602.0,
                  "chips": 4, "model_flops_per_token": 5010432000.0,
                  "attention_flops_per_step_per_chip": 1237554561024.0,
                  "peak_bf16_flops_per_s": 197e12})
    assert round(got["collective_ms_per_step"]["value"], 1) == 57.6
    assert round(got["collective_exposed_ms_per_step"]["value"], 1) == 57.6
    assert round(got["train_mfu"]["value"], 1) == 53.2
    assert round(got["flash_attention_roofline"]["value"], 1) == 22.9


def _holds(got: dict, digits: int, want: dict) -> None:
    """Every metric listed, at its value; a later PR's metric that
    reaches the cell too may stand beside them."""
    assert {k: round(got[k]["value"], digits) for k in want} == want


def test_the_readers_read_the_recorded_traces():
    train = cell_lib.load_cell("opt-6.7b.train_sync_1chip")
    got = run_mod.per_layer_metrics(
        train, tr.reduce(_trace("v5e_train_two_steps.json.gz")),
        {"setup_compile_s": 1.2, "host_step_ms_p50": 1.5,
         "prefetch_depth_p50": 2.0, "tokens_per_s": 24692.0, "chips": 1,
         "model_flops_per_token": 5010432000.0,
         "attention_flops_per_step_per_chip": 3 * 3 * 4 * 1024.5 * 4096
         * 8192, "peak_bf16_flops_per_s": 197e12})
    _holds(got, 2, {
        "compile_or_load_s": 1.2, "host_step_ms_p50": 1.5,
        "prefetch_depth_p50": 2.0, "train_step_device_ms": 329.69,
        "train_mfu": 62.8, "train_pallas_share_of_busy": 8.33,
        "flash_attention_roofline": 22.88, "train_device_idle_share": 0.01})
    closed = cell_lib.load_cell("opt-1.3b.serve_decode_closed")
    got = run_mod.per_layer_metrics(
        closed, tr.reduce(_trace("v5e_decode_three_steps.json.gz")),
        {"setup_compile_s": 3.0, "weights_ready_s": 30.0,
         "tokens_in_trace": 48, "itl_ms_p50": 170.8, "itl_ms_p99": 172.6,
         "loadgen_late_ms_p99": 0.4, "decode_bytes_per_step": 3.57e9,
         "peak_hbm_bytes_per_s": 819e9})
    _holds(got, 1, {
        "compile_or_load_s": 3.0, "weights_ready_s": 30.0,
        "decode_iter_ms_p50": 170.9, "tokens_per_decode_step": 16.0,
        "decode_step_device_ms": 143.2, "decode_step_roofline": 3.0,
        "serve_device_idle_share": 11.5, "loadgen_late_ms_p99": 0.4,
        "itl_ms_p50": 170.8, "itl_ms_p99": 172.6})


def test_cut_keeps_whole_events_and_the_annotation():
    trace = {"planes": [{"name": "/host:CPU", "lines": [{"name": "t",
             "events": [[tr.WINDOW_ANNOTATION, 0.0, 100.0, ""],
                        ["a", 10.0, 5.0, ""], ["b", 48.0, 5.0, ""]]}]}]}
    small = tr.cut(trace, 8.0, 50.0)
    assert small["planes"][0]["lines"][0]["events"] == [
        [tr.WINDOW_ANNOTATION, 8.0, 42.0, ""], ["a", 10.0, 5.0, ""]]


# -- the walk over sorted lists against the plain form ---------------------

def _plain_subtract(intervals, cover):
    """``subtract`` as it was until PR 38: the whole cover clipped to
    each interval."""
    def clip(spans, lo, hi):
        return [(max(a, lo), min(b, hi)) for a, b in spans
                if min(b, hi) > max(a, lo)]
    cover = tr.merge(cover)
    out = []
    for a, b in tr.merge(intervals):
        out += tr.gaps(clip(cover, a, b), a, b)
    return out


def _plain_labels(host, spans):
    """Every host event for every span: the rule as it is stated."""
    return [tr._host_label(host, a, b) for a, b in spans]


@pytest.mark.parametrize("name", RECORDED)
def test_a_recorded_trace_reduces_to_what_the_plain_form_gives(
        name, monkeypatch):
    trace = _trace(name)
    got = tr.reduce(trace, top=1000), tr.reduce(trace)
    with monkeypatch.context() as m:
        m.setattr(tr, "subtract", _plain_subtract)
        m.setattr(tr, "host_labels", _plain_labels)
        want = tr.reduce(trace, top=1000), tr.reduce(trace)
    # the same floats, and the same order of programs, operations, labels
    assert got == want
    assert [list(g["modules"]) for g in got] == [
        list(w["modules"]) for w in want]
    assert sum(s for _, s in got[0]["idle_gaps"]) == pytest.approx(
        got[0]["per_device"][0]["window_s"]
        - got[0]["per_device"][0]["busy_s"], rel=1e-9)


def test_the_idle_shares_of_the_recorded_run_are_the_plain_forms(
        monkeypatch):
    trace = _trace(CHAT_TRACE)
    spans = sorted(pt.span_names(trace))
    assert pt.SPAN_SAMPLE in spans and len(spans) >= 8
    got = ([pt.idle_share_inside(trace, s) for s in spans],
           pt.idle_share_unattributed(trace))
    with monkeypatch.context() as m:
        m.setattr(tr, "subtract", _plain_subtract)
        want = ([pt.idle_share_inside(trace, s) for s in spans],
                pt.idle_share_unattributed(trace))
    assert got == want
    assert got[1] == pytest.approx(1.5015, rel=1e-4)     # my chip run, PR 23


#: (host events, span, the label): the rule case by case
_A, _B = "a", "b"
NAMED = {
    "the_larger_overlap": ([[_A, 0.0, 6.0], [_B, 5.0, 9.0]], (4.0, 10.0),
                           _B),
    "equal_overlaps_the_shorter": ([[_A, 0.0, 100.0], [_B, 2.0, 50.0]],
                                   (10.0, 20.0), _B),
    "equal_overlaps_the_shorter_comes_first": (
        [[_B, 2.0, 50.0], [_A, 0.0, 100.0]], (10.0, 20.0), _B),
    "equal_keys_the_first_in_order": (
        [["x", 30.0, 1.0], [_A, 5.0, 20.0], [_B, 5.0, 20.0]], (10.0, 20.0),
        _A),
    "equal_keys_on_two_threads_the_first_line": (
        [[_B, 8.0, 20.0], [_A, 2.0, 20.0]], (10.0, 20.0), _B),
    "an_event_that_ends_where_the_span_begins": (
        [[_A, 0.0, 10.0]], (10.0, 20.0), "nothing recorded on the host"),
    "an_event_that_begins_where_the_span_ends": (
        [[_A, 20.0, 10.0]], (10.0, 20.0), "nothing recorded on the host"),
    "a_zero_length_event_inside": (
        [[_A, 15.0, 0.0]], (10.0, 20.0), "nothing recorded on the host"),
    "a_span_of_one_nanosecond": (
        [[_A, 0.0, 100.0], [_B, 10.0, 1.0], ["c", 10.5, 1.0]], (10.0, 11.0),
        _B),
    "no_event_at_all": ([], (10.0, 20.0), "nothing recorded on the host"),
    "the_window_annotation_does_not_count": (
        [[tr.WINDOW_ANNOTATION, 0.0, 100.0], [_A, 12.0, 1.0]], (10.0, 20.0),
        _A),
    "the_window_annotation_alone": (
        [[tr.WINDOW_ANNOTATION, 0.0, 100.0]], (10.0, 20.0),
        "nothing recorded on the host"),
}


@pytest.mark.parametrize("case", sorted(NAMED))
def test_the_host_label_of_a_span(case):
    host, span, want = NAMED[case]
    host = [[name, start, dur, ""] for name, start, dur in host]
    assert tr._host_label(host, *span) == want
    # alone, and as the middle one of three spans of a walk
    assert tr.host_labels(host, [span]) == [want]
    around = [(span[0] - 3.0, span[0] - 2.0), span,
              (span[1] + 2.0, span[1] + 3.0)]
    assert tr.host_labels(host, around)[1] == want


def _random_case(seed: int):
    """Host events on several threads (nested three deep, side by side,
    repeated, of no length, on whole nanoseconds so that equal overlaps
    and touching edges are common) and the idle gaps of a random busy
    line, most of them one nanosecond long."""
    rng = random.Random(seed)
    end = rng.choice([60, 400, 4000])
    host = []

    def nest(thread, lo, hi, depth):
        at = lo
        while at < hi:
            dur = rng.randint(0, max(1, (hi - at) // rng.randint(1, 4)))
            host.append([f"t{thread}.d{depth}.{rng.randint(0, 5)}",
                         float(at), float(dur), ""])
            if depth < 3 and dur > 1 and rng.random() < 0.7:
                nest(thread, at, at + dur, depth + 1)
            if rng.random() < 0.15:          # the same span once more
                host.append([f"again{len(host)}", float(at), float(dur), ""])
            at += dur + rng.choice([0, 0, 1, rng.randint(0, end // 8)])

    for thread in range(rng.randint(0, 6)):
        nest(thread, rng.randint(0, end // 4), end - rng.randint(0, end // 4),
             1)
    if rng.random() < 0.5:
        host.insert(rng.randint(0, len(host)),
                    [tr.WINDOW_ANNOTATION, 0.0, float(end), ""])
    if rng.random() < 0.3:
        rng.shuffle(host)
    busy, at = [], 0
    while at < end:
        dur = rng.randint(1, max(1, end // 20))
        busy.append((float(at), float(at + dur)))
        at += dur + rng.choice([1, 1, 1, 2, 3, rng.randint(0, end // 10)])
    return host, tr.gaps(tr.merge(busy), 0.0, float(end)), busy


@pytest.mark.parametrize("seed", range(300))
def test_the_walk_gives_what_the_plain_form_gives(seed):
    host, idle, busy = _random_case(seed)
    assert tr.host_labels(host, idle) == _plain_labels(host, idle)
    cover = [(e[1], e[1] + e[2]) for e in host
             if e[0] != tr.WINDOW_ANNOTATION]
    for a, b in ((idle, cover), (cover, idle), (cover, busy), (busy, cover),
                 (idle, []), ([], cover), (idle, idle)):
        assert tr.subtract(a, b) == _plain_subtract(a, b)
    # what is left and what was taken are the whole
    left = tr.subtract(idle, cover)
    taken = tr.subtract(idle, left)
    assert tr.total(left) + tr.total(taken) == tr.total(idle)
    assert tr.subtract(taken, cover) == []


# -- a trace as large as a fast decode step makes it ------------------------

def _large_trace(seed: int = 0):
    """The sizes of PR 37's traced run (170 steps of 14 ms in 3 s):
    150,000 idle gaps of 1-3 ns with a few thousand long ones, 22,000
    host events on eight threads nested three deep, the program's span
    names among them."""
    rng = random.Random(seed)
    ops, modules, at, n_gaps, long_gaps = [], [], 1000.0, 150_000, 0
    for i in range(n_gaps + 1):
        dur = float(rng.randint(5_000, 30_000))
        if i % 900 == 0:
            modules.append(["jit_decode_step(7)", at, 0.0, ""])
        if i % 20 == 0:       # a loop and its body: one busy interval
            ops.append([f"%while.{i % 48} = () while", at, dur, "while"])
            ops += [[f"%fusion.{i % 48}.{k} = f32[16] fusion",
                     at + k * dur / 4, dur / 4, "fusion"] for k in range(4)]
        else:
            name = ("%paged_decode.1 = f32[16,32,128] " + tr.PALLAS
                    if i % 20 == 1 else f"%fusion.{i % 300} = f32[8] fusion")
            ops.append([name, at, dur, tr.PALLAS if i % 20 == 1
                        else "fusion"])
        at += dur
        modules[-1][2] = at - modules[-1][1]
        if i < n_gaps:
            long_ = rng.random() < 0.02
            long_gaps += long_
            at += float(rng.randint(1_000, 50_000) if long_
                        else rng.randint(1, 3))
    end = at + 1000.0
    names = ["dml.serve.step.fetch", "dml.serve.step.dispatch",
             "dml.serve.stream", "np.asarray(jax.Array)", "PjitFunction(f)"]
    lines = []
    for thread in range(8):
        events, t = [], float(rng.randint(0, 2000))
        while t < end - 1e6:
            dur = float(rng.randint(1_500_000, 4_500_000))
            events.append([names[thread % 3], t, dur, "", {}])
            events.append([rng.choice(names), t + dur / 8, dur / 2, "", {}])
            events.append([rng.choice(names), t + dur / 4, dur / 8, "", {}])
            t += dur + rng.randint(0, 20_000)
        lines.append({"name": f"thread/{thread}", "events": events})
    lines[0]["events"].append([tr.WINDOW_ANNOTATION, 0.0, end, "", {}])
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.MODULES_LINE, "events": modules},
            {"name": tr.OPS_LINE, "events": ops}]},
        {"name": tr.HOST_PLANE, "lines": lines}]}
    return trace, n_gaps, long_gaps


def test_a_trace_of_150000_gaps_and_22000_host_events_reduces_in_seconds():
    """With the plain form this is 150,000 x 22,000 steps of Python, 40
    minutes: the run would be stopped at its limit (PR 37 was). No
    timing here is a device metric."""
    trace, n_gaps, long_gaps = _large_trace()
    host = tr.host_events(trace)
    assert 21_000 <= len(host) <= 24_000 and 2_000 <= long_gaps <= 4_000
    t0 = time.perf_counter()
    reduced, sizes = tr.reduce_sized(trace, top=1000)
    unattributed = pt.idle_share_unattributed(trace)
    fetch = pt.idle_share_inside(trace, "dml.serve.step.fetch")
    took = time.perf_counter() - t0
    assert took < 20.0, took
    assert sizes == {"ops": 150_001 + 4 * 7_501, "gaps": n_gaps,
                     "host_events": len(host)}
    assert sum(s for _, s in reduced["idle_gaps"]) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)
    assert reduced["pallas_s"] > 0 and len(reduced["modules"]) == 1
    idle_share = 100 * (1 - reduced["busy_s"] / reduced["window_s"])
    assert 0.0 <= unattributed < 5.0
    assert 0.0 < fetch <= idle_share * (1 + 1e-9)
    # and a sample of it against the plain form
    idle, _, _ = pt.device_idle(trace)
    labels = tr.host_labels(host, idle)
    some = random.Random(1).sample(range(n_gaps), 40)
    assert [labels[i] for i in some] == _plain_labels(
        host, [idle[i] for i in some])
    head = idle[:1500]
    cover = [(e[1], e[1] + e[2]) for e in host if e[0].startswith("dml.")]
    assert tr.subtract(head, cover) == _plain_subtract(head, cover)


# -- a step with a Mosaic call and loops ------------------------------------

def test_paged_decode_steps_kernel_loops_and_gap_labels():
    trace = _trace("v5e_paged_decode_two_steps.json.gz")
    ops = tr._line(tr.device_planes(trace)[0], tr.OPS_LINE)
    # a step: 24 paged kernels, and 48 loops of 16 iterations that write
    # the new token's keys and values into the cache, slot by slot
    assert len(ops) == 13_034
    assert sum(e[3] == tr.PALLAS for e in ops) == 2 * 24
    assert sum(e[3] == "while" for e in ops) == 2 * 48
    r, sizes = tr.reduce_sized(trace)
    assert sizes == {"ops": 13_034, "gaps": 2_281, "host_events": 201}
    name, module = tr.main_module(r)
    assert name == "jit_decode_step"
    assert module["durations_ms"] == pytest.approx([14.203, 14.212],
                                                   abs=1e-3)
    assert r["pallas_s"] == pytest.approx(0.0157129, rel=1e-5)
    assert all(label.startswith("%paged_decode.") and label.endswith(
        "f32[16,32,128] tpu_custom_call") for label, _ in r["device_ops"])
    # 2,281 gaps, all but one of about a nanosecond; the one between the
    # two steps is the host fetching the sampled tokens
    idle, lo, hi = pt.device_idle(trace)
    lengths = sorted(b - a for a, b in idle)
    assert lengths[len(lengths) // 2] < 1.5 and lengths[-2] < 1.5
    assert lengths[-1] == pytest.approx(3.2387e6, rel=1e-4)
    assert [label for label, _ in r["idle_gaps"][:3]] == [
        "dml.serve.step.fetch", "nothing recorded on the host",
        "np.asarray(jax.Array)"]
    assert r["idle_gaps"][0][1] == pytest.approx(3.23876e-3, rel=1e-5)
    assert sum(s for _, s in tr.reduce(trace, top=1000)["idle_gaps"]) == (
        pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9))
    # the step by scope (program_trace): after the kernel, the cache write
    table = pt.scope_table(trace, pt.DECODE_STEP)
    assert table["executions"] == 2
    assert table["total_ms"] == pytest.approx(14.206, abs=1e-3)
    assert table["by_kernel"] == {"paged_decode": pytest.approx(7.8564,
                                                                 abs=1e-4)}
    assert table["by_scope"][("attention/cache_write", "forward")] == (
        pytest.approx(4.2432, abs=1e-4))
    assert pt.scope_ms(table, "ffn") == pytest.approx(0.8015, abs=1e-4)
    assert pt.scope_ms(table, "head") == pytest.approx(0.2940, abs=1e-4)


# -- the run says what its own measurement cost -----------------------------

def test_a_traced_run_prints_its_trace_cost_before_its_result(
        monkeypatch, tmp_path, capsys):
    from bench_toy import ToyRuntime
    name = "v5e_decode_three_steps.json.gz"

    class Driver:
        @staticmethod
        def run(cell, rt):
            rt.window_opens()
            rt.start_trace()
            rt.stop_trace()
            rt.window_closes()
            return {"correct": True, "attempted": 3, "failed": 0,
                    "values": {}, "compared": {"tokens_flowed": [1, 1]},
                    "counters": {
                        "weights_ready_s": 30.0, "tokens_in_trace": 48,
                        "itl_ms_p50": 170.8, "itl_ms_p99": 172.6,
                        "loadgen_late_ms_p99": 0.4,
                        "decode_bytes_per_step": 3.57e9,
                        "peak_hbm_bytes_per_s": 819e9}}

    monkeypatch.setattr(run_mod, "gate", lambda chips: (
        {"platform": "cpu", "kind": "toy", "count": chips},
        {"bf16_flops_per_s": 1e12}))
    monkeypatch.setattr(run_mod, "Runtime", lambda *a: ToyRuntime(
        *a, work_root=tmp_path / "work"))
    monkeypatch.setattr(cell_lib, "load_driver", lambda kind, root: Driver)
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: name)
    monkeypatch.setattr(tr, "load", _trace)
    assert run_mod.main(["--workload", "opt-1.3b.serve_decode_closed",
                         "--seed", "2147483659", "--seconds", "1",
                         "--trace", "1"]) == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    result, event = lines[-1], lines[-2]
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "breakdown", "device", "compared"]
    assert event.pop("event") == "trace_reduced"
    assert {k: event.pop(k) for k in ("ops", "gaps", "host_events")} == {
        "ops": 2988, "gaps": 2409, "host_events": 2072}
    assert sorted(event) == ["load_s", "readers_s", "reduce_s", "stop_s"]
    assert all(isinstance(v, float) and v >= 0 for v in event.values())
    assert "trace_reduced" not in json.dumps(result)
    assert round(result["metrics"]["decode_step_device_ms"]["value"],
                 1) == 143.2
    assert result["breakdown"]["idle_gaps"][0][0] == "np.asarray(jax.Array)"
