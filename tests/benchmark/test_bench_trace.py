"""``trace_reduce.py`` on small recorded traces: two train steps of
``opt-6.7b.train_sync_1chip``, three decode steps of
``opt-1.3b.serve_decode_closed`` and one step of
``opt-6.7b.train_quorum3of4_4chip`` on its four chips, cut with ``trace_reduce.cut`` from the
traces of PR 22's first chip runs (TPU v5 lite) after ``trace_reduce.load``
had shortened the instruction texts. The numbers pinned here were
measured on the chip; the test checks the arithmetic that reads them."""

import gzip
import json
from pathlib import Path

import pytest

from benchmark import run as run_mod
from benchmark.lib import cell as cell_lib, trace_reduce as tr

DATA = Path(__file__).parent / "data"


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
