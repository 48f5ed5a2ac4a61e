"""The yardstick's arithmetic: operation counts, interval algebra,
traffic generation, token data, the serving reduction."""

import json
import math

import numpy as np
import pytest

from benchmark.lib import (flops, peaks, serve_metrics, stats, tokens,
                           trace_reduce as tr, traffic)

OPT_6_7B = {"hidden_size": 4096, "ffn_dim": 16384, "num_attention_heads": 32,
            "num_hidden_layers": 32, "vocab_size": 50272,
            "max_position_embeddings": 2048}


# -- flops.py against a hand count ------------------------------------------

def test_block_flops_match_a_hand_count():
    one = {**OPT_6_7B, "num_hidden_layers": 1}
    # q, k, v, out: 4 x 4096^2; fc1, fc2: 2 x 4096 x 16384
    assert flops.block_matmul_params(one) == 4 * 16_777_216 + 2 * 67_108_864
    assert flops.block_matmul_params(one) == 201_326_592
    head = 2 * 4096 * 50272
    attn = 4 * 100 * 4096          # QK^T and PV against 100 keys
    assert flops.forward_flops_per_token(one, 100) == (
        2 * 201_326_592 + attn + head)
    # training is forward plus twice that, at the causal mean context
    assert flops.train_flops_per_token(one, 2048) == pytest.approx(
        3 * (2 * 201_326_592 + 4 * 1024.5 * 4096 + head))


def test_param_count_is_the_published_size():
    # OPT-6.7B: 6.66 B parameters without the biases the block lacks
    assert flops.param_count(OPT_6_7B) == pytest.approx(6.66e9, rel=5e-3)
    three = {**OPT_6_7B, "num_hidden_layers": 3}
    # the tied embedding, the positions, three blocks, the final norm
    assert flops.param_count(three) == (205_914_112 + 8_388_608
                                        + 3 * 201_334_784 + 4096)


def test_decode_bytes_count_weights_once_and_kv_per_token():
    one = {**OPT_6_7B, "num_hidden_layers": 1}
    w = (201_326_592 + 50272 * 4096) * 2
    assert flops.decode_bytes_per_step(one, [10, 30]) == (
        w + 40 * 2 * 4096 * 2)
    # OPT-1.3B: 196,608 bytes of keys and values a cached token
    small = {**OPT_6_7B, "hidden_size": 2048, "ffn_dim": 8192,
             "num_hidden_layers": 24}
    assert (flops.decode_bytes_per_step(small, [1])
            - flops.decode_bytes_per_step(small, [])) == 196_608


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


# -- interval algebra ---------------------------------------------------------

def test_merge_gaps_subtract():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert merged == [(0, 3), (5, 8)]
    assert tr.total(merged) == 6
    assert tr.gaps(merged, 0, 10) == [(3, 5), (8, 10)]
    # exposed collective time: the part no compute covers
    assert tr.subtract([(0, 10)], [(2, 4), (3, 6), (9, 12)]) == [
        (0, 2), (6, 9)]


def test_self_time_takes_children_out():
    events = [["while", 0.0, 100.0, ""], ["fusion.1", 10.0, 30.0, ""],
              ["fusion.2", 50.0, 20.0, ""], ["copy", 200.0, 5.0, ""]]
    assert {ev[0]: t for ev, t in tr.self_times(events)} == {
        "while": 50.0, "fusion.1": 30.0, "fusion.2": 20.0, "copy": 5.0}


def _synthetic_trace():
    def dev(n, ops, modules):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "Async XLA Ops", "events": flight},
            {"name": "XLA Modules", "events": modules}]}
    ops = [["fusion.1", 100.0, 300.0, "fusion"],
           ["flash.2", 400.0, 100.0, tr.PALLAS],
           ["custom-call.9", 400.0, 0.0, "custom-call"],   # not a kernel
           ["all-reduce.3", 450.0, 250.0, "all-reduce"],  # 200 exposed
           ["all-gather-done.5", 880.0, 20.0, "all-gather-done"],
           ["fusion.4", 900.0, 100.0, "fusion"]]
    # an asynchronous collective in flight 800..900, under no compute
    # until 900 but for its own done operation
    flight = [["all-gather-start.5", 800.0, 100.0, "all-gather-start"],
              ["copy-start.1", 100.0, 500.0, "copy-start"]]
    modules = [["jit_step(123)", 99.0, 602.0, ""],
               ["jit_step(123)", 899.0, 102.0, ""],
               ["jit_step(77)", 700.0, 50.0, ""],   # another program
               ["jit_other(9)", 2000.0, 10.0, ""]]
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [tr.WINDOW_ANNOTATION, 0.0, 2000.0, ""],
        ["TransferFromDevice", 690.0, 220.0, ""],
        ["outer", 600.0, 400.0, ""]]}]}
    return {"planes": [dev(1, ops, modules), dev(0, ops, modules), host]}


def test_reduce_on_a_synthetic_trace():
    r = tr.reduce(_synthetic_trace())
    assert r["devices"] == 2
    # busy 100..700 and 880..1000 of the window 100..1000
    assert r["busy_s"] == pytest.approx(720e-9)
    assert r["window_s"] == pytest.approx(900e-9)
    assert r["pallas_s"] == pytest.approx(100e-9)
    # 450..700 and 800..900; exposed where only collectives run
    assert r["collective_s"] == pytest.approx(350e-9)
    assert r["collective_exposed_s"] == pytest.approx(300e-9)
    name, module = tr.main_module(r)
    assert name == "jit_step" and module["durations_ms"] == [602e-6, 102e-6]
    # the one gap, 700..880, is named by the host span inside it
    assert r["idle_gaps"] == [["TransferFromDevice", pytest.approx(180e-9)]]
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]


def test_parse_op_cuts_an_instruction_to_name_shape_and_opcode():
    text = ("%fusion.83 = (bf16[4096]{0:T(1024)(128)(2,1)S(1)}, f32[4,2048]"
            "{1,0:T(4,128)S(1)}) fusion(bf16[4,2048,4096]{2,1,0:T(8,128)(2,1)"
            "S(1)} %copy-done.20), kind=kOutput, calls=%fused_computation.173")
    assert tr.parse_op(text) == (
        "%fusion.83 = (bf16[4096], f32[4,2048]) fusion", "fusion")
    kernel = ("%flash_attention_bshd.9 = bf16[4,2048,4096]{2,1,0:T(8,128)"
              "(2,1)} custom-call(bf16[4,2048,4096]{2,1,0} %x), "
              'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.parse_op(kernel)[1] == tr.PALLAS
    other = ('%custom-call.16 = f32[4096,4096]{1,0} custom-call(f32[1024,4096]'
             '{1,0} %s), custom_call_target="ConcatBitcast"')
    assert tr.parse_op(other)[1] == "custom-call"
    assert tr.parse_op("%all-reduce-start.2 = f32[8]{0} all-reduce-start("
                       "f32[8]{0} %p)")[1] == "all-reduce-start"
    assert tr.is_collective("all-reduce-start")
    assert not tr.is_collective("copy-start")
    assert tr.parse_op("PjitFunction(x)") == ("PjitFunction(x)", "")


def test_reduce_refuses_a_trace_without_a_device():
    with pytest.raises(tr.TraceError):
        tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


# -- traffic -----------------------------------------------------------------

CHAT = {"arrivals": {"rate_per_s": 3.0}, "warmup_s": 4, "deadline_ms": 1000,
        "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 0.7,
                       "lo": 64, "hi": 1024},
        "max_tokens": {"dist": "lognormal", "median": 48, "sigma": 0.6,
                       "lo": 16, "hi": 128}}


def test_open_schedule_is_a_pure_function_of_the_seed():
    a = traffic.open_schedule(CHAT, 5, 20.0, 1000)
    assert a == traffic.open_schedule(CHAT, 5, 20.0, 1000)
    b = traffic.open_schedule(CHAT, 6, 20.0, 1000)
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    # the amount of work is the file's, not the seed's
    assert len(a) == len(b) == round(3.0 * 4) + round(3.0 * 20.0)
    assert sum(r["due_s"] >= 0 for r in a) == 60
    assert all(-4 <= r["due_s"] < 20 for r in a)
    for key, total_of in (("prompt", lambda r: len(r["prompt"])),
                          ("max_tokens", lambda r: r["max_tokens"])):
        ta, tb = sum(map(total_of, a)), sum(map(total_of, b))
        assert abs(ta - tb) / ta < 0.03, key
    assert all(64 <= len(r["prompt"]) <= 1024 and 16 <= r["max_tokens"] <= 128
               and all(0 <= t < 1000 for t in r["prompt"]) for r in a)


def test_lengths_are_stratified_over_the_distribution():
    rng = np.random.default_rng(0)
    got = traffic.draw_lengths({"dist": "loguniform", "lo": 32, "hi": 128},
                               64, rng)
    assert got.min() >= 32 and got.max() <= 128
    # one draw from each of 64 equal slices: the median is the law's
    assert abs(np.median(got) - 64) <= 2
    assert traffic.draw_lengths({"dist": "fixed", "value": 7}, 3,
                                rng).tolist() == [7, 7, 7]


CLOSED = {"requests_per_client": 3, "deadline_ms": 5, "prompt_len":
          {"dist": "loguniform", "lo": 8, "hi": 16}, "max_tokens":
          {"dist": "loguniform", "lo": 100, "hi": 200},
          "stagger_first_wave": True}


def _sizes(queues):
    return [[(r["id"], len(r["prompt"]), r["max_tokens"]) for r in c]
            for c in queues]


def test_closed_queues_and_first_wave():
    q = traffic.closed_queues(CLOSED, 1, 4, 50)
    assert q == traffic.closed_queues(CLOSED, 1, 4, 50)
    assert len(q) == 4 and all(len(c) == 3 for c in q)
    assert all(c[0]["max_tokens"] <= 200 for c in q)
    assert all(100 <= r["max_tokens"] <= 200 for c in q for r in c[1:])
    assert any(c[0]["max_tokens"] < 100 for c in q)
    assert len({r["id"] for c in q for r in c}) == 12


def test_sizes_seed_gives_every_seed_one_seeds_sizes_and_its_own_tokens():
    theirs = traffic.closed_queues(CLOSED, 7, 4, 50)
    pinned = {**CLOSED, "sizes_seed": 7}
    a = traffic.closed_queues(pinned, 1, 4, 50)
    b = traffic.closed_queues(pinned, 2 ** 31 + 5, 4, 50)
    assert a == traffic.closed_queues(pinned, 1, 4, 50)
    # the work is seed 7's, shares of the first wave and order included
    assert _sizes(a) == _sizes(b) == _sizes(theirs)
    assert _sizes(traffic.closed_queues(CLOSED, 1, 4, 50)) != _sizes(theirs)
    # the tokens are the run's own
    prompts = lambda q: [r["prompt"] for c in q for r in c]  # noqa: E731
    assert prompts(a) != prompts(b) and prompts(a) != prompts(theirs)
    assert all(0 <= t < 50 for p in prompts(a) for t in p)
    assert {k: v for k, v in a[0][0].items() if k != "prompt"} == {
        k: v for k, v in theirs[0][0].items() if k != "prompt"}


@pytest.mark.parametrize("cell_name, clients, vocab", [
    ("opt-1.3b.serve_decode_closed", 32, 50272),
    ("openpangu-ultra-moe-718b.serve_reason_closed", 128, 19200)])
def test_a_closed_cell_offers_every_seed_the_same_sizes(cell_name, clients,
                                                        vocab):
    """A closed cell opens its window on a young replica, so the order
    of the lengths decides the work: how long ``opt-1.3b``'s step runs
    at its narrowest table; how many prefills stall the latent cell's
    window and whether its end meets the widest table. The files pin
    the sizes (PERF.md, PR 38)."""
    from benchmark.lib import cell as cell_lib
    tr = cell_lib.load_cell(cell_name).traffic
    assert "sizes_seed" in tr
    a, b = (traffic.closed_queues(tr, s, clients, vocab) for s in (1, 2))
    assert _sizes(a) == _sizes(b)
    assert a[0][0]["prompt"] != b[0][0]["prompt"]


def test_warmup_lengths_reach_every_power_of_two_bucket():
    assert traffic.warmup_lengths(CHAT) == [64, 128, 256, 512, 1024]
    assert traffic.warmup_lengths(
        {"prompt_len": {"dist": "loguniform", "lo": 33, "hi": 100}}) == [
            33, 64, 100]


# -- tokens ------------------------------------------------------------------

def test_tokens_are_seeded_and_learnable():
    a = tokens.make_lm_tokens(3, 16, 256, 1000, 1.1, 0.5)
    assert a.dtype == np.int32 and a.shape == (16, 256)
    assert (a == tokens.make_lm_tokens(3, 16, 256, 1000, 1.1, 0.5)).all()
    assert (a != tokens.make_lm_tokens(4, 16, 256, 1000, 1.1, 0.5)).any()
    assert a.min() >= 0 and a.max() < 1000
    # about half the tokens are the favourite successor of the one before
    pairs = {}
    for prev, nxt in zip(a[:, :-1].ravel(), a[:, 1:].ravel()):
        pairs.setdefault(int(prev), []).append(int(nxt))
    top = sum(max(v.count(x) for x in set(v)) for v in pairs.values())
    assert 0.45 < top / (16 * 255) < 0.75
    assert 0 < tokens.unigram_entropy(50272, 1.1) < math.log(50272)


# -- the serving reduction ----------------------------------------------------

def _rec(rid, due, times, max_tokens, terminal=True, **kw):
    stream = [[t, i, 5] for i, t in enumerate(times)]
    term = ({"t": times[-1], "status": "ok", "reason": None,
             "finish_reason": "max_tokens", "tokens": [5] * len(times)}
            if terminal else None)
    return {"id": rid, "client": None, "due": due, "prompt_len": 3,
            "max_tokens": max_tokens, "sent": due + 0.001, "stream": stream,
            "terminal": term, "error": None, "ended": None,
            "aborted": not terminal, **kw}


def test_serving_numbers_run_from_due_time():
    load = {"window_start": 10.0, "window_end": 20.0, "records": [
        _rec("w0", 1.0, [1.5, 1.6], 2, warmup=True),
        _rec("a", 9.0, [9.5, 10.5, 11.5], 3),       # due before the window
        _rec("b", 12.0, [12.25, 12.35, 12.45, 12.55], 4),
        _rec("c", 19.0, [19.5, 20.5], 5, terminal=False),  # cut off, healthy
        _rec("d", 13.0, [13.1], 2)]}                 # short: failed
    s = serve_metrics.summarize(load, vocab=10)
    assert s["attempted"] == 4 and s["failed"] == 1
    assert "d" in s["failures"] and s["cut_off_at_end"] == 1
    # tokens at 10.5, 11.5, b's four, 19.5 and d's one lie in the window
    assert s["tokens_in_window"] == 8
    assert s["serve_tokens_per_s"] == pytest.approx(0.8)
    # TTFT of the requests DUE in the window: b 250 ms, c 500 ms, d failed
    assert s["ttft_samples"] == 3 and s["ttft_missing"] == 1
    assert s["ttft_ms_p50"] == pytest.approx(500.0)
    assert math.isinf(s["ttft_ms_p90"])
    # gaps whose later token lies in the window: a's two, b's three
    assert s["gap_samples"] == 5
    assert s["itl_ms_p99"] == pytest.approx(1000.0)
    assert s["loadgen_late_ms_p99"] == pytest.approx(1.0)


def test_a_bad_stream_fails_its_request():
    bad_index = _rec("x", 0.0, [1.0, 2.0], 2)
    bad_index["stream"][1][1] = 5
    out_of_vocab = _rec("y", 0.0, [1.0, 2.0], 2)
    out_of_vocab["stream"][0][2] = 99
    refused = _rec("z", 0.0, [1.0], 1)
    refused["stream"] = []
    refused["terminal"] = {"t": 1.0, "status": "rejected",
                           "reason": "overloaded", "finish_reason": None,
                           "tokens": None}
    for rec, why in ((bad_index, "indices"), (out_of_vocab, "vocabulary"),
                     (refused, "overloaded")):
        assert why in serve_metrics.check_request(rec, vocab=10)
    assert serve_metrics.check_request(_rec("ok", 0.0, [1.0], 1), 10) is None


def test_percentile_is_nearest_rank():
    assert stats.percentile([3, 1, 2], 0.5) == 2
    assert stats.percentile(list(range(101)), 0.99) == 99
    assert stats.percentile([1.0], 0.9) == 1.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)
    assert json.dumps(stats.percentile([1, 2], 1.0)) == "2"
