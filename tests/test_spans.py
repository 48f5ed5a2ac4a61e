"""The program's own names in a profiler trace (obsv/spans.py): host
spans of the decode loop and the trainer, device scopes in the lowered
steps, program and kernel names, and the same facts in the journal and
the heartbeat. Everything here runs on the CPU test mesh: what is
checked is which spans exist, what they carry and how they nest, never
a time as a device metric."""

import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedmnist_tpu.obsv import spans

LM_MODEL = {"name": "transformer", "seq_len": 64, "model_dim": 64,
            "num_heads": 4, "num_layers": 2, "vocab_size": 32,
            "compute_dtype": "float32", "attention_impl": "dense"}
SERVE_LEAVES = {spans.SERVE_IDLE, spans.SERVE_ADMIT,
                spans.SERVE_PREFILL_FORWARD,
                spans.SERVE_PREFILL_CACHE_WRITE, spans.SERVE_STEP_INPUTS,
                spans.SERVE_STEP_DISPATCH, spans.SERVE_STEP_FETCH,
                spans.SERVE_SAMPLE, spans.SERVE_STREAM, spans.SERVE_FINISH,
                spans.SERVE_HEARTBEAT}


# -- reading a trace -------------------------------------------------------

def host_spans(trace_dir) -> dict[str, list[dict]]:
    """The ``dml.*`` spans of a trace, per host thread, in start order:
    ``{"name", "start", "end", **facts}``."""
    from jax.profiler import ProfileData

    [path] = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [{"name": ev.name, "start": ev.start_ns,
                    "end": ev.start_ns + ev.duration_ns, **dict(ev.stats)}
                   for ev in line.events if ev.name.startswith(spans.PREFIX)]
            if evs:
                # thread names repeat: the line's index tells them apart
                out[f"{i}:{line.name}"] = sorted(evs,
                                                 key=lambda e: e["start"])
    return out


def self_ns(evs: list[dict]) -> dict[int, float]:
    """Self time per span (index into ``evs``): its duration less its
    direct children's."""
    out, stack = {}, []
    for i, e in enumerate(sorted(evs, key=lambda e: (e["start"],
                                                      -e["end"]))):
        while stack and e["start"] >= stack[-1][1]:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= e["end"] - e["start"]
        out[i] = e["end"] - e["start"]
        stack.append((i, e["end"]))
    return out


# -- the decode replica ----------------------------------------------------

class StubConn:
    def __init__(self):
        self.lines = []

    def settimeout(self, t):
        pass

    def gettimeout(self):
        return None

    def sendall(self, b):
        self.lines += [json.loads(l) for l in b.decode().splitlines()]

    def close(self):
        pass


@pytest.fixture(scope="module")
def published(tmp_path_factory):
    """A transformer checkpoint to follow: initial weights, no training."""
    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.parallel.api import init_train_state
    from distributedmnist_tpu.train.checkpoint import save_checkpoint

    train_dir = tmp_path_factory.mktemp("published")
    cfg = ExperimentConfig.from_dict({
        "model": dict(LM_MODEL), "train": {"train_dir": str(train_dir)}})
    state = init_train_state(get_model(cfg.model), cfg)
    save_checkpoint(train_dir, state, 0, extra={"config": cfg.to_dict()})
    return train_dir, cfg


def drive(published, serve_dir, trace_dir=None):
    """Three requests through a three-slot replica, the batcher's loop
    body called from this thread: a and b admitted together, c two
    iterations later; a and c greedy, b a seeded draw. Returns the
    replica, what each client received and the occupancy before every
    iteration that dispatched a step."""
    from distributedmnist_tpu.core.config import DecodeConfig, ServeConfig
    from distributedmnist_tpu.servesvc.decode import DecodeReplica

    train_dir, cfg = published
    rep = DecodeReplica(
        train_dir, serve_dir=serve_dir, scfg=ServeConfig(poll_secs=0.05),
        dcfg=DecodeConfig(decode_slots=3, block_size=8, num_blocks=32,
                          max_prompt_len=16, max_new_tokens=10), cfg=cfg)
    rep._load_initial()
    conns, occupancy = {}, []

    def admit(req_id, prompt, max_tokens, **sampling):
        conns[req_id] = StubConn()
        seq = rep._build_item({"id": req_id, "prompt": prompt,
                               "max_tokens": max_tokens,
                               "deadline_ms": 600000, **sampling},
                              conns[req_id])
        rep._journal({"action": "admit", "id": req_id,
                      "deadline_ms": 600000.0})
        rep._queue.put_nowait(seq)

    def iteration():
        rep._maybe_swap()
        rep._admit_new()
        live = sum(s is not None for s in rep._slots)
        if live:
            occupancy.append(live)
        rep._step_active()
        rep._maybe_heartbeat()

    if trace_dir is not None:
        spans.start_profile(trace_dir)
    try:
        admit("a", [1, 2, 3], 4)
        admit("b", [4, 5, 6, 7, 8], 7, temperature=0.8, top_k=8)
        iteration()
        iteration()
        admit("c", [9, 10], 3)
        while True:
            iteration()
            if all(s is None for s in rep._slots):
                break
        # the loop goes on: it parks on its empty queue, and before it
        # does, writes what the last fetch decided
        iteration()
    finally:
        if trace_dir is not None:
            spans.stop_profile()
    return rep, conns, occupancy


@pytest.fixture(scope="module")
def traced_decode(published, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("traced_decode")
    rep, conns, occupancy = drive(published, tmp / "replica", tmp / "trace")
    [evs] = host_spans(tmp / "trace").values()      # one thread drove it
    return {"rep": rep, "conns": conns, "occupancy": occupancy,
            "spans": evs, "dir": tmp}


def _named(evs, name):
    return [e for e in evs if e["name"] == name]


def test_decode_loop_span_names_and_the_facts_they_carry(traced_decode):
    evs = traced_decode["spans"]
    assert {e["name"] for e in evs} == SERVE_LEAVES | {spans.SERVE_PREFILL}
    # the first arrivals found the replica parked on its queue and the
    # last finish left it so; no iteration between did, and nothing was
    # staged to swap
    assert len(_named(evs, spans.SERVE_IDLE)) == 2
    assert evs[0]["name"] == spans.SERVE_IDLE
    assert max(evs, key=lambda e: e["start"])["name"] in (
        spans.SERVE_IDLE, spans.SERVE_HEARTBEAT)
    prefills = _named(evs, spans.SERVE_PREFILL)
    assert [p["id"] for p in prefills] == ["a", "b", "c"]
    assert [p["prompt_len"] for p in prefills] == [3, 5, 2]
    assert all(p["bucket"] >= p["prompt_len"] and p["queue_ms"] >= 0
               for p in prefills)
    # c waited in the queue for the two iterations that ran before it
    assert prefills[2]["queue_ms"] > 0
    # the children of a prefill lie inside it; the admit span does not
    # contain it (the leaves tile the loop)
    for p in prefills:
        inside = [e["name"] for e in evs
                  if p["start"] <= e["start"] and e["end"] <= p["end"]
                  and e is not p]
        assert inside[:2] == [spans.SERVE_PREFILL_FORWARD,
                              spans.SERVE_PREFILL_CACHE_WRITE]
        assert spans.SERVE_SAMPLE in inside and spans.SERVE_STREAM in inside
        assert spans.SERVE_ADMIT not in inside
    assert not any(a["start"] <= p["start"] and p["end"] <= a["end"]
                   for a in _named(evs, spans.SERVE_ADMIT) for p in prefills)


def test_one_id_across_a_requests_prefill_sample_stream_finish(
        traced_decode):
    evs = traced_decode["spans"]
    for req_id, n_tokens in (("a", 4), ("b", 7), ("c", 3)):
        mine = [e for e in evs if e.get("id") == req_id]
        count = lambda name: len(_named(mine, name))  # noqa: E731
        assert count(spans.SERVE_PREFILL) == 1
        # the prefill's first token; an iteration's span is no request's
        assert count(spans.SERVE_SAMPLE) == 1
        assert count(spans.SERVE_STREAM) == n_tokens
        [fin] = _named(mine, spans.SERVE_FINISH)
        assert fin["reason"] == "max_tokens"
        assert mine[0]["name"] == spans.SERVE_PREFILL and mine[-1] is fin
    slots = {e["id"]: e["slot"] for e in _named(evs, spans.SERVE_SAMPLE)
             if "id" in e}
    assert slots == {"a": 0, "b": 1, "c": 2}


def test_one_sample_span_an_iteration_whatever_its_slots_ask_for(
        traced_decode):
    evs, rep = traced_decode["spans"], traced_decode["rep"]
    samples = _named(evs, spans.SERVE_SAMPLE)
    prefills = _named(evs, spans.SERVE_PREFILL)
    first = [e for e in samples if "id" in e]
    assert len(first) == len(prefills) == 3
    for p, e in zip(prefills, first):       # one inside each prefill
        assert p["start"] <= e["start"] and e["end"] <= p["end"]
    # the others: one between each dispatch and the next, counting the
    # slots whose token the step picked (a, c) and those that drew (b)
    per_iter = [e for e in samples if "id" not in e]
    dispatches = _named(evs, spans.SERVE_STEP_DISPATCH)
    assert len(per_iter) == len(dispatches)
    for i, (d, e) in enumerate(zip(dispatches, per_iter)):
        nxt = (dispatches[i + 1]["start"] if i + 1 < len(dispatches)
               else float("inf"))
        assert d["end"] <= e["start"] and e["end"] <= nxt
        assert e["device"] + e["host"] == d["live"]
    # b draws while it lives (6 tokens after its first), alone or not;
    # an iteration whose slots are all greedy still opens the span
    assert [e["host"] for e in per_iter] == [1] * 6
    assert [e["device"] for e in per_iter] == [1, 1, 2, 1, 0, 0]
    assert rep.tokens_sampled_device == sum(e["device"] for e in per_iter)
    assert rep.tokens_sampled_host == 3 + sum(e["host"] for e in per_iter)
    # neither name inside the other: idle time under a sample span is
    # all counted as sampling (serve_idle_sample_share)
    for e in samples:
        assert not any(t["start"] < e["end"] and e["start"] < t["end"]
                       for t in _named(evs, spans.SERVE_STREAM))


def test_between_a_fetch_and_the_next_dispatch_nothing_is_written(
        traced_decode):
    """The gap between two steps (`benchmark/lib/host_gaps.py` reads it
    from a fetch's end to the next dispatch's start) holds the books,
    the admission's polls, a heartbeat and the taking of the inputs; a
    line or a finish is written there only before a prefill or a park
    (a forced flush: that gap is the prefill's, not a plain one). Every
    other write, and the inputs built ahead, lie between a dispatch and
    its fetch."""
    evs = traced_decode["spans"]
    dispatches = _named(evs, spans.SERVE_STEP_DISPATCH)
    fetches = _named(evs, spans.SERVE_STEP_FETCH)
    assert len(dispatches) == len(fetches)
    writes = (spans.SERVE_STREAM, spans.SERVE_FINISH)
    stops = (spans.SERVE_PREFILL, spans.SERVE_IDLE)
    under_a_step = forced = 0
    for f, d in zip(fetches, dispatches[1:] + [None]):
        lo, hi = f["end"], d["start"] if d else float("inf")
        inside = [e for e in evs if lo <= e["start"] and e["end"] <= hi]
        plain = not any(e["name"] in stops for e in inside)
        names = {e["name"] for e in inside}
        if plain:
            assert names <= {spans.SERVE_SAMPLE, spans.SERVE_ADMIT,
                             spans.SERVE_HEARTBEAT,
                             spans.SERVE_STEP_INPUTS}, names
            assert len(_named(inside, spans.SERVE_STEP_INPUTS)) == 1
        else:
            first_stop = min(e["start"] for e in inside
                             if e["name"] in stops)
            for w in inside:
                if w["name"] in writes and w["start"] < first_stop:
                    forced += 1
        assert spans.SERVE_SAMPLE in names      # the books, every time
    for d, f in zip(dispatches, fetches):
        inside = [e for e in evs
                  if d["end"] <= e["start"] and e["end"] <= f["start"]]
        names = [e["name"] for e in inside]
        assert set(names) <= {*writes, spans.SERVE_STEP_INPUTS}, names
        # the inputs built ahead, after the lines: once a step where one
        # version is live
        assert names.count(spans.SERVE_STEP_INPUTS) == 1
        assert names[-1] == spans.SERVE_STEP_INPUTS
        under_a_step += len(names) - 1
    # all 14 tokens' lines less the three prefills' own, and three
    # terminals, were written in one of the two places
    assert under_a_step + forced == 14 - 3 + 3
    assert under_a_step > forced > 0


def test_live_on_the_dispatch_span_is_the_occupancy_arranged(traced_decode):
    dispatches = _named(traced_decode["spans"], spans.SERVE_STEP_DISPATCH)
    assert [d["live"] for d in dispatches] == traced_decode["occupancy"]
    assert traced_decode["occupancy"][:3] == [2, 2, 3]
    assert all(d["waiting"] == 0 and d["version"] == 0 for d in dispatches)
    assert traced_decode["rep"].decode_steps == len(dispatches)


def test_leaf_self_times_cover_the_loop_between_dispatches(traced_decode):
    evs = traced_decode["spans"]
    dispatches = _named(evs, spans.SERVE_STEP_DISPATCH)
    lo, hi = dispatches[0]["start"], dispatches[-1]["end"]
    own = self_ns(evs)
    ordered = sorted(evs, key=lambda e: (e["start"], -e["end"]))
    covered = sum(ns for i, ns in own.items()
                  if ordered[i]["name"] in SERVE_LEAVES
                  and lo <= ordered[i]["start"] and ordered[i]["end"] <= hi)
    assert covered / (hi - lo) >= 0.90


def test_journal_and_heartbeat_carry_the_same_facts(traced_decode):
    # the sinks validate every record against obsv/schema.py as it is
    # written (conftest sets DMT_VALIDATE_EVENTS), so being here at all
    # says the schema accepts the new fields
    from distributedmnist_tpu.obsv.schema import validate_event

    rep = traced_decode["rep"]
    read = lambda name: [json.loads(l) for l in  # noqa: E731
                         (rep.serve_dir / name).read_text().splitlines()]
    prefills = [r for r in read("serve_log.jsonl")
                if r.get("action") == "prefill"]
    assert [r["id"] for r in prefills] == ["a", "b", "c"]
    by_id = {p["id"]: p for p in _named(traced_decode["spans"],
                                        spans.SERVE_PREFILL)}
    for r in prefills:
        assert validate_event(r) == []
        assert r["prefill_ms"] == r["ttft_ms"] > 0
        assert r["queue_ms"] == pytest.approx(by_id[r["id"]]["queue_ms"])
    beats = read("train_log.jsonl")
    assert beats and all(validate_event(b) == [] for b in beats)
    assert all({"slots_live", "decode_steps", "tokens_sampled_device",
                "tokens_sampled_host"} <= set(b) for b in beats)
    assert beats[-1]["slots_live"] == 0
    assert beats[-1]["decode_steps"] == rep.decode_steps
    assert (beats[-1]["tokens_sampled_device"]
            + beats[-1]["tokens_sampled_host"]) == rep.tokens_streamed == 14
    # occupancy as the heartbeat saw it: written after a's finish, with
    # b and c still generating
    assert any(b["slots_live"] == 2 for b in beats)


def test_a_heartbeat_span_when_and_only_when_one_is_written(traced_decode):
    evs, rep = traced_decode["spans"], traced_decode["rep"]
    beats = (rep.serve_dir / "train_log.jsonl").read_text().splitlines()
    opened = _named(evs, spans.SERVE_HEARTBEAT)
    # every iteration asks; one is written after the first (the count
    # of terminals, 0, is news) and after each iteration in which a
    # request ended (three did, in three iterations)
    assert len(opened) == len(beats) == 4
    assert len(_named(evs, spans.SERVE_STEP_DISPATCH)) > len(opened)
    # each of those after a finish that no earlier heartbeat has told
    # of; none of the four under another span
    at = 0
    for e in opened:
        finished = [f for f in _named(evs, spans.SERVE_FINISH)
                    if at <= f["start"] and f["end"] <= e["start"]]
        assert bool(finished) == (e is not opened[0])
        assert not any(o["start"] <= e["start"] and e["end"] <= o["end"]
                       for o in evs if o is not e)
        at = e["end"]


def test_the_same_tokens_with_and_without_a_profiler_session(
        traced_decode, published, tmp_path):
    _, conns, occupancy = drive(published, tmp_path / "replica")
    assert occupancy == traced_decode["occupancy"]
    for req_id, conn in conns.items():
        assert conn.lines[-1]["status"] == "ok"
        assert conn.lines == traced_decode["conns"][req_id].lines


# -- the trainer -----------------------------------------------------------

def _train(tmp_path, name, **train):
    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.train.loop import Trainer

    losses = []
    cfg = ExperimentConfig.from_dict({
        "data": {"dataset": "synthetic", "batch_size": 64,
                 "synthetic_train_size": 256, "synthetic_test_size": 64},
        "model": {"compute_dtype": "float32"},
        "train": {"max_steps": 6, "log_every_steps": 3,
                  "train_dir": str(tmp_path / name),
                  "save_results_period": 0, "async_checkpoint": False,
                  **train}})
    Trainer(cfg).run(step_callback=lambda s, r: losses.append(r["loss"]))
    return losses


def test_trainer_spans_under_profile_steps(tmp_path):
    traced = _train(tmp_path, "traced", profile_steps=[2, 5])
    by_thread = host_spans(tmp_path / "traced" / "profile")
    loop = next(evs for evs in by_thread.values()
                if _named(evs, spans.TRAIN_DISPATCH))
    # one dispatch per step of the profiled window, carrying its step
    assert [d["step"] for d in _named(loop, spans.TRAIN_DISPATCH)] == [
        2, 3, 4]
    assert len(_named(loop, spans.TRAIN_FEED)) == 3
    # the log window that closes inside the profile (step 3) is flushed
    # there: the fetch of its losses is where the loop waits
    assert len(_named(loop, spans.TRAIN_FLUSH)) == 1
    assert not _named(loop, spans.TRAIN_PROBE)      # the probe is off
    names = {e["name"] for evs in by_thread.values() for e in evs}
    assert names <= {spans.TRAIN_FEED, spans.TRAIN_DISPATCH,
                     spans.TRAIN_FLUSH, spans.TRAIN_SAVE,
                     spans.PREFETCH_ASSEMBLE, spans.PREFETCH_PUT}
    # the same losses with and without a profiler session
    assert traced == _train(tmp_path, "plain")
    assert len(traced) == 6


def test_the_final_save_is_a_span(tmp_path):
    spans.start_profile(tmp_path / "trace")
    try:
        _train(tmp_path, "run", max_steps=2, log_every_steps=2)
    finally:
        spans.stop_profile()
    evs = [e for line in host_spans(tmp_path / "trace").values()
           for e in line]
    [save] = _named(evs, spans.TRAIN_SAVE)
    assert save["step"] == 2
    assert [d["step"] for d in _named(evs, spans.TRAIN_DISPATCH)] == [0, 1]


# -- names on the device ---------------------------------------------------

def _scopes_in(lowered) -> set[str]:
    """The scopes of obsv/spans.py that some operation's name holds."""
    import re
    text = lowered.as_text(debug_info=True)
    parts = {re.sub(r"^(?:\w+\()+|\)+$", "", part)
             for name in re.findall(r'loc\("([^"]+)"', text)
             for part in name.split("/")}
    return parts & set(spans.SCOPES)


def test_the_lowered_train_step_names_every_scope():
    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.train.loop import Trainer

    cfg = ExperimentConfig.from_dict({
        "data": {"dataset": "synthetic_lm", "batch_size": 16,
                 "synthetic_train_size": 64, "synthetic_test_size": 16,
                 "use_native_pipeline": False},
        "model": {**LM_MODEL, "remat": True},
        "optim": {"name": "momentum", "momentum": 0.9},
        "sync": {"mode": "quorum", "num_replicas_to_aggregate": 6},
        "train": {"max_steps": 1, "train_dir": ""}})
    t = Trainer(cfg)
    batch = t.topo.device_put_batch(next(t.train_feed.inner
                                         if hasattr(t.train_feed, "inner")
                                         else t.train_feed))
    lowered = t.step_fn.jitted.lower(
        t.state, batch, t.topo.zeros_measured(),
        t.step_fn.default_discipline())
    assert _scopes_in(lowered) == {"embed", "attention", "ffn", "head",
                                   "loss", "aggregate", "update", "timing"}
    text = lowered.as_text(debug_info=True)
    assert "module @jit_shard_fn" in text
    # backward and recomputed operations keep their layer's name
    assert "transpose(jvp(head))" in text
    assert "rematted_computation/ffn" in text


def test_the_decode_programs_carry_their_names_and_scopes(published,
                                                          tmp_path):
    from distributedmnist_tpu.core.config import DecodeConfig, ServeConfig
    from distributedmnist_tpu.servesvc.decode import DecodeReplica

    train_dir, cfg = published
    rep = DecodeReplica(
        train_dir, serve_dir=tmp_path / "replica", scfg=ServeConfig(),
        dcfg=DecodeConfig(decode_slots=2, block_size=8, num_blocks=16,
                          max_prompt_len=16, max_new_tokens=8), cfg=cfg)
    rep._load_initial()
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    step = rep._decode_jit.lower(
        rep._params, ints(2), ints(2), rep.cache.k, rep.cache.v,
        ints(2, rep.cache.max_blocks_per_seq), ints(2))
    assert "module @jit_decode_step" in step.as_text()
    assert _scopes_in(step) == {"embed", "attention", "cache_write",
                                "cache_gather", "ffn", "head"}
    assert "attention/cache_gather" in step.as_text(debug_info=True)
    prefill = rep._prefill_jit.lower(rep._params, ints(1, 16))
    assert "module @jit_decode_prefill" in prefill.as_text()
    assert _scopes_in(prefill) == {"embed", "attention", "ffn", "head"}
    ks = jnp.zeros((2, 16, 4, 16), jnp.float32)
    write = rep.cache._write.lower(rep.cache.k, rep.cache.v, ks, ks,
                                   ints(rep.cache.max_blocks_per_seq),
                                   ints(), block_size=8)
    assert "module @jit_write_prompt_kv" in write.as_text()


def _kernel_names(jaxpr, out=None) -> list[str]:
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _kernel_names(inner, out)
    return out


@pytest.mark.parametrize("seq, want", [
    (128, ["flash_fwd", "flash_bwd_fused"]),
    (256, ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])])
def test_every_flash_kernel_has_its_name(seq, want):
    from distributedmnist_tpu.ops.pallas_attention import \
        flash_attention_bshd

    q = jnp.ones((1, seq, 2, 64), jnp.float32)
    loss = lambda q: flash_attention_bshd(  # noqa: E731
        q, q, q, block_q=128, block_k=128).sum()
    assert _kernel_names(jax.make_jaxpr(jax.grad(loss))(q).jaxpr) == want


def test_the_paged_kernel_has_its_name():
    from distributedmnist_tpu.ops.pallas_paged_attention import \
        paged_attention

    pages = jnp.ones((4, 8, 2, 64))
    jaxpr = jax.make_jaxpr(lambda q: paged_attention(
        q, pages, pages, jnp.zeros((2, 2), jnp.int32),
        jnp.ones((2,), jnp.int32)))(jnp.ones((2, 2, 64))).jaxpr
    assert _kernel_names(jaxpr) == ["paged_decode"]


def test_span_names_are_unique_and_prefixed():
    names = [v for k, v in vars(spans).items()
             if k.isupper() and isinstance(v, str) and k != "PREFIX"]
    assert len(names) == len(set(names)) == 20
    assert all(n.startswith(spans.PREFIX) for n in names)
    assert len(set(spans.SCOPES)) == len(spans.SCOPES)
    # a span with no profiler session is a no-op that still nests
    with spans.span(spans.SERVE_SAMPLE, id="x", slot=0):
        with spans.span(spans.SERVE_STREAM, id="x"):
            assert np.asarray(jnp.ones(1)).sum() == 1
