"""The width of the block table a decode iteration hands the step
(servesvc/decode.py: ``_table_widths``, ``_table_width``, ``_tables_for``,
``_warm_up``): the narrowest of at most four compiled widths that holds
the longest live sequence and the token being written.

A toy geometry with eight blocks a sequence (block 4, prompts to 16,
16 new tokens), so that four rungs exist: 2, 4, 6, 8 blocks = 8, 16, 24,
32 positions. Everything runs on the CPU test mesh; nothing here is a
time."""

import glob
import json

import jax.numpy as jnp
import numpy as np
import pytest

from distributedmnist_tpu.obsv import spans

LM_MODEL = {"name": "transformer", "seq_len": 64, "model_dim": 64,
            "num_heads": 4, "num_layers": 2, "vocab_size": 32,
            "compute_dtype": "float32", "attention_impl": "dense"}
SLOTS, BLOCK, MAX_PROMPT, MAX_NEW = 3, 4, 16, 16
RUNGS = [2, 4, 6, 8]
FULL = RUNGS[-1]


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


def make_replica(published, serve_dir, **geometry):
    from distributedmnist_tpu.core.config import DecodeConfig, ServeConfig
    from distributedmnist_tpu.servesvc.decode import DecodeReplica

    train_dir, cfg = published
    dcfg = {"decode_slots": SLOTS, "block_size": BLOCK, "num_blocks": 40,
            "max_prompt_len": MAX_PROMPT, "max_new_tokens": MAX_NEW,
            **geometry}
    return DecodeReplica(train_dir, serve_dir=serve_dir,
                         scfg=ServeConfig(poll_secs=0.05),
                         dcfg=DecodeConfig(**dcfg), cfg=cfg)


def read_jsonl(path) -> list[dict]:
    return [json.loads(l) for l in path.read_text().splitlines()
            if l.strip()]


# -- the ladder ------------------------------------------------------------

@pytest.mark.parametrize("max_prompt, max_new, rungs", [
    (16, 16, RUNGS),            # eight blocks: the four quarters
    (16, 4, [2, 3, 4, 5]),      # five blocks: the quarters rounded up
    (8, 4, [1, 2, 3]),          # a toy table under four blocks: fewer
    (4, 4, [1, 2]),
    (3, 1, [1]),
])
def test_the_ladder_is_the_quarters_of_the_full_table(
        published, tmp_path, max_prompt, max_new, rungs):
    rep = make_replica(published, tmp_path / "replica",
                       max_prompt_len=max_prompt, max_new_tokens=max_new)
    assert rep._table_widths == rungs and len(rungs) <= 4
    assert rungs[-1] == rep.cache.max_blocks_per_seq == -(
        -(max_prompt + max_new) // BLOCK)


# -- what a dispatch is handed, with a step that computes nothing -----------

def direct(published, serve_dir):
    """A replica with its weights loaded, its loop driven from this
    thread, and its compiled step replaced by one that records what it
    was handed and picks token 0 for every slot."""
    rep = make_replica(published, serve_dir)
    rep._load_initial()
    calls = []

    def recording_step(params, tokens, positions, k, v, tables, lengths):
        calls.append({"params": params, "tables": np.asarray(tables),
                      "positions": np.asarray(positions),
                      "lengths": np.asarray(lengths)})
        n = tokens.shape[0]
        return (jnp.zeros((n, LM_MODEL["vocab_size"])),
                jnp.zeros((n,), jnp.int32), k, v)

    rep._decode_jit = recording_step
    return rep, calls


def place(rep, slot, length, version=None):
    """A sequence of ``length`` cached tokens in ``slot``, as admission
    and some iterations would have left it."""
    seq = rep._build_item({"id": f"s{slot}", "prompt": [1],
                           "max_tokens": MAX_NEW, "deadline_ms": 600000},
                          StubConn())
    seq.block_table = rep.cache.alloc_sequence(MAX_PROMPT + MAX_NEW)
    seq.params_step = seq.started_step = (
        rep.model_step if version is None else version)
    seq.length, seq.tokens = length, [1]
    rep._slots[slot] = seq
    rep._bump_tables_epoch()
    return seq


#: the longest sequence's length + 1 (what the step calls ``lengths``) at,
#: one under and one over each rung's edge, and the rung it needs
EDGES = [(7, 2), (8, 2), (9, 4), (15, 4), (16, 4), (17, 6), (23, 6),
         (24, 6), (25, 8), (31, 8), (32, 8)]


@pytest.mark.parametrize("need, rung", EDGES)
def test_a_dispatch_gets_the_smallest_rung_that_holds_its_longest(
        published, tmp_path, need, rung):
    rep, calls = direct(published, tmp_path / "replica")
    short = place(rep, 0, 2)
    long_ = place(rep, 2, need - 1)
    rep._step_active()
    [call] = calls
    width = call["tables"].shape[1]
    assert call["tables"].shape == (SLOTS, width) and width == rung
    assert max(call["lengths"]) == need
    assert width in RUNGS and width * BLOCK >= max(call["lengths"])
    assert all(w * BLOCK < need for w in RUNGS if w < width)
    # the position written lies inside the table, at the sequence's block
    written = call["positions"][2] // BLOCK
    assert written < width
    assert call["tables"][2, written] == long_.block_table[written] != 0
    np.testing.assert_array_equal(call["tables"][0],
                                  short.block_table[:width])
    np.testing.assert_array_equal(call["tables"][2],
                                  long_.block_table[:width])
    assert not call["tables"][1].any()            # the idle slot
    assert rep.decode_table_blocks == width


def test_a_sequence_growing_past_a_rung_gets_a_fresh_wider_table(
        published, tmp_path):
    """No admit, finish or restart between the three iterations, so the
    table epoch stands still; a cache keyed by (version, epoch) alone
    would hand the third one the second's table, two blocks wide, and
    the step would write position 8 through an entry that is not there."""
    rep, calls = direct(published, tmp_path / "replica")
    seq = place(rep, 1, 6)
    epoch, uploads, reuses = rep._tables_epoch, [], []
    for _ in range(3):
        rep._step_active()
        uploads.append(rep.table_uploads)
        reuses.append(rep.table_upload_reuses)
    assert rep._tables_epoch == epoch
    assert [int(c["lengths"][1]) for c in calls] == [7, 8, 9]
    assert [c["tables"].shape[1] for c in calls] == [2, 2, 4]
    # a step's table is asked for while the step before runs (the
    # inputs made ahead), so the wider one is uploaded an iteration
    # before the step that needs it, and found again for the one after
    assert uploads == [1, 2, 2] and reuses == [1, 1, 2]
    assert rep.step_inputs_rebuilt == 1 and rep.step_inputs_ahead == 2
    np.testing.assert_array_equal(calls[2]["tables"][1],
                                  seq.block_table[:4])
    assert calls[2]["tables"][1, 8 // BLOCK] == seq.block_table[2] != 0
    # and the narrower one is found again when the long one is gone
    place(rep, 0, 2)
    rep._finish_seq(1, seq, "max_tokens")
    rep._step_active()
    assert calls[3]["tables"].shape[1] == 2


def test_each_params_version_takes_the_width_of_its_own_sequences(
        published, tmp_path):
    rep, calls = direct(published, tmp_path / "replica")
    old = {"pinned": "params"}
    rep._versions[-7] = old           # an older version, still pinned
    mine = place(rep, 0, 20)          # on the current one: rung 6
    pinned = place(rep, 2, 3, version=-7)     # rung 2
    rep._step_active()
    first, second = calls             # versions in ascending order
    assert first["params"] is old and second["params"] is rep._params
    assert first["tables"].shape == (SLOTS, 2)
    assert second["tables"].shape == (SLOTS, 6)
    np.testing.assert_array_equal(first["tables"][2],
                                  pinned.block_table[:2])
    np.testing.assert_array_equal(second["tables"][0],
                                  mine.block_table[:6])
    # the rows of the other version's slots are the null block, and
    # their lengths zero, at either width
    assert not first["tables"][:2].any() and not second["tables"][1:].any()
    assert list(first["lengths"]) == [0, 0, 4]
    assert list(second["lengths"]) == [21, 0, 0]


# -- the real step: the same tokens at every width -------------------------

def scenario(published, serve_dir, full_width: bool, trace_dir=None):
    """Short sequences, then one long one admitted, grown to the top
    rung and finished, then a short one alone: every rung upwards, and
    down again. Returns per dispatch its width, the live rows and their
    logits, and per request the tokens it streamed."""
    rep = make_replica(published, serve_dir)
    if full_width:
        rep._table_widths = [FULL]    # the parent's one width
    rep._load_initial()
    step, calls, conns = rep._decode_jit, [], {}

    def recording_step(*args):
        out = step(*args)
        live = np.flatnonzero(np.asarray(args[6]))
        calls.append({"width": args[5].shape[1], "live": live,
                      "logits": np.asarray(out[0])[live]})
        return out

    rep._decode_jit = recording_step

    def admit(req_id, prompt_len, max_tokens):
        conns[req_id] = StubConn()
        prompt = [(3 * i + len(req_id)) % 31 + 1 for i in range(prompt_len)]
        seq = rep._build_item({"id": req_id, "prompt": prompt,
                               "max_tokens": max_tokens,
                               "deadline_ms": 600000}, conns[req_id])
        rep._journal({"action": "admit", "id": req_id,
                      "deadline_ms": 600000.0})
        rep._queue.put_nowait(seq)

    def iterations(n):
        for _ in range(n):
            rep._maybe_swap()
            rep._admit_new()
            rep._step_active()
            rep._maybe_heartbeat()

    if trace_dir is not None:
        spans.start_profile(trace_dir)
    try:
        admit("a", 3, 5)
        admit("bb", 3, MAX_NEW)       # grows through rungs 2, 4 (and 6)
        iterations(8)
        admit("long", MAX_PROMPT, MAX_NEW)    # rung 6, then the top
        iterations(10)
        admit("c", 2, 9)              # outlives the long one
        while any(s is not None for s in rep._slots) or rep._queue.qsize():
            iterations(1)
        iterations(1)     # parks idle: the last lines are written
    finally:
        if trace_dir is not None:
            spans.stop_profile()
    tokens = {req_id: conn.lines[-1]["tokens"]
              for req_id, conn in conns.items()}
    assert all(conn.lines[-1]["status"] == "ok" for conn in conns.values())
    return {"rep": rep, "calls": calls, "tokens": tokens}


@pytest.fixture(scope="module")
def both(published, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("both")
    ladder = scenario(published, tmp / "ladder", False, tmp / "trace")
    full = scenario(published, tmp / "full", True)
    return {"ladder": ladder, "full": full, "dir": tmp}


def test_the_scenario_crosses_every_rung_upwards_and_comes_back(both):
    widths = [c["width"] for c in both["ladder"]["calls"]]
    assert list(dict.fromkeys(widths)) == RUNGS       # 2, 4, 6, then 8
    top = len(widths) - 1 - widths[::-1].index(FULL)
    assert widths[top + 1:] and max(widths[top + 1:]) < FULL
    assert RUNGS[0] in widths[top + 1:]
    assert {c["width"] for c in both["full"]["calls"]} == {FULL}
    # a sequence did reach the last position a table can hold a token for
    assert len(both["ladder"]["tokens"]["long"]) == MAX_NEW


def test_the_tokens_are_those_of_the_full_width_and_the_logits_agree(both):
    ladder, full = both["ladder"], both["full"]
    assert ladder["tokens"] == full["tokens"]
    assert {k: len(v) for k, v in ladder["tokens"].items()} == {
        "a": 5, "bb": MAX_NEW, "long": MAX_NEW, "c": 9}
    assert len(ladder["calls"]) == len(full["calls"])
    narrower = 0
    for got, want in zip(ladder["calls"], full["calls"]):
        np.testing.assert_array_equal(got["live"], want["live"])
        np.testing.assert_allclose(got["logits"], want["logits"],
                                   rtol=1e-5, atol=1e-5)
        narrower += got["width"] < want["width"]
    assert narrower > len(full["calls"]) // 2


# -- the counter: the span, the heartbeat, the journal ---------------------

def test_blocks_is_on_every_dispatch_span(both):
    from jax.profiler import ProfileData

    [path] = glob.glob(
        f"{both['dir']}/trace/plugins/profile/*/*.xplane.pb")
    dispatches = sorted(
        (ev.start_ns, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for ev in line.events
        if ev.name == spans.SERVE_STEP_DISPATCH)
    calls = both["ladder"]["calls"]
    assert len(dispatches) == len(calls) == both["ladder"]["rep"].decode_steps
    assert all({"live", "waiting", "version", "blocks"} <= set(facts)
               for _, facts in dispatches)
    assert [facts["blocks"] for _, facts in dispatches] == [
        c["width"] for c in calls]


def test_the_heartbeat_carries_the_last_width_and_validates(both):
    from distributedmnist_tpu.obsv.schema import validate_event

    rep = both["ladder"]["rep"]
    beats = read_jsonl(rep.serve_dir / "train_log.jsonl")
    assert beats and all(validate_event(b) == [] for b in beats)
    assert all(b["decode_table_blocks"] in RUNGS for b in beats)
    # written after each finish's terminal, which goes out under the
    # step after the one that ended it: the long one's says the rung the
    # others went back to, above the narrowest, the last one's a narrow one
    assert max(b["decode_table_blocks"] for b in beats) > RUNGS[0]
    assert beats[-1]["decode_table_blocks"] == rep.decode_table_blocks < FULL
    assert validate_event({**beats[-1], "decode_table_blox": 2}) != []


# -- start(): every width compiled before a request is accepted ------------

def test_every_width_is_compiled_by_start_and_none_after(published,
                                                         tmp_path):
    from distributedmnist_tpu.obsv.schema import validate_event
    from distributedmnist_tpu.servesvc.client import ServeClient

    rep = make_replica(published, tmp_path / "replica")
    assert rep._steps == {} and rep._decode_jit._cache_size() == 0
    rep.start()
    try:
        # an executable a width, compiled ahead of time; the jitted
        # function is what a width without one would fall back to
        assert sorted(rep._steps) == RUNGS
        assert rep._decode_jit._cache_size() == 0
        assert rep.decode_steps == 0 and rep.tokens_streamed == 0
        assert not rep.cache.allocator.in_use     # the null block alone
        client = ServeClient([("127.0.0.1", rep.bound_port)],
                             deadline_s=60.0)
        # to max_prompt_len + max_new_tokens: the top rung
        long_ = client.generate(list(range(1, MAX_PROMPT + 1)),
                                max_tokens=MAX_NEW)
        assert rep.decode_table_blocks == FULL
        short = client.generate([5, 6, 7], max_tokens=4)
        assert rep.decode_table_blocks == RUNGS[0]
        assert long_["status"] == short["status"] == "ok"
        assert len(long_["tokens"]) == MAX_NEW and len(short["tokens"]) == 4
        assert rep._decode_jit._cache_size() == 0      # no width fell back
    finally:
        rep.stop()
    [started] = [r for r in read_jsonl(rep.serve_dir / "serve_log.jsonl")
                 if r.get("action") == "decode_start"]
    assert started["table_widths"] == RUNGS and validate_event(started) == []
