"""Continuous-batching decode service: sampling math, paged-decode ==
full-context parity (dense and flash prefill), the DecodeReplica
end-to-end over real sockets (streaming, refill, admission, graceful
drain), deterministic swap-policy drives (pin / restart), and the
decode_swap replay invariant over handcrafted journals."""

import json
import shutil
import socket
import threading
import time
from pathlib import Path

import numpy as np
import pytest

LM_MODEL = {"name": "transformer", "seq_len": 64, "model_dim": 64,
            "num_heads": 4, "num_layers": 2, "vocab_size": 32,
            "compute_dtype": "float32", "attention_impl": "dense"}


# ---------------------------------------------------------------------------
# sampling (models/registry.sample_token)
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_sample_token_greedy_is_argmax():
    import jax.numpy as jnp

    from distributedmnist_tpu.models.registry import sample_token

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(5, 32)).astype(np.float32))
    got = sample_token(logits)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.argmax(np.asarray(logits), axis=-1))


@pytest.mark.tier1
def test_sample_token_temperature_to_zero_converges_to_greedy():
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.models.registry import sample_token

    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    greedy = int(np.argmax(np.asarray(logits)))
    # tiny temperature: every key must sample the mode
    for seed in range(8):
        got = int(sample_token(logits, jax.random.PRNGKey(seed),
                               temperature=1e-6))
        assert got == greedy
    # top_k=1 is greedy at any temperature
    got = int(sample_token(logits, jax.random.PRNGKey(0),
                           temperature=5.0, top_k=1))
    assert got == greedy
    # missing key is a loud error, not a silent greedy fallback
    with pytest.raises(ValueError, match="PRNG key"):
        sample_token(logits, temperature=1.0)


@pytest.mark.tier1
def test_sample_token_top_k_restricts_support():
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.models.registry import sample_token

    rng = np.random.default_rng(2)
    logits_np = rng.normal(size=(32,)).astype(np.float32)
    logits = jnp.asarray(logits_np)
    top3 = set(np.argsort(logits_np)[-3:].tolist())
    for seed in range(24):
        got = int(sample_token(logits, jax.random.PRNGKey(seed),
                               temperature=2.0, top_k=3))
        assert got in top3


# ---------------------------------------------------------------------------
# paged decode == full-context forward (the numerical core)
# ---------------------------------------------------------------------------

def _greedy_paged(model, params, prompt, n_new, *, block_size=8,
                  num_blocks=32, slot=1, num_slots=3):
    """Greedy-generate ``n_new`` tokens through the paged cache, using
    a non-zero slot in a wider-than-needed slot shape (the fixed
    compiled shape the replica runs)."""
    import functools

    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    L, H, HD = model.decode_cache_shape
    cache = PagedKVCache(L, num_blocks, block_size, H, HD,
                         max_blocks_per_seq=16, dtype=jnp.float32)
    plen = len(prompt)
    bucket = 16
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :plen] = prompt
    logits, ks, vs = model.decode_prefill(params, jnp.asarray(toks))
    table = cache.alloc_sequence(plen + n_new)
    cache.write_prompt(table, ks[:, 0], vs[:, 0], plen)
    step = jax.jit(functools.partial(model.decode_step,
                                     block_size=block_size))
    gen = [int(jnp.argmax(logits[0, plen - 1]))]
    length = plen
    width = cache.max_blocks_per_seq
    for _ in range(n_new - 1):
        tokens = np.zeros(num_slots, np.int32)
        positions = np.zeros(num_slots, np.int32)
        lengths = np.zeros(num_slots, np.int32)
        tables = np.zeros((num_slots, width), np.int32)
        tokens[slot] = gen[-1]
        positions[slot] = length
        lengths[slot] = length + 1
        tables[slot] = table
        lg, cache.k, cache.v = step(
            params, jnp.asarray(tokens), jnp.asarray(positions),
            cache.k, cache.v, jnp.asarray(tables), jnp.asarray(lengths))
        length += 1
        gen.append(int(jnp.argmax(lg[slot])))
    return gen


@pytest.mark.tier1
def test_paged_decode_matches_full_context_greedy():
    """Greedy decode through the paged cache reproduces the argmax of
    the full-context forward token-for-token — the claim that one
    compiled decode shape serves any sequence length correctly."""
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model

    model = get_model(ModelConfig(**LM_MODEL))
    params = model.init(jax.random.PRNGKey(0))
    prompt = [3, 7, 1, 9, 2, 11, 4]
    gen = _greedy_paged(model, params, prompt, 9)
    ref_seq = list(prompt)
    for _ in range(9):
        full = model.apply(params,
                           jnp.asarray(np.array(ref_seq, np.int32)[None]),
                           train=False)
        ref_seq.append(int(jnp.argmax(full[0, -1])))
    assert gen == ref_seq[len(prompt):]


@pytest.mark.tier1
def test_prefill_logits_match_plain_apply_and_flash_kernel():
    """The prefill export is the SAME forward as the training apply
    (logits bitwise-close), through the dense path and the fused
    pallas flash kernel alike — the prefill-reuses-the-flash-kernel
    claim, pinned in interpret mode."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model

    dense_cfg = ModelConfig(**LM_MODEL)
    flash_cfg = dataclasses.replace(dense_cfg, attention_impl="flash")
    dense = get_model(dense_cfg)
    flash = get_model(flash_cfg)
    params = dense.init(jax.random.PRNGKey(3))
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, size=(2, 16))
        .astype(np.int32))
    ref = dense.apply(params, toks, train=False)
    for model, tol in ((dense, 0.0), (flash, 2e-4)):
        logits, ks, vs = model.decode_prefill(params, toks)
        assert ks.shape == (2, 2, 16, 4, 16) and vs.shape == ks.shape
        if tol == 0.0:
            np.testing.assert_array_equal(np.asarray(logits),
                                          np.asarray(ref))
        else:
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(ref),
                                       rtol=tol, atol=tol)


@pytest.mark.tier1
def test_decode_config_validation():
    from distributedmnist_tpu.core.config import ConfigError, DecodeConfig

    DecodeConfig().validate()
    with pytest.raises(ConfigError, match="swap_policy"):
        DecodeConfig(swap_policy="replay").validate()
    with pytest.raises(ConfigError, match="num_blocks"):
        DecodeConfig(num_blocks=4, max_prompt_len=64,
                     max_new_tokens=64, block_size=8).validate()
    assert DecodeConfig(block_size=16, max_prompt_len=64,
                        max_new_tokens=33).max_blocks_per_seq() == 7


# ---------------------------------------------------------------------------
# shared LM publisher (one short deterministic training run per module)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_published(tmp_path_factory):
    staging = tmp_path_factory.mktemp("lm_staging")
    from distributedmnist_tpu.core.config import ExperimentConfig
    cfg = ExperimentConfig.from_dict({
        "data": {"dataset": "synthetic_lm", "batch_size": 32,
                 "synthetic_train_size": 256, "synthetic_test_size": 64,
                 "use_native_pipeline": False},
        "model": dict(LM_MODEL),
        "train": {"max_steps": 20, "log_every_steps": 10,
                  "train_dir": str(staging),
                  "save_interval_steps": 10, "save_results_period": 0,
                  "async_checkpoint": False},
    })
    from distributedmnist_tpu.train.loop import Trainer
    Trainer(cfg).run()
    steps = sorted(int(p.name[5:13]) for p in staging.glob("ckpt-*.msgpack"))
    assert steps == [10, 20]
    return {"staging": staging, "cfg": cfg, "steps": steps}


def publish_step(staging: Path, serve_dir: Path, step: int) -> None:
    name = f"ckpt-{step:08d}.msgpack"
    serve_dir.mkdir(parents=True, exist_ok=True)
    for sfx in ("", ".sha256"):
        shutil.copy2(staging / (name + sfx), serve_dir / (name + sfx))
    tmp = serve_dir / "checkpoint.json.tmp"
    tmp.write_text(json.dumps({"latest_step": step, "latest_path": name,
                               "written_at": time.time()}))
    tmp.replace(serve_dir / "checkpoint.json")


def make_replica(lm_published, tmp_path, policy="pin", slots=3,
                 max_new=10):
    from distributedmnist_tpu.core.config import DecodeConfig, ServeConfig
    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    serve_src = tmp_path / "publish"
    publish_step(lm_published["staging"], serve_src, 10)
    rep = DecodeReplica(
        serve_src, serve_dir=tmp_path / "replica",
        scfg=ServeConfig(poll_secs=0.05),
        dcfg=DecodeConfig(decode_slots=slots, block_size=8,
                          num_blocks=32, max_prompt_len=16,
                          max_new_tokens=max_new, swap_policy=policy),
        cfg=lm_published["cfg"])
    return rep, serve_src


def serve_records(rep) -> list[dict]:
    return [json.loads(l) for l in
            (rep.serve_dir / "serve_log.jsonl").read_text().splitlines()
            if l.strip()]


class StubConn:
    """Direct-drive connection double: collects every streamed line."""

    def __init__(self):
        self.lines: list[dict] = []

    def settimeout(self, t):
        pass

    def gettimeout(self):
        return None

    def sendall(self, b):
        for line in b.decode().splitlines():
            self.lines.append(json.loads(line))

    def close(self):
        pass


def admit_direct(rep, req: dict) -> object:
    """Admit one request the way _handle_conn would (validation +
    admit journal + queue), without a socket — what lets the swap
    tests drive the decode loop deterministically."""
    conn = StubConn()
    seq = rep._build_item(req, conn)
    assert seq is not None
    rep._journal({"action": "admit", "id": seq.req_id,
                  "deadline_ms": round(
                      (seq.deadline_at - seq.admitted_at) * 1e3, 3)})
    rep._queue.put_nowait(seq)
    return seq, conn


# ---------------------------------------------------------------------------
# the replica end-to-end (real sockets, threads, streaming)
# ---------------------------------------------------------------------------

def test_decode_replica_streams_and_batches_end_to_end(lm_published,
                                                       tmp_path):
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.servesvc.loadgen import (make_prompt_fn,
                                                       run_load)

    rep, serve_src = make_replica(lm_published, tmp_path)
    rep.start()
    try:
        client = ServeClient([("127.0.0.1", rep.bound_port)],
                             deadline_s=30.0)
        meta = client.meta()
        assert meta["decode"] is True and meta["vocab_size"] == 32
        assert meta["model_step"] == 10
        streamed = []
        # ids 100/101: the loadgen below issues ids 0..11, and a reused
        # id is now a DUPLICATE the dedup cache answers from the first
        # execution — colliding would hide two of the 14 executions
        out = client.generate([1, 2, 3, 4, 5], request_id=100,
                              max_tokens=6,
                              on_token=lambda r: streamed.append(
                                  r.get("token")))
        assert out["status"] == "ok", out
        assert out["finish_reason"] == "max_tokens"
        assert len(out["tokens"]) == 6 and streamed == out["tokens"]
        assert out["ttft_ms"] is not None
        # greedy determinism: the same prompt generates the same tokens
        out2 = client.generate([1, 2, 3, 4, 5], request_id=101,
                               max_tokens=6)
        assert out2["tokens"] == out["tokens"]
        # continuous batching: 3 slots, 12 concurrent requests of
        # wildly different lengths — all complete, zero drops, and the
        # loadgen summary carries the decode latency split
        s = run_load(client, 12, 4, make_prompt_fn(32, 16),
                     journal_path=tmp_path / "lg.jsonl", decode=True)
        assert s["dropped"] == 0 and s["errors"] == 0, s
        assert s["responses"] == 12
        assert s["tokens_streamed"] > 12  # every response streamed
        assert "ttft_ms" in s and "inter_token_ms" in s
        assert s["tokens_per_sec"] > 0
        recs = serve_records(rep)
        fins = [r for r in recs if r["action"] == "decode_finish"]
        assert len(fins) >= 14  # 2 singles + 12 loadgen
        # more sequences finished than slots exist: slots turned over
        assert len(fins) > rep.dcfg.decode_slots
        admits = [r for r in recs if r["action"] == "admit"]
        assert len(admits) == len(fins)  # exactly-one-terminal
        # bad requests are typed, never crashes: too-long prompt,
        # out-of-vocab token, missing prompt
        for bad in ({"id": 90, "prompt": [1] * 99},
                    {"id": 91, "prompt": [999]},
                    {"id": 92, "inputs": [1, 2]}):
            got = _raw_request(rep.bound_port, bad)
            assert got["status"] == "rejected"
            assert got["reason"] == "bad_request"
    finally:
        rep.stop()
    # graceful stop: journal closed with serve_stop, no dangling admits
    recs = serve_records(rep)
    assert recs[-1]["action"] == "serve_stop"


def _raw_request(port: int, payload: dict, timeout=10.0) -> dict:
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as conn:
        conn.settimeout(timeout)
        conn.sendall((json.dumps(payload) + "\n").encode())
        buf = b""
        while b"\n" not in buf:
            chunk = conn.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf.decode().splitlines()[0])


def test_decode_replica_sheds_typed_on_stop_mid_generation(lm_published,
                                                           tmp_path):
    """SIGTERM-equivalent stop with generations in flight: every
    admitted request still reaches exactly one typed terminal."""
    from distributedmnist_tpu.servesvc.client import ServeClient

    rep, _ = make_replica(lm_published, tmp_path, slots=2, max_new=10)
    rep.start()
    outcomes = []

    def gen(i):
        client = ServeClient([("127.0.0.1", rep.bound_port)],
                             deadline_s=10.0, max_attempts=1)
        outcomes.append(client.generate([1, 2, 3], request_id=i,
                                        max_tokens=10))

    try:
        threads = [threading.Thread(target=gen, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.25)  # let some get admitted / generating
    finally:
        rep.stop()
    for t in threads:
        t.join(timeout=15)
    assert len(outcomes) == 4
    # every client outcome is terminal (ok, a typed reject, or the
    # client-side error after its bounded retry) — nothing hangs
    assert all(o.get("status") in ("ok", "rejected", "error")
               for o in outcomes), outcomes
    recs = serve_records(rep)
    admits = sum(1 for r in recs if r["action"] == "admit")
    terminal = sum(1 for r in recs
                   if r["action"] == "decode_finish"
                   or (r["action"] == "reject" and r.get("admitted")))
    assert admits == terminal  # server-side books balance


# ---------------------------------------------------------------------------
# swap-during-generation policies (deterministic direct drive)
# ---------------------------------------------------------------------------

def _drive_swap(lm_published, tmp_path, policy):
    rep, serve_src = make_replica(lm_published, tmp_path, policy=policy,
                                  slots=2, max_new=8)
    rep._load_initial()
    assert rep.model_step == 10
    seq, conn = admit_direct(rep, {"id": 7, "prompt": [1, 2, 3],
                                   "max_tokens": 8,
                                   "deadline_ms": 60000})
    rep._admit_new()
    assert rep._slots[0] is seq and len(seq.tokens) == 1
    rep._step_active()
    publish_step(lm_published["staging"], serve_src, 20)
    got = rep.follower.poll(rep._read_weights)
    assert got is not None and got[0] == "swap"
    rep._staged = got[1:]
    rep._maybe_swap()
    assert rep.model_step == 20
    while rep._slots[0] is not None:
        rep._step_active()
    rep._admit_new()    # the loop parks idle: what was decided is written
    return rep, conn


def test_swap_policy_pin_finishes_on_old_weights(lm_published, tmp_path):
    rep, conn = _drive_swap(lm_published, tmp_path, "pin")
    recs = serve_records(rep)
    fin = next(r for r in recs if r["action"] == "decode_finish")
    sw = next(r for r in recs if r["action"] == "weight_swap"
              and not r.get("initial"))
    assert fin["model_step"] == fin["started_step"] == 10
    assert sw["sequences_pinned"] == 1
    assert sw["sequences_restarted"] == 0
    assert not any(r["action"] == "seq_restart" for r in recs)
    # the pinned version was released the moment its sequence finished
    assert not rep._versions
    # a fresh admission runs on the NEW weights
    seq2, conn2 = admit_direct(rep, {"id": 8, "prompt": [4, 5],
                                     "max_tokens": 2,
                                     "deadline_ms": 60000})
    rep._admit_new()
    while rep._slots[0] is not None:
        rep._step_active()
    rep._admit_new()
    assert conn2.lines[-1]["model_step"] == 20
    # the invariant replays green over the real journal
    assert _decode_swap_violations(rep, tmp_path / "pin_trial") == []


def test_swap_policy_restart_reprefills_with_license(lm_published,
                                                     tmp_path):
    rep, conn = _drive_swap(lm_published, tmp_path, "restart")
    recs = serve_records(rep)
    fin = next(r for r in recs if r["action"] == "decode_finish")
    sw = next(r for r in recs if r["action"] == "weight_swap"
              and not r.get("initial"))
    restart = next(r for r in recs if r["action"] == "seq_restart")
    assert fin["model_step"] == 20 and fin["started_step"] == 10
    assert fin["restarts"] == 1
    assert sw["sequences_restarted"] == 1
    assert restart["from_step"] == 10 and restart["to_step"] == 20
    assert restart["tokens_discarded"] >= 1
    # the stream told the client to reset before re-streaming
    events = [l.get("stream") for l in conn.lines if "stream" in l]
    assert "restart" in events
    # the terminal carries the full regenerated sequence
    final = conn.lines[-1]
    assert final["status"] == "ok" and len(final["tokens"]) == 8
    assert _decode_swap_violations(rep, tmp_path / "restart_trial") == []


def _decode_swap_violations(rep, troot):
    from distributedmnist_tpu.obsv.invariants import check_serving
    (troot / "worker1").mkdir(parents=True)
    shutil.copy2(rep.serve_dir / "serve_log.jsonl",
                 troot / "worker1" / "serve_log.jsonl")
    violations, applicable, _, decode_applicable = check_serving(
        troot, {"serve_workers": [1]}, [])
    assert applicable and decode_applicable
    return [v.to_dict() for v in violations]


# ---------------------------------------------------------------------------
# where a token is picked: by the compiled step (greedy), or drawn from
# the slot's row of the logits (temperature > 0)
# ---------------------------------------------------------------------------

def _parents_token(s, row, n_tokens: int) -> int:
    """What the parent of PR 25 streamed for sequence ``s`` holding
    ``n_tokens`` tokens, given this row of the step's logits: it fetched
    the row and ran ``sample_token`` on it, with the sequence's key."""
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.models.registry import sample_token
    if s.temperature <= 0.0:
        return int(sample_token(jnp.asarray(row)))
    key = jax.random.fold_in(jax.random.PRNGKey(s.sample_seed),
                             n_tokens + 1000 * s.restarts)
    return int(sample_token(jnp.asarray(row), key,
                            temperature=s.temperature, top_k=s.top_k))


GREEDY, DRAW, DRAW_K8 = {}, {"temperature": 0.8}, {"temperature": 0.8,
                                                   "top_k": 8}


@pytest.mark.parametrize("case, sampling", [
    ("all_greedy", [GREEDY, GREEDY, GREEDY]),
    ("mixed", [GREEDY, DRAW, DRAW_K8]),
    ("idle_slot", [DRAW_K8, GREEDY]),
    ("tie", [GREEDY, GREEDY, DRAW]),
    ("two_versions", [GREEDY, DRAW_K8, GREEDY]),
])
def test_streamed_tokens_are_the_parents_rule_on_the_same_logits(
        lm_published, tmp_path, case, sampling):
    rep, serve_src = make_replica(lm_published, tmp_path, slots=3,
                                  max_new=10)
    rep._load_initial()
    if case == "tie":
        # the head is tied to the embedding: rows 2k and 2k+1 made equal
        # tie every logit with its neighbour, the largest included
        embed = rep._params["embed"]
        rep._params = {**rep._params,
                       "embed": embed.at[1::2].set(embed[0::2])}
    step, calls = rep._decode_jit, []

    def recording_step(*args):
        out = step(*args)
        calls.append(np.asarray(out[0]))
        return out

    rep._decode_jit = recording_step
    seqs = []

    def admit(n):
        seqs.append(admit_direct(rep, {
            "id": n, "prompt": [1 + n, 2, 3 + n][:2 + n % 2],
            "max_tokens": 4 + 3 * n, "deadline_ms": 60000,
            **sampling[n]}))

    picked = {"device": 0, "host": 0}
    versions_live = set()

    def iteration():
        rep._admit_new()
        before = [(i, s, len(s.tokens))
                  for i, s in enumerate(rep._slots) if s is not None]
        first = len(calls)
        rep._step_active()
        vers = sorted({s.params_step for _, s, _ in before})
        versions_live.add(len(vers))
        assert len(calls) - first == len(vers)
        for logits, ver in zip(calls[first:], vers):
            for i, s, n in before:
                if s.params_step != ver:
                    continue
                assert s.tokens[n] == _parents_token(s, logits[i], n)
                picked["host" if s.temperature > 0 else "device"] += 1
                if case == "tie" and s.temperature <= 0:
                    top = s.tokens[n]
                    assert top % 2 == 0     # the first index of the tie
                    assert logits[i, top] == logits[i, top + 1]

    late = len(sampling) - 1 if case == "two_versions" else len(sampling)
    for n in range(late):
        admit(n)
    iteration()
    if case == "two_versions":
        publish_step(lm_published["staging"], serve_src, 20)
        rep._staged = rep.follower.poll(rep._read_weights)[1:]
        rep._maybe_swap()
        admit(late)
    while any(s is not None for s in rep._slots) or rep._queue.qsize():
        iteration()
    rep._admit_new()    # parks idle: the last lines are written

    assert versions_live == ({1, 2} if case == "two_versions" else {1})
    if case == "two_versions":
        assert [s.params_step for s, _ in seqs] == [10, 10, 20]
    for (s, conn), asked in zip(seqs, sampling):
        assert s.temperature == asked.get("temperature", 0.0)
        assert len(s.tokens) == s.max_tokens
        assert [l["token"] for l in conn.lines
                if l.get("stream") == "token"] == s.tokens
        assert conn.lines[-1]["tokens"] == s.tokens
    # every prefill's first token goes through `_sample`, on the host
    assert rep.tokens_sampled_host == len(seqs) + picked["host"]
    assert rep.tokens_sampled_device == picked["device"]
    assert (rep.tokens_sampled_device + rep.tokens_sampled_host
            == rep.tokens_streamed == sum(len(s.tokens) for s, _ in seqs))
    # counted from the requests alone: every token of a greedy request
    # after its first is the step's own pick
    assert rep.tokens_sampled_device == sum(
        s.max_tokens - 1 for s, _ in seqs if s.temperature <= 0) > 0


# ---------------------------------------------------------------------------
# between a step's tokens and the next launch: the books are kept at the
# fetch, the lines written under the next step, the next step's inputs
# built ahead (plain block, latent routed block, hybrid with slot state)
# ---------------------------------------------------------------------------

KINDS = ("plain", "latent", "hybrid")


def _kind_model(kind: str) -> dict:
    # the other two files import this one: asked for when first needed
    if kind == "plain":
        return dict(LM_MODEL)
    if kind == "latent":
        from test_latent_decode import LATENT
        return dict(LATENT)
    from test_slot_state import HYBRID
    return dict(HYBRID)


@pytest.fixture(scope="module")
def kind_states():
    """Seeded weights a kind and a seed, made once: (cfg, state)."""
    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.parallel.api import init_train_state
    made = {}

    def state(kind: str, seed: int):
        if (kind, seed) not in made:
            cfg = ExperimentConfig.from_dict({
                "model": {**_kind_model(kind), "init_seed": seed},
                "train": {"seed": seed}})
            made[kind, seed] = (cfg, init_train_state(get_model(cfg.model),
                                                      cfg))
        return made[kind, seed]
    return state


def kind_publish(kind_states, kind, train_dir, step: int, seed: int):
    from distributedmnist_tpu.train.checkpoint import save_checkpoint
    cfg, state = kind_states(kind, seed)
    save_checkpoint(train_dir, state, step, extra={"config": cfg.to_dict()})
    return cfg, state


class RawConn:
    """A socket double that keeps the bytes as sent and tells a shared
    log of every line; ``dies_after`` lines it raises as a reset peer
    does."""

    def __init__(self, log: list, dies_after: int | None = None):
        self.log, self.sent, self.dies_after = log, b"", dies_after
        self.closed = 0

    def settimeout(self, t):
        pass

    def gettimeout(self):
        return None

    def sendall(self, b):
        if (self.dies_after is not None
                and self.sent.count(b"\n") >= self.dies_after):
            raise ConnectionResetError("the client is gone")
        self.sent += b
        line = json.loads(b)
        self.log.append(("line", line["id"], line.get(
            "index", line.get("stream") or line["status"])))

    def close(self):
        self.closed += 1


def kind_replica(kind_states, kind, tmp_path, monkeypatch, policy="pin",
                 slots=2):
    """A replica of this kind, weights (seed 3, step 10) loaded, driven
    from the test's thread; ``log`` says in order every prefill, every
    step's dispatch returned, every fetch opened, every park, swap and
    line, and whether anything was still queued when a prefill, a park
    or a swap began."""
    import jax

    from distributedmnist_tpu.core.config import DecodeConfig, ServeConfig
    from distributedmnist_tpu.servesvc import decode
    cfg, state = kind_publish(kind_states, kind, tmp_path / "publish", 10, 3)
    rep = decode.DecodeReplica(
        tmp_path / "publish", serve_dir=tmp_path / "replica",
        scfg=ServeConfig(poll_secs=0.05),
        dcfg=DecodeConfig(decode_slots=slots, block_size=8, num_blocks=32,
                          max_prompt_len=16, max_new_tokens=10,
                          swap_policy=policy), cfg=cfg)
    rep._load_initial()
    log: list = []
    step, prefill, install, get = (rep._decode_jit, rep._prefill_jit,
                                   rep._install, rep._queue.get)

    def stepped(*args):
        out = step(*args)
        log.append(("dispatch", [np.asarray(a) for a in (
            args[1], args[2], args[5], args[6])]))
        return out

    def prefilled(*args):
        log.append(("prefill", len(rep._deferred)))
        return prefill(*args)

    def installed(*args, **kwargs):
        log.append(("swap", len(rep._deferred)))
        return install(*args, **kwargs)

    def parked(*args, timeout=None, **kwargs):
        if timeout is not None:
            log.append(("park", len(rep._deferred)))
        return get(*args, timeout=timeout, **kwargs)

    def fetched(x):
        log.append(("fetch",))
        return device_get(x)

    device_get = jax.device_get
    monkeypatch.setattr(decode.jax, "device_get", fetched)
    rep._decode_jit, rep._prefill_jit = stepped, prefilled
    rep._install, rep._queue.get = installed, parked
    return rep, log, cfg, state


def kind_admit(rep, log, req_id, prompt, max_tokens, dies_after=None,
               **sampling):
    conn = RawConn(log, dies_after)
    seq = rep._build_item({"id": req_id, "prompt": prompt,
                           "max_tokens": max_tokens, "deadline_ms": 600000,
                           **sampling}, conn)
    rep._journal({"action": "admit", "id": req_id, "deadline_ms": 600000.0})
    rep._queue.put_nowait(seq)
    return seq, conn


def loop_once(rep):
    rep._maybe_swap()
    rep._admit_new()
    rep._step_active()
    rep._maybe_heartbeat()


def drive_to_idle(rep, limit=60):
    for _ in range(limit):
        loop_once(rep)
        if (all(s is None for s in rep._slots) and not rep._waiting
                and not rep._queue.qsize()):
            loop_once(rep)       # parks: what the last fetch decided goes
            return
    raise AssertionError("the replica never ran dry")


def greedy_by_full_forward(cfg, state, prompt, n: int) -> list[int]:
    """``n`` greedy tokens by the model's plain forward over the whole
    context each time: no cache, no replica."""
    import jax
    import jax.numpy as jnp

    from distributedmnist_tpu.models.registry import get_model
    model, seq, out = get_model(cfg.model), list(prompt), []
    with jax.default_matmul_precision("highest"):
        for _ in range(n):
            logits = model.apply(state.params, jnp.asarray([seq]))
            out.append(int(jnp.argmax(logits[0, -1])))
            seq.append(out[-1])
    return out


def parents_bytes(req_id, tokens, reason="max_tokens", step=10) -> bytes:
    """What the tree before PR 44 sent a greedy request's connection
    (its `_stream_token` and `_finish_seq`): one line a token, the
    terminal last."""
    lines = [{"id": req_id, "stream": "token", "token": t, "index": i,
              "model_step": step} for i, t in enumerate(tokens)]
    lines.append({"id": req_id, "status": "ok", "tokens": tokens,
                  "finish_reason": reason, "model_step": step,
                  "started_step": step})
    return "".join(json.dumps(line) + "\n" for line in lines).encode()


@pytest.mark.parametrize("kind", KINDS)
def test_a_connection_receives_the_bytes_it_always_did(
        kind_states, tmp_path, monkeypatch, kind):
    """Three greedy requests on two slots: the third is admitted into
    the slot a ``max_tokens`` finish freed, in the very next iteration;
    every connection's bytes are the parent's, terminal last and once."""
    rep, log, cfg, state = kind_replica(kind_states, kind, tmp_path,
                                        monkeypatch)
    asked = {"a": ([1, 2, 3], 3), "b": ([4, 5, 6, 7, 8], 7),
             "c": ([9, 10], 4)}
    conns = {rid: kind_admit(rep, log, rid, prompt, n)[1]
             for rid, (prompt, n) in asked.items()}
    loop_once(rep)                  # a and b prefilled and stepped once
    loop_once(rep)                  # a's third token: its finish decided
    assert rep._slots[0] is None and rep.sequences_finished == 1
    assert b'"status"' not in conns["a"].sent       # decided, not written
    rep._maybe_swap()
    rep._admit_new()                # the very next admission takes the slot
    assert rep._slots[0] is not None and rep._slots[0].req_id == "c"
    assert conns["a"].sent.endswith(parents_bytes("a", [0])[-2:])
    rep._step_active()
    drive_to_idle(rep)
    for rid, (prompt, n) in asked.items():
        tokens = greedy_by_full_forward(cfg, state, prompt, n)
        assert conns[rid].sent == parents_bytes(rid, tokens), rid
        assert conns[rid].closed == 1
    assert rep._deferred == []
    fins = [r for r in serve_records(rep)
            if r["action"] == "decode_finish"]
    assert sorted(r["id"] for r in fins) == ["a", "b", "c"]
    assert rep.lines_deferred == sum(n - 1 for _, n in asked.values()) + 3
    assert rep.tokens_streamed == sum(n for _, n in asked.values())


@pytest.mark.parametrize("kind", KINDS)
def test_a_tokens_line_goes_out_under_the_next_step(
        kind_states, tmp_path, monkeypatch, kind):
    """One request alone: the line of the token a fetch brought is
    written after the NEXT step's dispatch returned and before its
    fetch opens; the last one and the terminal when the loop parks."""
    rep, log, _, _ = kind_replica(kind_states, kind, tmp_path, monkeypatch)
    kind_admit(rep, log, "a", [3, 1, 4], 4)
    drive_to_idle(rep)
    parks = [i for i, e in enumerate(log) if e[0] == "park"]
    order = [e if e[0] == "line" else e[0] for e in log[parks[0]:]]
    assert order == [
        "park",                                 # the request arrives
        "prefill", ("line", "a", 0),            # first token: at once
        "dispatch", "fetch",                    # brings token 1
        "dispatch", ("line", "a", 1), "fetch",
        "dispatch", ("line", "a", 2), "fetch",  # brings 3: max_tokens
        ("line", "a", 3), ("line", "a", "ok"),  # about to park: written
        "park"]
    # nothing waited when the prefill or either park began
    assert [e[1] for e in log if e[0] in ("prefill", "park")] == [0, 0, 0]
    assert rep.flushes == {"dispatch": 2, "prefill": 0, "swap": 0,
                           "park": 1, "stop": 0}
    assert rep.lines_deferred == 4


@pytest.mark.parametrize("kind", KINDS)
def test_a_vanished_client_finishes_one_iteration_later_and_once(
        kind_states, tmp_path, monkeypatch, kind):
    rep, log, _, _ = kind_replica(kind_states, kind, tmp_path, monkeypatch)
    # takes lines 0 and 1, resets on line 2: the parent learnt it writing
    # token 2 inside the iteration that picked it and finished with 3
    # tokens; now token 2's line goes under the next step, whose fetch
    # brings token 3 before the books say the client is gone
    seq, conn = kind_admit(rep, log, "gone", [5, 6, 7], 9, dies_after=2)
    _, other = kind_admit(rep, log, "stays", [8, 9], 6)
    drive_to_idle(rep)
    fins = [r for r in serve_records(rep) if r["action"] == "decode_finish"]
    [gone] = [r for r in fins if r["id"] == "gone"]
    assert gone["reason"] == "client_gone" and gone["tokens_streamed"] == 4
    assert conn.sent.count(b"\n") == 2 and conn.closed == 1
    [stays] = [r for r in fins if r["id"] == "stays"]
    assert stays["reason"] == "max_tokens"
    assert other.sent.count(b"\n") == 7 and other.closed == 1
    assert all(s is None for s in rep._slots)
    assert not rep.cache.allocator.in_use


@pytest.mark.parametrize("kind", KINDS)
def test_nothing_is_queued_when_a_prefill_a_swap_a_restart_or_a_park_begins(
        kind_states, tmp_path, monkeypatch, kind):
    """And nothing after the drain on stop. Restart policy: the swap
    re-prefills both live sequences, so its flush is a restart's too."""
    rep, log, _, _ = kind_replica(kind_states, kind, tmp_path, monkeypatch,
                                  policy="restart")
    _, a = kind_admit(rep, log, "a", [1, 2, 3], 9)
    _, b = kind_admit(rep, log, "b", [4, 5], 9)
    loop_once(rep)
    loop_once(rep)
    assert len(rep._deferred) == 2          # the second fetch's two lines
    kind_publish(kind_states, kind, tmp_path / "publish", 20, 4)
    rep._staged = rep.follower.poll(rep._read_weights)[1:]
    loop_once(rep)                          # swap: flush, restart both
    assert rep.model_step == 20 and rep.flushes["swap"] == 1
    _, c = kind_admit(rep, log, "c", [6, 7, 8], 3)   # waits for a slot
    for _ in range(3):
        loop_once(rep)
    assert len(rep._deferred) == 2 and rep._waiting
    rep._stop.set()
    rep._batch_loop()                       # the drain alone
    assert rep._deferred == [] and rep.flushes["stop"] == 1
    # whatever began, began with nothing queued
    began = [e for e in log if e[0] in ("prefill", "swap", "park")]
    assert {e[0] for e in began} == {"prefill", "swap", "park"}
    assert all(e[1] == 0 for e in began), began
    assert len([e for e in began if e[0] == "prefill"]) == 4
    # per connection: its lines in order, the restart marker after the
    # lines of the old weights and before the new ones', then the typed
    # terminal of a stopping replica, last and once
    for conn, rid in ((a, "a"), (b, "b")):
        lines = [json.loads(l) for l in conn.sent.splitlines()]
        kinds = [l.get("stream") or l["status"] for l in lines]
        assert kinds == (["token"] * 3 + ["restart"] + ["token"] * 5
                         + ["rejected"]), (rid, kinds)
        assert [l["index"] for l in lines if l.get("stream") == "token"] \
            == [0, 1, 2, 0, 1, 2, 3, 4]
        assert [l["model_step"] for l in lines[:-1]] == [10] * 3 + [20] * 6
        assert lines[-1]["reason"] == "shutting_down" and conn.closed == 1
    assert [json.loads(l)["reason"] for l in c.sent.splitlines()] \
        == ["shutting_down"]


def parents_inputs(rep, ver: int):
    """What the tree before PR 44 handed one version's step, from the
    books as they are now (its `_step_active`, the block under
    `dml.serve.step.inputs`, and `_tables_for`): kept as the oracle."""
    num_slots = rep.dcfg.decode_slots
    mine = [(i, s) for i, s in enumerate(rep._slots)
            if s is not None and s.params_step == ver]
    width = rep._table_width(mine)
    tokens = np.zeros((num_slots,), np.int32)
    positions = np.zeros((num_slots,), np.int32)
    lengths = np.zeros((num_slots,), np.int32)
    tables = np.zeros((num_slots, width), np.int32)
    for i, s in mine:
        tokens[i] = s.tokens[-1]
        positions[i] = s.length
        lengths[i] = s.length + 1
        tables[i] = s.block_table[:width]
    return [i for i, _ in mine], [tokens, positions, tables, lengths]


@pytest.mark.parametrize("kind, policy", [
    ("plain", "pin"), ("plain", "restart"), ("latent", "pin"),
    ("hybrid", "restart"), ("hybrid", "pin")])
def test_the_step_is_handed_what_the_parent_built_ahead_or_not(
        kind_states, tmp_path, monkeypatch, kind, policy):
    """Through an admission, a finish, a draw, a swap (a restart, or a
    second version pinned) every step's positions, tables and lengths
    are the parent's arrays to the element, and its tokens at every live
    slot (an idle slot's is the step's own pick where the array never
    left the device); the counters say which iterations took the inputs
    built ahead."""
    rep, log, _, _ = kind_replica(kind_states, kind, tmp_path, monkeypatch,
                                  policy=policy, slots=3)
    paths, fed = [], []

    def iteration(expect: str):
        rep._maybe_swap()
        rep._admit_new()
        versions = sorted({s.params_step for s in rep._slots
                           if s is not None})
        want = [parents_inputs(rep, ver) for ver in versions]
        before = (rep.step_inputs_ahead, rep.step_inputs_rebuilt, len(log))
        rep._step_active()
        rep._maybe_heartbeat()
        got = [e[1] for e in log[before[2]:] if e[0] == "dispatch"]
        assert len(got) == len(want)
        for (live, arrays), handed in zip(want, got):
            for name, a, b in zip(("tokens", "positions", "tables",
                                   "lengths"), arrays, handed):
                if name == "tokens":
                    a, b = a[live], b[live]
                np.testing.assert_array_equal(a, b, err_msg=name)
        ahead = rep.step_inputs_ahead - before[0]
        rebuilt = rep.step_inputs_rebuilt - before[1]
        assert ahead + rebuilt == len(versions)
        paths.append("ahead" if ahead else "rebuilt")
        assert paths[-1] == expect, paths

    kind_admit(rep, log, "a", [1, 2, 3], 5)
    iteration("rebuilt")            # the first step of anything
    iteration("ahead")
    kind_admit(rep, log, "b", [4, 5, 6, 7], 9)
    iteration("rebuilt")            # an admission
    iteration("ahead")              # a's fifth token: finish decided
    iteration("rebuilt")            # a finish
    iteration("ahead")
    kind_admit(rep, log, "draws", [8, 9], 6, temperature=0.8, top_k=8)
    iteration("rebuilt")
    iteration("ahead")              # b greedy, one draws: tokens uploaded
    kind_publish(kind_states, kind, tmp_path / "publish", 20, 4)
    rep._staged = rep.follower.poll(rep._read_weights)[1:]
    # restart: both re-prefilled on step 20. pin: both stay on step 10,
    # nothing about their step changes
    iteration("rebuilt" if policy == "restart" else "ahead")
    iteration("ahead")
    kind_admit(rep, log, "late", [2, 4, 6], 4)
    # pin: a second version is live, a step each, none built ahead
    iteration("rebuilt")
    iteration("rebuilt" if policy == "pin" else "ahead")
    drive_to_idle(rep)
    beat = rep._pressure_fields()
    assert beat["step_inputs_ahead"] == rep.step_inputs_ahead >= 6
    assert beat["step_inputs_rebuilt"] == rep.step_inputs_rebuilt >= 5
    assert (beat["step_inputs_ahead"] + beat["step_inputs_rebuilt"]
            == rep.decode_steps)
    assert beat["lines_deferred"] == rep.lines_deferred > 0
    flushes = beat["line_flushes"]
    assert sum(flushes.values()) <= rep.decode_steps + 5
    # forced: one before the swap, one before each admission that found
    # lines waiting, the parks; never one an iteration
    assert flushes["swap"] == 1 and flushes["stop"] == 0
    assert 1 <= flushes["prefill"] <= 4 and flushes["park"] >= 1
    assert flushes["dispatch"] >= rep.decode_steps - 8
    from distributedmnist_tpu.obsv.schema import validate_event
    assert validate_event({"event": "heartbeat", "step": 1, "time": 0.0,
                           **beat}) == []
    assert validate_event({"event": "heartbeat", "step": 1, "time": 0.0,
                           **beat, "lines_defered": 1}) != []


# ---------------------------------------------------------------------------
# the decode_swap invariant over handcrafted journals
# ---------------------------------------------------------------------------

def _decode_trial(tmp_path, records) -> Path:
    trial = tmp_path / "trial"
    (trial / "worker1").mkdir(parents=True)
    (trial / "worker1" / "serve_log.jsonl").write_text(
        "".join(json.dumps({"event": "serve", **r}) + "\n"
                for r in records))
    (trial / "worker1" / "train_log.jsonl").write_text("")
    return trial


def _swap_rec(step, t, **over):
    return {"action": "weight_swap", "step": step, "from_step": step - 10,
            "digest": "d", "tier": "fp32", "source_artifact": None,
            "source_digest": "d", "swap_ms": 1.0, "time": t, **over}


def _finish_rec(rid, model_step, started_step, t):
    return {"action": "decode_finish", "id": rid, "reason": "max_tokens",
            "tokens_streamed": 4, "model_step": model_step,
            "started_step": started_step, "latency_ms": 5.0, "time": t}


def _check(trial):
    from distributedmnist_tpu.obsv.invariants import check_serving
    violations, applicable, _, decode_applicable = check_serving(
        trial, {"serve_workers": [1]}, [])
    assert applicable
    return decode_applicable, {v.invariant for v in violations}, violations


@pytest.mark.tier1
def test_decode_swap_invariant_clean_pin_and_restart(tmp_path):
    # pin: every finish on its started step — green
    dec, by_inv, _ = _check(_decode_trial(tmp_path / "a", [
        _swap_rec(20, 100.0, sequences_pinned=1, sequences_restarted=0),
        {"action": "admit", "id": 1, "deadline_ms": 100.0, "time": 100.1},
        _finish_rec(1, 10, 10, 100.2),
    ]))
    assert dec and "decode_swap" not in by_inv
    # restart: step changed WITH the seq_restart license — green
    dec, by_inv, _ = _check(_decode_trial(tmp_path / "b", [
        _swap_rec(20, 100.0, sequences_pinned=0, sequences_restarted=1),
        {"action": "admit", "id": 1, "deadline_ms": 100.0, "time": 100.05},
        {"action": "seq_restart", "id": 1, "from_step": 10,
         "to_step": 20, "tokens_discarded": 2, "time": 100.1},
        _finish_rec(1, 20, 10, 100.2),
    ]))
    assert dec and "decode_swap" not in by_inv


@pytest.mark.tier1
def test_decode_swap_invariant_catches_unlicensed_step_change(tmp_path):
    dec, by_inv, v = _check(_decode_trial(tmp_path, [
        _swap_rec(20, 100.0, sequences_pinned=0, sequences_restarted=0),
        {"action": "admit", "id": 1, "deadline_ms": 100.0, "time": 100.1},
        _finish_rec(1, 20, 10, 100.2),  # drifted, no license
    ]))
    assert dec and "decode_swap" in by_inv
    assert "no live seq_restart license" in v[0].detail


@pytest.mark.tier1
def test_decode_swap_invariant_catches_restart_without_swap(tmp_path):
    dec, by_inv, _ = _check(_decode_trial(tmp_path / "none", [
        {"action": "admit", "id": 1, "deadline_ms": 100.0, "time": 100.0},
        {"action": "seq_restart", "id": 1, "from_step": 10,
         "to_step": 20, "tokens_discarded": 2, "time": 100.1},
        _finish_rec(1, 20, 10, 100.2),
    ]))
    assert dec and "decode_swap" in by_inv
    # ORDER matters: a swap journaled only AFTER the restart is not a
    # license — the restart ran on weights nothing had installed yet
    dec, by_inv, _ = _check(_decode_trial(tmp_path / "late", [
        {"action": "admit", "id": 1, "deadline_ms": 100.0, "time": 100.0},
        {"action": "seq_restart", "id": 1, "from_step": 10,
         "to_step": 20, "tokens_discarded": 2, "time": 100.1},
        _swap_rec(20, 100.15),
        _finish_rec(1, 20, 10, 100.2),
    ]))
    assert "decode_swap" in by_inv


@pytest.mark.tier1
def test_decode_swap_license_is_consumed_per_generation(tmp_path):
    """Request ids recycle across sweeps in one journal: a legitimate
    restart in generation 1 must not launder a LATER generation's
    unlicensed mixed-weights finish under the same id."""
    dec, by_inv, _ = _check(_decode_trial(tmp_path, [
        _swap_rec(20, 100.0, sequences_pinned=0, sequences_restarted=1),
        {"action": "admit", "id": 1, "deadline_ms": 100.0, "time": 100.05},
        {"action": "seq_restart", "id": 1, "from_step": 10,
         "to_step": 20, "tokens_discarded": 2, "time": 100.1},
        _finish_rec(1, 20, 10, 100.2),   # licensed — consumed here
        {"action": "admit", "id": 1, "deadline_ms": 100.0, "time": 100.3},
        _finish_rec(1, 30, 20, 100.4),   # drifted again, NO new license
    ]))
    assert dec and "decode_swap" in by_inv


@pytest.mark.tier1
def test_decode_swap_invariant_skipped_for_classification_trials(tmp_path):
    dec, by_inv, _ = _check(_decode_trial(tmp_path, [
        _swap_rec(20, 100.0),
        {"action": "admit", "id": 1, "deadline_ms": 100.0, "time": 100.1},
        {"action": "respond", "id": 1, "model_step": 20, "tier": "fp32",
         "batch": 1, "bucket": 1, "latency_ms": 2.0, "time": 100.2},
    ]))
    assert not dec and "decode_swap" not in by_inv


# ---------------------------------------------------------------------------
# chaos decode-mode wiring + the acceptance trial
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_chaos_decode_payload_wiring():
    from distributedmnist_tpu.launch.chaos import ChaosConfig
    from distributedmnist_tpu.launch.cluster import ClusterError

    cfg = ChaosConfig(payload="serving", serve_decode=True,
                      serve_replicas=2)
    cmd = cfg.resolved_train_command()
    assert "model.name=transformer" in cmd
    assert "data.dataset=synthetic_lm" in cmd
    wc = cfg.resolved_worker_commands()
    assert set(wc) == {"1", "2"}
    assert all("--decode" in c for c in wc.values())
    assert all("--max-new-tokens 16" in c for c in wc.values())
    # prompt + generation must fit the compact LM's position table —
    # the replica validates at boot, so the payload must pin both
    assert all("--max-prompt-len 16" in c for c in wc.values())
    # decode serves fp32 only: quant tiers refused at config build
    with pytest.raises(ClusterError, match="fp32"):
        ChaosConfig(payload="serving", serve_decode=True,
                    serve_precision_tiers=("int8",))


@pytest.mark.slow  # boots an LM publisher + 2 decode replicas + reference
def test_decode_chaos_trial_end_to_end(tmp_path):
    """The acceptance scenario: a seeded decode-mode serving trial —
    replica killed mid-generation, published checkpoint torn, live
    generate load throughout — completes with dropped == 0 and ALL
    serving invariants (including decode_swap) passing."""
    from distributedmnist_tpu.launch.chaos import ChaosConfig, run_campaign

    cfg = ChaosConfig(
        name="decodetrial", workdir=str(tmp_path), payload="serving",
        serve_decode=True, trials=1, seed=0, until_step=60,
        save_interval_steps=10, serve_replicas=2,
        request_deadline_s=10.0, serve_fault_window=(3, 20),
        shrink=False, trial_timeout_s=420.0)
    summary = run_campaign(cfg)
    assert summary["all_green"], summary
    assert summary["faults"]["fired"] > 0, summary["faults"]
    sv = summary["serving"]
    assert sv["issued"] > 0 and sv["dropped"] == 0, sv
    assert sv["tokens_streamed"] > 0
    assert sv["ttft_p99_ms"] is not None
    inv = summary["invariants"]
    assert inv["decode_swap"]["fail"] == 0
    assert (inv["decode_swap"]["pass"]
            + inv["decode_swap"]["skipped"]) == 1
