"""State that is a sequence's and not a token's, in the decode replica
(servesvc/kv_cache.py::SlotState, servesvc/decode.py): a toy hybrid of
state-space and attention layers (3 of 4 layers Mamba, 1 key-value head
for 4 queries) served through ``DecodeReplica``. A slot reused by a new
sequence starts from zeros; finish, restart and a weight swap each leave
no array of the old sequence readable; sequences of different lengths
batched get the logits each gets alone; ``tp_ranks > 1`` is refused."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributedmnist_tpu.core.config import (ConfigError, DecodeConfig,
                                              ExperimentConfig, ServeConfig)
from distributedmnist_tpu.models.registry import get_model
from distributedmnist_tpu.servesvc.kv_cache import SlotState

from test_decode import admit_direct, serve_records

HYBRID = {"name": "transformer", "seq_len": 64, "model_dim": 64,
          "num_heads": 4, "kv_heads": 1, "num_layers": 4, "vocab_size": 61,
          "ffn_dim": 96, "ssm_state_dim": 8, "ssm_dt_rank": 6,
          "attn_layer_period": 4, "attn_layer_offset": 1,
          "compute_dtype": "float32", "attention_impl": "dense"}
DECODE = dict(decode_slots=3, block_size=8, num_blocks=32, max_prompt_len=16,
              max_new_tokens=10)


def _publish(train_dir, step: int, seed: int):
    """Seeded weights of the toy hybrid as checkpoint ``step``."""
    from distributedmnist_tpu.parallel.api import init_train_state
    from distributedmnist_tpu.train.checkpoint import save_checkpoint
    cfg = ExperimentConfig.from_dict({
        "model": {**HYBRID, "init_seed": seed},
        "train": {"train_dir": str(train_dir), "seed": seed}})
    state = init_train_state(get_model(cfg.model), cfg)
    save_checkpoint(train_dir, state, step, extra={"config": cfg.to_dict()})
    return cfg, state.params


def _replica(tmp_path, policy="pin", **scfg):
    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    cfg, params = _publish(tmp_path / "publish", 10, seed=3)
    rep = DecodeReplica(
        tmp_path / "publish", serve_dir=tmp_path / "replica",
        scfg=ServeConfig(poll_secs=0.05, **scfg),
        dcfg=DecodeConfig(swap_policy=policy, **DECODE), cfg=cfg)
    return rep, cfg, params


def _loaded(tmp_path, policy="pin"):
    """The replica with its weights in, driven by hand (no threads)."""
    rep, cfg, params = _replica(tmp_path, policy)
    rep._load_initial()
    assert rep.model_step == 10
    return rep, cfg, params


def _drive(rep, rounds=40):
    for _ in range(rounds):
        rep._maybe_swap()
        rep._admit_new()
        rep._step_active()


def _slot(rep, slot: int):
    return (np.stack([np.asarray(s[slot]) for s in rep.state.state]),
            np.stack([np.asarray(t[:, slot]) for t in rep.state.tail]))


def _largest(arrays) -> float:
    return max(float(jnp.abs(a).max()) for a in arrays)


# -- the store alone ---------------------------------------------------------

def test_slot_state_alloc_write_reset_free():
    st = SlotState(layers=2, slots=3, state_dim=4, channels=8, taps_before=3)
    # a pair of arrays a layer
    assert [a.shape for a in st.state] == [(3, 4, 8)] * 2
    assert [a.shape for a in st.tail] == [(3, 3, 8)] * 2
    assert st.slot_bytes() == 2 * (4 * 8 * 4 + 3 * 8 * 4)
    # what a prefill hands over: [layers, batch, N, E], [layers, K-1, batch, E]
    ones = (jnp.ones((2, 2, 4, 8)), jnp.ones((2, 3, 2, 8)))
    with pytest.raises(ValueError, match="not allocated"):
        st.write(1, *ones)
    st.alloc(1)
    with pytest.raises(ValueError, match="already owns"):
        st.alloc(1)
    st.write(1, ones[0].at[:, 1].set(2.0), ones[1].at[:, :, 1].set(3.0),
             row=1)
    assert all(float(s[1].min()) == 2.0 for s in st.state)
    assert all(float(t[:, 1].min()) == 3.0 for t in st.tail)
    # the neighbours are as they were
    assert _largest(s[0] for s in st.state) == 0.0
    assert _largest(t[:, 2] for t in st.tail) == 0.0
    st.free(1)
    assert st.resets == 1
    assert _largest(st.state) == 0.0 and _largest(st.tail) == 0.0
    with pytest.raises(ValueError, match="owns no state"):
        st.free(1)
    st.alloc(1)            # and the slot can be handed out again


# -- the replica -------------------------------------------------------------

def test_decode_start_and_heartbeat_say_the_state(tmp_path):
    rep, _, _ = _replica(tmp_path)
    rep.start()
    try:
        start = next(r for r in serve_records(rep)
                     if r.get("action") == "decode_start")
        assert start["state_arrays"] == [[3, 8, 128], [3, 3, 128]]
        assert start["state_layers"] == 3
        assert start["state_slot_bytes"] == 3 * (8 * 128 * 4 + 3 * 128 * 4)
        assert start["state_device_bytes"] >= 3 * start["state_slot_bytes"]
        assert start["kv_heads"] == 1
        assert start["cache_arrays"][0] == [1, 32, 8, 1, 16]
        # no delta-rule layer: nothing for the state's kernel to hold
        assert start["state_arm"] == "xla"
        assert start["state_kernel_calls"] == [0] * len(start["table_widths"])
        assert "state_resets" in rep._pressure_fields()
    finally:
        rep.stop()


def test_a_reused_slot_starts_from_zeros_and_finish_leaves_nothing(tmp_path):
    rep, _, _ = _loaded(tmp_path)
    seq, conn = admit_direct(rep, {"id": "a", "prompt": [5, 9, 2, 7, 1],
                                   "max_tokens": 4, "deadline_ms": 120000})
    rep._admit_new()
    slot = rep._slots.index(seq)
    held_state, held_tail = _slot(rep, slot)
    assert np.abs(held_state).max() > 0 and np.abs(held_tail).max() > 0
    prefill = next(r for r in serve_records(rep)
                   if r.get("action") == "prefill")
    assert prefill["state_write_ms"] >= 0.0
    _drive(rep, 6)
    assert rep._slots[slot] is None          # finished
    for part in _slot(rep, slot):
        assert np.abs(part).max() == 0.0     # nothing of it readable
    assert rep.state.resets == 1
    first = [ln["token"] for ln in conn.lines if ln.get("stream") == "token"]
    # the same request again lands in the same (lowest free) slot and
    # generates the same tokens: it started from zeros, not from what a
    # predecessor left
    seq2, conn2 = admit_direct(rep, {"id": "b", "prompt": [5, 9, 2, 7, 1],
                                     "max_tokens": 4, "deadline_ms": 120000})
    _drive(rep, 6)
    again = [ln["token"] for ln in conn2.lines if ln.get("stream") == "token"]
    assert again == first and len(first) == 4


def test_an_idle_slot_is_not_stepped(tmp_path):
    rep, _, _ = _loaded(tmp_path)
    admit_direct(rep, {"id": "a", "prompt": [5, 9, 2], "max_tokens": 8, "deadline_ms": 120000})
    rep._admit_new()
    rep._step_active()
    rep._step_active()
    for idle in (1, 2):
        for part in _slot(rep, idle):
            assert np.abs(part).max() == 0.0


def test_batched_sequences_get_the_logits_each_gets_alone(tmp_path):
    """3 sequences of different lengths in one replica's slots against
    each alone in a session: greedy tokens equal (logits compared through
    what they pick at every step, and directly at the prefill)."""
    rep, cfg, params = _loaded(tmp_path)
    prompts = {"a": [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], "b": [2, 7],
               "c": [8, 2, 8, 1, 8, 2, 8]}
    conns = {}
    for rid, prompt in prompts.items():
        _, conns[rid] = admit_direct(rep, {"id": rid, "prompt": prompt,
                                           "max_tokens": 9, "deadline_ms": 120000})
    _drive(rep, 12)
    model = get_model(cfg.model)
    served = jax.tree.map(jnp.asarray, rep._params)
    for rid, prompt in prompts.items():
        got = [ln["token"] for ln in conns[rid].lines
               if ln.get("stream") == "token"]
        assert len(got) == 9
        ses = model.decode_session(served, rep.dcfg, jnp.float32)
        row = ses.prefill(np.asarray(prompt, np.int32))
        alone = [int(jnp.argmax(row))]
        for i in range(8):
            row = ses.step(alone[-1], len(prompt) + i)
            alone.append(int(jnp.argmax(row)))
        assert got == alone, rid


@pytest.mark.parametrize("policy", ["restart", "pin"])
def test_a_swap_leaves_no_state_of_the_old_weights(tmp_path, policy):
    rep, cfg, _ = _loaded(tmp_path, policy=policy)
    seq, conn = admit_direct(rep, {"id": "a", "prompt": [5, 9, 2, 7, 1, 3],
                                   "max_tokens": 8, "deadline_ms": 120000})
    rep._admit_new()
    rep._step_active()
    slot = rep._slots.index(seq)
    before = _slot(rep, slot)
    # a second publish, other weights
    _publish(tmp_path / "publish", 20, seed=4)
    got = rep.follower.poll(rep._read_weights)
    assert got is not None and got[0] == "swap"
    rep._staged = got[1:]
    rep._maybe_swap()
    assert rep.model_step == 20
    if policy == "restart":
        # the slot was zeroed and re-prefilled on the new weights: what
        # it holds is the new prompt state, which a fresh session on the
        # new weights reproduces
        assert rep.state.resets == 1
        assert seq.params_step == 20 and seq.restarts == 1
        model = get_model(cfg.model)
        ses = model.decode_session(jax.tree.map(jnp.asarray, rep._params),
                                   rep.dcfg, jnp.float32)
        ses.prefill(np.asarray([5, 9, 2, 7, 1, 3], np.int32))
        np.testing.assert_allclose(
            np.stack([np.asarray(s[0]) for s in ses.state.state]),
            _slot(rep, slot)[0], rtol=1e-5, atol=1e-6)
        assert np.abs(_slot(rep, slot)[0] - before[0]).max() > 0
    else:
        # pinned: the sequence keeps its state and its weights; a slot
        # admitted now runs on the new ones and its step leaves the
        # pinned slot's state as its own step left it
        assert seq.params_step == 10
        seq2, _ = admit_direct(rep, {"id": "b", "prompt": [1, 2, 3],
                                     "max_tokens": 4, "deadline_ms": 120000})
        rep._admit_new()
        other = rep._slots.index(seq2)
        assert other != slot
        held = _slot(rep, slot)
        rep._step_active()      # one step a version, each its own slots
        assert seq.length == 8 and seq2.length == 4
        assert np.abs(_slot(rep, slot)[0] - held[0]).max() > 0
    _drive(rep, 12)
    assert all(s is None for s in rep._slots)
    assert _largest(rep.state.state) == 0.0
    assert _largest(rep.state.tail) == 0.0


def test_tensor_parallel_ranks_are_refused(tmp_path):
    with pytest.raises(ConfigError, match="state-space"):
        _replica(tmp_path, tp_ranks=2)


# -- a tail of another width, a matrix state (PR 45) ---------------------------

def test_slot_state_with_a_tail_wider_than_its_channels():
    st = SlotState(layers=2, slots=3, state_dim=4, channels=8, taps_before=3,
                   tail_channels=24)
    assert [a.shape for a in st.state] == [(3, 4, 8)] * 2
    assert [a.shape for a in st.tail] == [(3, 3, 24)] * 2
    assert st.slot_bytes() == 2 * (4 * 8 * 4 + 3 * 24 * 4)
    st.alloc(2)
    st.write(2, jnp.ones((2, 1, 4, 8)), jnp.full((2, 3, 1, 24), 2.0))
    assert all(float(t[:, 2].min()) == 2.0 for t in st.tail)
    assert _largest(t[:, :2] for t in st.tail) == 0.0
    st.free(2)
    assert _largest(st.tail) == 0.0 and st.resets == 1


def test_slot_state_with_a_matrix_a_head():
    """A delta-rule layer's: ``state_dim`` a pair, the heads apart."""
    st = SlotState(layers=2, slots=3, state_dim=(2, 4), channels=4,
                   taps_before=3, tail_channels=24, dtype=jnp.bfloat16)
    assert [a.shape for a in st.state] == [(3, 2, 4, 4)] * 2
    assert st.state[0].dtype == jnp.float32
    assert st.tail[0].dtype == jnp.bfloat16
    assert st.slot_bytes() == 2 * (2 * 4 * 4 * 4 + 3 * 24 * 2)
    st.alloc(1)
    new = jnp.arange(2 * 2 * 2 * 4 * 4, dtype=jnp.float32).reshape(
        2, 2, 2, 4, 4)
    st.write(1, new, jnp.ones((2, 3, 2, 24)), row=1)
    assert all(float(jnp.abs(s[1] - new[i, 1]).max()) == 0.0
               for i, s in enumerate(st.state))
    assert _largest(s[0] for s in st.state) == 0.0
    assert _largest(s[2] for s in st.state) == 0.0
    st.reset(1)
    assert _largest(st.state) == 0.0


KDA_HYBRID = {"name": "transformer", "seq_len": 64, "model_dim": 64,
              "num_heads": 4, "num_layers": 4, "vocab_size": 61,
              "ffn_dim": 96, "kda_head_dim": 16, "attn_layer_period": 4,
              "attn_layer_offset": 3, "kv_latent_dim": 32, "qk_nope_dim": 16,
              "qk_rope_dim": 8, "v_head_dim": 16, "attn_head_gate": True,
              "routed_experts": 16, "held_experts": 4,
              "experts_per_token": 2, "shared_experts": 1,
              "expert_ffn_dim": 32, "routed_scaling": 2.5, "dense_layers": 1,
              "router_groups": 4, "router_topk_groups": 2,
              "compute_dtype": "float32", "attention_impl": "dense"}


def test_a_delta_rule_replica_says_its_three_stores_and_counts_its_pairs(
        tmp_path, monkeypatch):
    """Three delta-rule layers, one latent-attention layer, three routed
    feed-forwards, served through the loop: ``decode_start`` says the
    matrix state, the tail of another width and the latent cache's two
    row widths; the heartbeat carries the routed layers' pair counts
    beside ``state_resets``; sequences batched get the logits each gets
    alone."""
    monkeypatch.setitem(globals(), "HYBRID", KDA_HYBRID)
    rep, cfg, params = _replica(tmp_path)
    rep.start()
    try:
        start = next(r for r in serve_records(rep)
                     if r.get("action") == "decode_start")
        assert start["state_arrays"] == [[3, 4, 16, 16], [3, 3, 192]]
        assert start["state_layers"] == 3 and start["mixer_kind"] == "kda"
        assert start["attention_layers"] == 1 and start["kv_heads"] == 1
        assert start["cache_arrays"] == [[1, 32, 8, 32], [1, 32, 8, 8]]
        assert start["state_slot_bytes"] == 3 * (4 * 16 * 16 * 4
                                                 + 3 * 192 * 4)
        assert start["attention_arm"] == ["gather"] * len(
            start["table_widths"])
        # a CPU's step advances the state through ops/kda.py::step
        assert start["state_arm"] == "xla"
        assert start["state_kernel_calls"] == [0] * len(start["table_widths"])
    finally:
        rep.stop()


def test_through_the_state_kernel_a_readmitted_slot_starts_from_zeros(
        tmp_path, monkeypatch):
    """The asking function patched to the kernel (interpreted off the
    chip: no Mosaic call to count): ``decode_start`` says the arm, a
    sequence is served as its session alone decodes it through the XLA
    step, a finished sequence leaves its slot zeros, and the next
    sequence admitted to that slot gets the tokens it gets alone."""
    from distributedmnist_tpu.ops import kda
    monkeypatch.setitem(globals(), "HYBRID", KDA_HYBRID)
    asked = []
    monkeypatch.setattr(kda, "state_arm",
                        lambda *a: asked.append(a) or "kernel")
    rep, cfg, params = _replica(tmp_path)
    rep.start()
    try:
        start = next(r for r in serve_records(rep)
                     if r.get("action") == "decode_start")
        assert start["state_arm"] == "kernel"
        assert start["state_kernel_calls"] == [0] * len(start["table_widths"])
    finally:
        rep.stop()
    assert ((3, 4, 16, 16), jnp.float32) in asked       # the step asked too
    rep, cfg, params = _loaded(tmp_path / "by_hand")
    model = get_model(cfg.model)
    served = jax.tree.map(jnp.asarray, rep._params)
    slots = []
    for rid, prompt in (("a", [3, 1, 4, 1, 5, 9, 2, 6]), ("b", [2, 7, 1])):
        seq, conn = admit_direct(rep, {"id": rid, "prompt": prompt,
                                       "max_tokens": 6,
                                       "deadline_ms": 120000})
        rep._admit_new()
        slots.append(rep._slots.index(seq))
        assert np.abs(_slot(rep, slots[-1])[0]).max() > 0
        _drive(rep, 8)
        got = [ln["token"] for ln in conn.lines
               if ln.get("stream") == "token"]
        assert len(got) == 6
        # freed at finish: nothing of it is left for the next occupant
        assert _largest(rep.state.state) == 0.0
        assert _largest(rep.state.tail) == 0.0
        with monkeypatch.context() as unpatched:
            unpatched.setattr(kda, "state_arm", lambda *a: "xla")
            ses = model.decode_session(served, rep.dcfg, jnp.float32)
            row = ses.prefill(np.asarray(prompt, np.int32))
            alone = [int(jnp.argmax(row))]
            for i in range(5):
                row = ses.step(alone[-1], len(prompt) + i)
                alone.append(int(jnp.argmax(row)))
        assert got == alone, rid
    assert slots[0] == slots[1]                 # the same slot, handed out again
    assert rep._pressure_fields()["state_resets"] == 2


def test_delta_rule_sequences_batched_get_the_logits_each_gets_alone(
        tmp_path, monkeypatch):
    monkeypatch.setitem(globals(), "HYBRID", KDA_HYBRID)
    rep, cfg, params = _loaded(tmp_path)
    prompts = {"a": [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5], "b": [2, 7],
               "c": [8, 2, 8, 1, 8, 2, 8]}
    conns = {}
    for rid, prompt in prompts.items():
        _, conns[rid] = admit_direct(rep, {
            "id": rid, "prompt": prompt, "max_tokens": 9,
            "deadline_ms": 120000})
    _drive(rep, 6)
    fields = rep._pressure_fields()
    # the last step's pairs on the four held experts of three routed layers
    assert 0 <= fields["expert_pairs_held"] <= 3 * 3 * 2
    assert 0 <= fields["experts_touched"] <= 3 * 4
    assert fields["expert_pairs_held"] >= fields["experts_touched"]
    _drive(rep, 8)
    assert rep._pressure_fields()["state_resets"] == 3   # freed at finish
    model = get_model(cfg.model)
    served = jax.tree.map(jnp.asarray, rep._params)
    for rid, prompt in prompts.items():
        got = [ln["token"] for ln in conns[rid].lines
               if ln.get("stream") == "token"]
        assert len(got) == 9
        ses = model.decode_session(served, rep.dcfg, jnp.float32)
        row = ses.prefill(np.asarray(prompt, np.int32))
        alone = [int(jnp.argmax(row))]
        for i in range(8):
            row = ses.step(alone[-1], len(prompt) + i)
            alone.append(int(jnp.argmax(row)))
        assert got == alone, rid
    # nothing of the finished sequences is left in a slot
    assert _largest(rep.state.state) == 0.0
    assert _largest(rep.state.tail) == 0.0
