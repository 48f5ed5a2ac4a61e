"""The serving check drives a decode session (``benchmark/lib/cell.py``):
the default one over the paged exports, built as the replica builds its
cache, and a model record's own, whatever arrays it holds. A toy whose
state is a sequence's and not a token's passes through its own session;
the same toy forgetting its state between two steps does not."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import pytest

import toy_recurrent
import toy_recurrent_model
from bench_toy import (routed_toy_cell,  # noqa: F401
                       routed_toy_unregistered, toy_cell)
from benchmark.lib import cell as cell_lib, decode_controls, serving
from distributedmnist_tpu.core.config import (DecodeConfig, ExperimentConfig,
                                              effective_model_config)
from distributedmnist_tpu.models.registry import get_model

DCFG = DecodeConfig(decode_slots=2, block_size=4, num_blocks=9,
                    max_prompt_len=24, max_new_tokens=8)
PLAIN_KEYS = {"decode_logits_max_rel_err", "positions", "ok"}
ROUTED_KEYS = PLAIN_KEYS | {"routing_slack_max", "routing_agreement",
                            "routing_ids_valid", "routing_flag_diff",
                            "routing_ok"}


def _recurrent_check(seed: int, dtype, forgetful: bool = False,
                     said: dict | None = None) -> dict:
    config = toy_recurrent.toy_config()
    params = toy_recurrent.init(jax.random.PRNGKey(seed), config)
    cell = dataclasses.replace(toy_cell("serve_closed"), config=config,
                               arch=toy_recurrent)
    return serving.check_decode_against_reference(
        toy_recurrent_model.record(forgetful), params, DCFG, dtype,
        config["vocab_size"], cell, seed, said=said)


@pytest.mark.parametrize("seed", [0, 1, 2147483999])
def test_a_state_that_is_a_sequences_passes_through_its_own_session(seed):
    said: dict = {}
    check = _recurrent_check(seed, jnp.float32, said=said)
    assert set(check) == PLAIN_KEYS and check["positions"] == 9
    assert check["ok"] and check["decode_logits_max_rel_err"] < 1e-5
    # the record's own session, not a paged cache: one row a slot
    assert said == {"session": "toy_recurrent", "state_arrays": [[2, 2, 32]]}
    # in the precision a cell serves in, under the harness's own limit
    served = _recurrent_check(seed, jnp.bfloat16)
    assert served["ok"]
    assert 1e-4 < served["decode_logits_max_rel_err"] \
        < serving.DECODE_LOGITS_TOL


@pytest.mark.parametrize("seed", [0, 1, 2147483999])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_session_that_forgets_the_state_between_steps_is_refused(seed,
                                                                   dtype):
    check = _recurrent_check(seed, dtype, forgetful=True)
    assert not check["ok"]
    assert check["decode_logits_max_rel_err"] > 3 * serving.DECODE_LOGITS_TOL
    assert decode_controls.failed_by(check) == ["decode_logits_max_rel_err"]


def test_a_step_asked_again_leaves_the_state_as_the_first_left_it():
    config = toy_recurrent.toy_config()
    params = toy_recurrent.init(jax.random.PRNGKey(3), config)
    session = serving.decode_session(toy_recurrent_model.record(), params,
                                     DCFG, jnp.float32)
    session.prefill(jnp.arange(5))
    first = session.step(7, 5)
    state = session.state
    assert (session.step(7, 5) == first).all()
    assert (session.state == state).all()
    assert not (session.step(7, 6) == first).all()


def _opt_toy():
    cell = toy_cell("serve_closed")
    cfg = ExperimentConfig.from_dict(serving.experiment(
        cell, types.SimpleNamespace(seed=5, workdir=cell_lib.ROOT)))
    model_cfg = effective_model_config(cfg, serving=True)
    model = get_model(model_cfg)
    return cell, cfg, model_cfg, model, model.init(jax.random.PRNGKey(5))


def test_a_record_without_the_export_takes_the_default_session():
    cell, cfg, model_cfg, model, params = _opt_toy()
    assert not hasattr(model, "decode_session")
    said: dict = {}
    check = serving.check_decode_against_reference(
        model, params, cfg.decode, jnp.dtype(model_cfg.compute_dtype),
        cfg.model.vocab_size, cell, 5, said=said)
    assert set(check) == PLAIN_KEYS and check["ok"]
    # built as the replica builds its cache: on a CPU a head's rows keep
    # their own width and the program's rule says the gather; the table
    # is the narrowest of the replica's widths that holds 64 + 8 tokens
    d = cfg.decode
    assert said["session"] == "paged" and said["attention_arm"] == "gather"
    assert said["cache_arrays"] == [[2, d.num_blocks, d.block_size, 4, 16]] * 2
    assert said["table_blocks"] == 5 and d.max_blocks_per_seq() == 6
    assert said["step_compiled_bytes"] is None \
        or said["step_compiled_bytes"] > 0


def test_the_default_session_is_the_replicas_cache_and_widths():
    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    cell, cfg, model_cfg, model, params = _opt_toy()
    session = serving.PagedSession(model, params, cfg.decode,
                                   jnp.dtype(model_cfg.compute_dtype))
    # the replica's own rule for the width an iteration hands the step,
    # asked of a replica that holds this cache and these widths
    rep = object.__new__(DecodeReplica)
    rep.cache, rep._table_widths = session.cache, session._widths
    session._table = session.cache.alloc_sequence(96)
    for length in (1, 15, 16, 40, 70, 95):
        width, _ = session._inputs(0, length)
        assert width == rep._table_width(
            [(0, types.SimpleNamespace(length=length))])


def test_a_routed_record_that_brings_the_paged_session_reads_the_same():
    from test_bench_routed import _inputs, _model
    cell = routed_toy_cell("serve_closed", 64)
    params, _ = _inputs(cell.config, 11)
    dcfg = DecodeConfig(**cell.config["serve"]["decode"])
    model = _model(64)
    args = (params, dcfg, jnp.bfloat16, cell.config["vocab_size"], cell, 11)
    default = serving.check_decode_against_reference(model, *args)
    assert set(default) == ROUTED_KEYS and default["ok"]
    own = types.SimpleNamespace(
        decode_session=lambda p, d, t: serving.PagedSession(model, p, d, t))
    said: dict = {}
    assert serving.check_decode_against_reference(
        own, *args, said=said) == default
    assert said["session"] == "paged"

    # a session of a routed model that returns no routing when asked
    class Mute(serving.PagedSession):
        def step(self, token, position, return_routing=False):
            return super().step(token, position)
    mute = types.SimpleNamespace(
        decode_session=lambda p, d, t: Mute(model, p, d, t))
    with pytest.raises(cell_lib.BenchmarkError, match="cannot vouch"):
        serving.check_decode_against_reference(mute, *args)


def test_the_step_control_is_the_default_sessions_alone():
    with pytest.raises(cell_lib.BenchmarkError, match="brings this control"):
        decode_controls._mask_newest(toy_recurrent_model.record())
