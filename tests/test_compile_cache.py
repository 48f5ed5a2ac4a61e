"""Restart-latency fast path: the one rule for where the persistent
compile cache lives (JAX_COMPILATION_CACHE_DIR where set, one fixed
in-checkout path otherwise, off on request) and bitwise parity of the
precompiled step vs the cold-compiled one."""

import json

import jax
import pytest

from distributedmnist_tpu.core import compile_cache as cc
from distributedmnist_tpu.core.config import CompileConfig, ExperimentConfig

pytestmark = pytest.mark.tier1


# ---------------------------------------------------------------------------
# config + persistent-cache wiring
# ---------------------------------------------------------------------------

def test_compile_config_roundtrip_and_unknown_key():
    cfg = ExperimentConfig.from_dict(
        {"compile": {"persistent_cache": False, "precompile": False}})
    assert not cfg.compile.persistent_cache
    assert ExperimentConfig.from_dict(cfg.to_dict()).compile == cfg.compile
    from distributedmnist_tpu.core.config import ConfigError
    with pytest.raises(ConfigError, match="min_entry"):
        ExperimentConfig.from_dict({"compile": {"min_entry": 1}})
    # the directory is not a config knob (core/compile_cache.py's rule)
    with pytest.raises(ConfigError, match="cache_dir"):
        ExperimentConfig.from_dict({"compile": {"cache_dir": "/x"}})


def test_resolve_cache_dir_rule(monkeypatch, tmp_path):
    # unset → the ONE fixed path inside the checkout
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    assert cc.resolve_cache_dir(CompileConfig()) == repo / ".jax_cache"
    assert cc.resolve_cache_dir() == cc.DEFAULT_CACHE_DIR
    # set → that directory
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(tmp_path / "env"))
    assert cc.resolve_cache_dir(CompileConfig()) == tmp_path / "env"
    # the enable flag wins over both
    assert cc.resolve_cache_dir(CompileConfig(persistent_cache=False)) is None
    monkeypatch.delenv(cc.CACHE_DIR_ENV)
    assert cc.resolve_cache_dir(CompileConfig(persistent_cache=False)) is None


@pytest.fixture()
def jax_cache_config():
    """Snapshot/restore the jax cache config a test flips, and drop the
    cache object jax built for a tmp dir pytest is about to delete."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_on = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_enable_compilation_cache", prev_on)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    cc._applied, cc._enabled_dir = False, None


def _unique_compile(tag: str) -> None:
    """Compile a program no earlier test can have compiled: jax's
    in-memory compilation LRU sits ABOVE the persistent cache, and an
    aliased HLO would never reach the disk layer (hash() is
    process-salted, so the constant is unique per run)."""
    import jax.numpy as jnp
    k = float(hash(tag) % 9973 + 2)
    jax.jit(lambda x: (x * k).sum())(jnp.ones((4,))).block_until_ready()


def test_variable_set_is_the_cache_and_no_other_dir_is_set(
        monkeypatch, tmp_path, jax_cache_config):
    """JAX_COMPILATION_CACHE_DIR set: jax's own cache lives there and
    the program issues no jax.config.update to any other directory."""
    d = tmp_path / "from_env"
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(d))
    # what jax itself does with the variable at import
    jax.config.update("jax_compilation_cache_dir", str(d))
    dir_updates = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            dir_updates.append(value)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    assert cc.enable_persistent_cache(CompileConfig()) == d
    assert dir_updates == []
    assert jax.config.jax_compilation_cache_dir == str(d)
    _unique_compile(str(d))
    stats = cc.cache_stats()
    assert stats["dir"] == str(d)
    assert stats["entries"] >= 1 and stats["bytes"] > 0
    # the monitoring listener fed the counters
    assert stats["hits"] + stats["misses"] >= 1
    assert not cc.DEFAULT_CACHE_DIR.joinpath("from_env").exists()


def test_variable_unset_uses_the_fixed_checkout_path(
        monkeypatch, tmp_path, jax_cache_config):
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    # point the constant at a scratch dir: the test must not write the
    # real checkout's cache, only show that the constant is what is used
    monkeypatch.setattr(cc, "DEFAULT_CACHE_DIR", tmp_path / ".jax_cache")
    got = cc.enable_persistent_cache(CompileConfig())
    assert got == tmp_path / ".jax_cache" and got.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(got)
    _unique_compile(str(got))
    assert cc.cache_stats()["entries"] >= 1


def test_persistent_cache_false_is_off_even_under_the_variable(
        monkeypatch, tmp_path, jax_cache_config):
    d = tmp_path / "inherited"
    monkeypatch.setenv(cc.CACHE_DIR_ENV, str(d))
    jax.config.update("jax_compilation_cache_dir", str(d))
    assert cc.enable_persistent_cache(
        CompileConfig(persistent_cache=False)) is None
    assert jax.config.jax_enable_compilation_cache is False
    _unique_compile(str(d))
    assert cc.cache_stats(d)["entries"] == 0  # cold means cold
    # and on again in the same process works (the reset in enable)
    assert cc.enable_persistent_cache(CompileConfig()) == d
    _unique_compile(str(d) + "again")
    assert cc.cache_stats(d)["entries"] >= 1


# ---------------------------------------------------------------------------
# precompiled step ≡ cold-compiled step, bitwise
# ---------------------------------------------------------------------------

def _tiny_cfg(train_dir: str, precompile: bool) -> ExperimentConfig:
    return ExperimentConfig.from_dict({
        "data": {"dataset": "synthetic", "batch_size": 32,
                 "synthetic_train_size": 256, "synthetic_test_size": 64},
        "model": {"compute_dtype": "float32"},
        # 2 replicas, not the full 8: the test pays TWO train-step
        # compiles (precompiled + cold arms) and the bitwise claim is
        # mesh-size-independent — keep the tier-1 budget
        "mesh": {"num_replicas": 2},
        "compile": {"precompile": precompile},
        "train": {"max_steps": 2, "train_dir": train_dir,
                  "log_every_steps": 1, "save_interval_steps": 0,
                  "save_results_period": 0, "async_checkpoint": False,
                  "summary_every_steps": 0}})


def test_precompile_first_step_bitwise_equals_cold(tmp_path):
    from distributedmnist_tpu.train.loop import Trainer
    t_pre = Trainer(_tiny_cfg(str(tmp_path / "pre"), precompile=True))
    info = t_pre.precompile()
    assert info["compile_s"] is not None and info["source"] == "compiled"
    # a CPU mesh: no compiler option asked for, no collective made
    # asynchronous (tests/test_async_aggregate.py has the TPU's side)
    assert info["compiler_options"] == [] and info["async_collectives"] == 0
    assert t_pre.precompile() is info  # idempotent per Trainer
    s_pre = t_pre.run()
    t_cold = Trainer(_tiny_cfg(str(tmp_path / "cold"), precompile=False))
    s_cold = t_cold.run()
    # the AOT executable and jit's own compile are the same program:
    # losses and final params must match BITWISE, not approximately
    pre = [json.loads(l) for l in
           (tmp_path / "pre" / "train_log.jsonl").read_text().splitlines()]
    cold = [json.loads(l) for l in
            (tmp_path / "cold" / "train_log.jsonl").read_text().splitlines()]
    assert [r["loss"] for r in pre if r["event"] == "step"] == \
           [r["loss"] for r in cold if r["event"] == "step"]
    assert s_pre["params_digest"] == s_cold["params_digest"]
    # compile time is journaled separately from step time
    compile_events = [r for r in pre if r["event"] == "compile"]
    assert len(compile_events) == 1
    assert compile_events[0]["compile_s"] == info["compile_s"]
    assert compile_events[0]["compiler_options"] == []
    assert compile_events[0]["async_collectives"] == 0
    from distributedmnist_tpu.obsv import schema
    assert schema.validate_event(compile_events[0]) == []
    assert {"compiler_options", "async_collectives"} <= set(
        schema.EVENT_SCHEMAS["compile"].optional)
    assert s_pre["compile"]["source"] == "compiled"
    assert s_cold["compile"] is None
