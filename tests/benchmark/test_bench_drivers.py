"""Each driver end to end at a toy size on the CPU test mesh (Pallas
interpreted): the harness runs the program through its normal entry
points and the result object has exactly the contract's keys. Nothing
timed here is a device metric; the values are only checked for being
there."""

import dataclasses
import json
import types

import jax
import numpy as np
import pytest

from bench_toy import toy_cell, toy_runtime  # noqa: F401  (fixture)
from benchmark import run as run_mod
from benchmark.lib import reference
from benchmark.lib.compare import max_rel_err

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _events(capsys) -> dict:
    """The run's JSON lines by their ``event``."""
    return {e["event"]: e for e in map(
        json.loads, (line for line in capsys.readouterr().out.splitlines()
                     if line.startswith("{")))}


def _check_result(result: dict, cell) -> None:
    assert set(result) == RESULT_KEYS
    assert set(result["device"]) == DEVICE_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, got in result["metrics"].items():
        assert set(got) == {"value", "unit"} and got["value"] > 0, name
    json.dumps(result)


@pytest.mark.parametrize("traffic", ["train_sync", "train_quorum",
                                     "serve_closed", "serve_open"])
def test_driver_end_to_end_at_a_toy_size(traffic, toy_runtime,  # noqa: F811
                                         capsys):
    cell = toy_cell(traffic)
    result = run_mod.measure(cell, toy_runtime(cell, seconds=1.5))
    _check_result(result, cell)
    events = _events(capsys)
    assert events["reference_check"]["ok"]
    window = events.get("train_window") or events["serve_window"]
    assert window["compiles_in_window"] == 0
    assert all(window["checks"].values())
    if traffic == "train_quorum":
        assert cell.chips == 4
    if traffic == "serve_open":
        # every request due in the window has its time to first token
        assert window["ttft_samples"] == round(4.0 * 1.5)
        assert window["ttft_missing"] == 0


def test_a_wrong_answer_is_not_correct(toy_runtime,  # noqa: F811
                                       monkeypatch, capsys):
    """The reference decides: a system that computes something else
    (here: the reference is handed a different mask) is reported as
    incorrect, with its numbers."""
    real = reference.hidden

    def other(params, tokens, num_heads):
        return real(params, tokens[::-1], num_heads)

    monkeypatch.setattr(reference, "hidden", other)
    cell = toy_cell("train_sync")
    result = run_mod.measure(cell, toy_runtime(cell, seconds=1.0))
    assert result["correct"] is False
    assert result["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0


@pytest.mark.parametrize("traffic", ["train_sync", "serve_closed"])
def test_the_cells_architecture_file_decides(
        traffic, toy_runtime, capsys):  # noqa: F811
    """Both drivers take the reference from the cell's architecture file
    and from nowhere else: hand them ``archs/opt.py`` with its reference
    given a different mask, ``lib/reference.py`` untouched, and the same
    program is reported as incorrect."""
    cell = toy_cell(traffic)
    opt = cell.arch
    other = types.SimpleNamespace(**vars(opt))
    other.logits = lambda params, tokens, config, last=None: opt.logits(
        params, tokens[:, ::-1], config, last=last)
    other.loss = lambda params, tokens, config: opt.loss(
        params, tokens[:, ::-1], config)
    cell = dataclasses.replace(cell, arch=other)
    result = run_mod.measure(cell, toy_runtime(cell, seconds=1.0))
    assert result["correct"] is False and result["attempted"] > 0
    assert _events(capsys)["reference_check"]["ok"] is False


# -- the reference against the program, forward, loss and gradients -------

def _toy_model(dtype="float32"):
    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    return get_model(ModelConfig(
        name="transformer", model_dim=32, num_heads=2, num_layers=2,
        seq_len=32, vocab_size=96, compute_dtype=dtype))


def test_reference_matches_the_program_in_float32():
    model = _toy_model()
    params = model.init(jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 96)
    sys_logits = model.apply(params, toks, train=False)
    np.testing.assert_allclose(
        reference.logits(params, toks, 2), sys_logits, atol=2e-5)
    np.testing.assert_allclose(
        reference.logits(params, toks, 2, last=5), sys_logits[:, -5:],
        atol=2e-5)
    sys_loss, sys_grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss(model.apply(p, toks, train=True), toks)))(params)
    ref_loss, ref_grads = jax.jit(
        lambda p: reference.loss_and_grads(p, toks, 2))(params)
    assert float(ref_loss) == pytest.approx(float(sys_loss), rel=1e-5)
    for got, want in zip(jax.tree.leaves(sys_grads),
                         jax.tree.leaves(ref_grads)):
        assert max_rel_err(got, want) < 1e-3


def test_the_tolerance_would_catch_a_lower_precision():
    """bf16 compute stays inside the benchmark's logits tolerances; the
    same forward on weights rounded to an 8-bit float does not."""
    import jax.numpy as jnp

    from benchmark.lib import cell as cell_lib, serving
    tol = cell_lib.load_driver("train").LOGITS_TOL
    assert tol <= serving.DECODE_LOGITS_TOL <= 3e-2
    params = _toy_model().init(jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 96)
    want = reference.logits(params, toks, 2)
    bf16 = _toy_model("bfloat16").apply(params, toks, train=False)
    assert max_rel_err(bf16, want) < tol
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32), params)
    fp8 = _toy_model().apply(rounded, toks, train=False)
    assert max_rel_err(fp8, want) > serving.DECODE_LOGITS_TOL
