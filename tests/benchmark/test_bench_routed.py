"""A routed architecture is held to its reference at every seed. The
toy routed block (``toy_routed.py``: softmax top-2 of 8 un-renormalised,
sigmoid top-4 of 64 renormalised and doubled, a selection bias, a
shared expert, one dense and two routed layers, 256 wide) run by its
bfloat16 stand-in (``toy_routed_model.py``), over 50 seeds: the
free-running compare fails, the compare under the program's own choices
passes with the dense control's error, wrong routers and a one-byte
float are refused by what the choices are held to
(``test_bench_routed_drivers.py`` takes both drivers through that
branch). Nothing timed here is a device metric."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy_routed as arch
import toy_routed_model as standin
from bench_toy import (routed_toy_cell,  # noqa: F401  (fixture)
                       routed_toy_unregistered)
from benchmark.lib import cell as cell_lib, compare, serving
from benchmark.lib.compare import max_rel_err

train_driver = cell_lib.load_driver("train")
LOGITS_TOL, CHECK_LAST = train_driver.LOGITS_TOL, train_driver.CHECK_LAST
SEEDS = tuple(range(50))
ROUTERS = (8, 64)          # softmax top-2 of 8, sigmoid top-4 of 64
#: routers that pick by a wrong score, which the slack sees
WRONG_SCORES = ("no_bias", "wrong_norm", "shifted_input")
#: and one that picks its best expert k times, which only its ids show
WRONG_ROUTERS = WRONG_SCORES + ("repeated_best",)


def _inputs(config: dict, seed: int):
    """Weights and the check's two held sequences, from the seed."""
    return (standin.init(jax.random.PRNGKey(seed), config),
            jax.random.randint(jax.random.PRNGKey(seed + 1_000_003),
                               (train_driver.CHECK_SEQUENCES,
                                config["max_position_embeddings"]),
                               0, config["vocab_size"]))


def sweep(experts: int, fault: str | None = None, seeds=SEEDS,
          width: int = 256) -> list[dict]:
    """What the train check reads, a seed a row, by programs compiled
    once: the error of the free-running compare, that of the compare
    under the stand-in's choices, and the verdict on the choices."""
    return _sweep(experts, fault, tuple(seeds), width)


@functools.lru_cache(maxsize=None)
def _sweep(experts: int, fault, seeds: tuple, width: int) -> list[dict]:
    config = standin.toy_config(experts, width)
    system = jax.jit(lambda p, t: standin.forward(
        p, t, config, jnp.bfloat16, fault)[:2])
    free = jax.jit(lambda p, t: arch.logits(p, t, config, last=CHECK_LAST))
    forced = jax.jit(lambda p, t, r: (
        arch.logits(p, t, config, last=CHECK_LAST, routing=r),
        arch.routing_slack(p, t, config, r),
        arch.routing_margin(p, t, config, r)))
    rows = []
    for seed in seeds:
        params, toks = _inputs(config, seed)
        got, routing = system(params, toks)
        got = got[:, -CHECK_LAST:]
        row = {"seed": seed, "free": max_rel_err(got, free(params, toks))}
        if experts:
            want, slack, margin = forced(params, toks, routing)
            row.update(forced=max_rel_err(got, want),
                       slack=np.asarray(slack), margin=np.asarray(margin),
                       routing=np.asarray(routing), **compare.routing_verdict(
                           slack, routing, arch.routed_experts(config), 0.0))
        rows.append(row)
    return rows


def _ok(row: dict) -> bool:
    return row["forced"] <= LOGITS_TOL and row["routing_ok"]


# -- the two implementations are one architecture ---------------------------

@pytest.mark.parametrize("experts", ROUTERS)
def test_the_stand_in_in_float32_is_the_reference(experts):
    config = standin.toy_config(experts)
    params, toks = _inputs(config, 3)
    got, routing, _, _ = standin.forward(params, toks, config, jnp.float32)
    assert routing.shape == (2, *toks.shape, config["num_experts_per_tok"])
    assert max_rel_err(got, arch.logits(params, toks, config)) < 1e-5
    # its choices are the reference's own, and forcing them changes nothing
    assert not np.asarray(arch.routing_slack(params, toks, config,
                                             routing)).any()
    assert max_rel_err(got, arch.logits(params, toks, config,
                                        routing=routing)) < 1e-5
    assert float(arch.loss(params, toks, config)) == pytest.approx(
        float(_model(experts, "float32").loss(got, toks)), rel=1e-5)


# -- the refusal reproduced, and cured --------------------------------------

@pytest.mark.parametrize("experts", ROUTERS)
def test_the_free_running_compare_fails_at_some_seeds(experts):
    """PR 27's refusal: the same sound program is inside the tolerance
    at one seed and outside it at another, or at none."""
    errors = np.array([row["free"] for row in sweep(experts)])
    share = float((errors > LOGITS_TOL).mean())
    print(f"\n{experts} experts: the free-running compare fails at "
          f"{share:.0%} of {len(SEEDS)} seeds (median "
          f"{np.median(errors):.3g}, largest {errors.max():.3g}, "
          f"tolerance {LOGITS_TOL})")
    assert share > 0.5
    # and not for the arithmetic: the dense control is inside at every seed
    assert max(row["free"] for row in sweep(0)) < LOGITS_TOL


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("experts", ROUTERS)
def test_the_forced_compare_passes_at_every_seed(experts, seed):
    row = sweep(experts)[seed]
    assert row["seed"] == seed
    assert row["forced"] <= LOGITS_TOL
    assert row["routing_slack_max"] <= compare.ROUTING_SLACK_MAX
    assert row["routing_agreement"] >= compare.ROUTING_AGREEMENT_MIN
    assert row["routing_ids_valid"] == 1.0
    assert _ok(row)


@pytest.mark.parametrize("experts", ROUTERS)
def test_the_forced_errors_are_no_wider_than_the_dense_controls(experts):
    """With the discontinuity gone what is left is rounding: the routed
    stand-in's errors lie where those of the same block with dense
    layers lie (the gates from rounded scores add one rounding more:
    a third of room)."""
    routed = np.array([row["forced"] for row in sweep(experts)])
    dense = np.array([row["free"] for row in sweep(0)])
    print(f"\n{experts} experts forced: median {np.median(routed):.3g} "
          f"largest {routed.max():.3g}; dense control: median "
          f"{np.median(dense):.3g} largest {dense.max():.3g}")
    assert np.median(routed) <= 1.35 * np.median(dense)
    assert routed.max() <= 1.35 * dense.max()


# -- what the choices are held to -------------------------------------------

@pytest.mark.parametrize("fault", WRONG_ROUTERS)
@pytest.mark.parametrize("experts", ROUTERS)
def test_a_wrong_router_is_refused_by_its_choices(experts, fault):
    """No selection bias, scores normalised over the wrong set of experts, the
    neighbouring position's input, the best expert taken k times: each
    picks experts the reference's scores do not bear out, at every seed
    tried."""
    for row in sweep(experts, fault, seeds=SEEDS[:4]):
        assert not row["routing_ok"], row["seed"]
        assert (row["routing_slack_max"] > 3 * compare.ROUTING_SLACK_MAX
                or row["routing_agreement"]
                < compare.ROUTING_AGREEMENT_MIN - 0.1), row


@pytest.mark.parametrize("experts", ROUTERS)
def test_an_expert_taken_k_times_is_refused_by_its_ids(experts):
    """Top-1 in place of top-k. The best expert lies above the
    reference's k-th best, so the slack is 0; the reference follows the
    repeated ids and renormalises their gates, so the logits agree as
    well. Only the ids themselves show it: no position has k different
    experts, none counts as agreeing, and the verdict is no."""
    for row in sweep(experts, "repeated_best", seeds=SEEDS[:4]):
        assert row["forced"] <= LOGITS_TOL and row["routing_slack_max"] == 0
        assert row["routing_ids_valid"] == 0 == row["routing_agreement"]
        assert not row["routing_ok"]


def test_ids_out_of_range_or_repeated_anywhere_are_refused():
    row = sweep(64)[0]
    routing = row["routing"]
    verdict = functools.partial(compare.routing_verdict, row["slack"],
                                experts=64, flag_diff=0.0)
    assert verdict(routing=routing)["routing_ok"]
    for layer, ids in ((0, [3, 3, 1, 2]), (1, [64, 0, 1, 2]),
                       (1, [-1, 0, 1, 2])):
        broken = routing.copy()
        broken[layer, 1, 17] = ids
        got = verdict(routing=broken)
        assert not got["routing_ok"] and got["routing_ids_valid"] < 1
        assert got["routing_agreement"] >= compare.ROUTING_AGREEMENT_MIN
    # the logits of the exports asked for their routing are the timed ones
    assert not compare.routing_verdict(row["slack"], routing, 64,
                                       1e-7)["routing_ok"]
    assert not compare.routing_verdict(row["slack"], routing, 64,
                                       float("nan"))["routing_ok"]
    with pytest.raises(cell_lib.BenchmarkError, match="routed_experts"):
        compare.routed_experts(types.SimpleNamespace(), {})


@pytest.mark.parametrize("experts", ROUTERS)
def test_a_one_byte_float_is_refused(experts):
    """The lower precision that would tempt a later PR, through every
    operand of the stand-in: the logits leave the tolerance, and at 64
    experts too many near ties fall the other way as well."""
    for row in sweep(experts, "fp8", seeds=SEEDS[:4]):
        assert not _ok(row), row["seed"]
        assert row["forced"] > LOGITS_TOL, row["seed"]
        if experts == 64:
            assert row["routing_agreement"] < compare.ROUTING_AGREEMENT_MIN


def test_the_agreement_floor_refuses_a_one_byte_router_input():
    """The floor's own case: everything in bfloat16 but the router's
    input, which goes through a one-byte float. The logits stay inside
    their tolerance; the share of equal sets does not stay above the
    floor: 0.904 over these eight seeds, as on the v5e at 20,480
    positions a seed (0.902 to 0.904, PERF.md). A seed of the toy has
    512 positions, so its own share swings by a hundredth either way
    (0.875 to 0.926): each lies under every sound seed's, the eight
    together well under the floor. (At 8 experts there are too few near
    ties to tell by their share: 0.95 to 0.96 against the sound 0.98 to
    1; on the v5e that router is refused by its slack.)"""
    rows = sweep(64, "fp8_router_input", seeds=SEEDS[:8])
    sound = min(r["routing_agreement"] for r in sweep(64))
    for row in rows:
        assert row["forced"] <= LOGITS_TOL
        assert row["routing_agreement"] < sound - 0.02
    together = float(np.mean([r["routing_agreement"] for r in rows]))
    assert together < compare.ROUTING_AGREEMENT_MIN - 0.015
    few = sweep(8, "fp8_router_input", seeds=SEEDS[:8])
    assert (np.mean([r["routing_agreement"] for r in few])
            < np.mean([r["routing_agreement"] for r in sweep(8)]))


@pytest.mark.parametrize("width", [256, 512])
def test_the_limits_rest_on_the_measured_noise(width):
    """``ROUTING_SLACK_MAX`` and the agreement floor against what the
    sound stand-in and the faulty ones read, and the curve they come
    from: the share of (layer, position) whose sets differ, by the
    reference's own margin between its k-th and next score."""
    seeds = SEEDS if width == 256 else SEEDS[:6]
    edges = [0, 0.005, 0.01, 0.02, 0.04, 0.08, 0.16, np.inf]
    for experts in ROUTERS:
        rows = sweep(experts, seeds=seeds, width=width)
        slack = np.concatenate([r["slack"].ravel() for r in rows])
        margin = np.concatenate([r["margin"].ravel() for r in rows])
        curve = [float((slack[(margin >= lo) & (margin < hi)] > 0).mean())
                 for lo, hi in zip(edges, edges[1:])]
        worst = max(r["routing_slack_max"] for r in rows)
        agreement = [r["routing_agreement"] for r in rows]
        print(f"\nwidth {width}, {experts} experts, {len(seeds)} seeds: "
              f"largest slack {worst:.3g}, agreement {min(agreement):.4f} "
              f"to {max(agreement):.4f} (all positions "
              f"{float((slack == 0).mean()):.4f}); share of sets that "
              f"differ by margin {edges[:-1]}: "
              f"{[round(c, 4) for c in curve]}")
        # three times the sound runs' largest, both limits
        assert 3 * worst <= compare.ROUTING_SLACK_MAX
        assert 3 * float((slack > 0).mean()) \
            <= 1 - compare.ROUTING_AGREEMENT_MIN
        assert min(agreement) >= compare.ROUTING_AGREEMENT_MIN + 0.02
        # disagreement is the near ties' and dies out with the margin
        assert curve[0] > 0.15 and curve[-1] == 0.0
    wrong = [row for experts in ROUTERS for fault in WRONG_SCORES
             for row in sweep(experts, fault, seeds=SEEDS[:4])]
    assert (min(r["routing_slack_max"] for r in wrong)
            >= 3 * compare.ROUTING_SLACK_MAX)


# -- the harness's own checks -----------------------------------------------

def _model(experts: int, compute_dtype: str = "bfloat16", **how):
    """The stand-in's model record, as the registry would build it."""
    return standin.build(types.SimpleNamespace(
        name="toy_routed", num_experts=experts, model_dim=256, num_layers=3,
        num_heads=4, vocab_size=512, seq_len=128,
        compute_dtype=compute_dtype), **how)


def _train_check(experts: int, seed: int, **how) -> dict:
    cell = routed_toy_cell("train_sync", experts)
    params, toks = _inputs(cell.config, seed)
    trainer = types.SimpleNamespace(
        model=_model(experts, **how),
        state=types.SimpleNamespace(params=params))
    return train_driver.check_against_reference(trainer, np.asarray(toks),
                                                cell)


def _decode_check(experts: int, seed: int, **how) -> dict:
    from distributedmnist_tpu.core.config import DecodeConfig
    cell = routed_toy_cell("serve_closed", experts)
    params, _ = _inputs(cell.config, seed)
    dcfg = DecodeConfig(**cell.config["serve"]["decode"])
    return serving.check_decode_against_reference(
        _model(experts, **how), params, dcfg, jnp.bfloat16,
        cell.config["vocab_size"], cell, seed)


def test_the_sweep_reads_what_the_train_check_reads():
    row, check = sweep(64)[5], _train_check(64, 5)
    assert check["ok"] and check["routing_ok"]
    assert check["logits_max_rel_err"] == pytest.approx(row["forced"])
    assert check["routing_slack_max"] == pytest.approx(
        row["routing_slack_max"])
    assert check["routing_agreement"] == row["routing_agreement"]
    assert check["routing_ids_valid"] == 1.0
    assert check["routing_flag_diff"] == 0.0


@pytest.mark.parametrize("experts", ROUTERS)
def test_the_decode_check_follows_prefill_and_steps(experts):
    """The prompt by the prefill's choices, each teacher-forced token
    by its own step's, through the program's paged cache."""
    check = _decode_check(experts, 11)
    assert check["ok"] and check["routing_ok"], check
    assert check["decode_logits_max_rel_err"] <= serving.DECODE_LOGITS_TOL
    assert 0 < check["routing_agreement"] <= 1
    assert check["routing_ids_valid"] == 1.0
    assert check["routing_flag_diff"] == 0.0
    wrong = _decode_check(experts, 11, fault="no_bias")
    assert not wrong["ok"] and not wrong["routing_ok"]
    repeated = _decode_check(experts, 11, fault="repeated_best")
    assert not repeated["ok"] and repeated["routing_ids_valid"] == 0
    assert repeated["decode_logits_max_rel_err"] <= serving.DECODE_LOGITS_TOL
    assert repeated["routing_slack_max"] == 0


@pytest.mark.parametrize("check", ["train", "decode"])
def test_the_choices_vouch_only_for_the_program_that_is_timed(check):
    """A program that routes soundly when it is asked for its choices
    and otherwise when it is not (another kernel on the timed path): the
    choices are sound, and the logits with the flag are not the ones
    without it."""
    run = {"train": _train_check, "decode": _decode_check}[check]
    got = run(64, 5, fault="no_bias", sound_when_asked=True)
    assert got["routing_slack_max"] <= compare.ROUTING_SLACK_MAX
    assert got["routing_agreement"] >= compare.ROUTING_AGREEMENT_MIN
    assert got["routing_flag_diff"] > 0
    assert not got["routing_ok"] and not got["ok"]


@pytest.mark.parametrize("exports", [False, "swallowed"])
@pytest.mark.parametrize("check", [_train_check, _decode_check])
def test_a_routed_architecture_without_exports_is_an_error(check, exports):
    """Never a silent free-running compare, nor a bare unpacking error
    from an export that takes any keyword and returns no routing."""
    with pytest.raises(cell_lib.BenchmarkError, match="cannot vouch"):
        check(64, 0, exports=exports)


def test_an_aux_without_routing_is_an_error():
    with pytest.raises(cell_lib.BenchmarkError, match="cannot vouch"):
        compare.routing_of(jnp.float32(0.0))     # the load-balance loss
    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    moe = get_model(ModelConfig(name="transformer", num_experts=4))
    assert moe.decode_step is None             # "dense FFNs only"
    with pytest.raises(cell_lib.BenchmarkError, match="cannot vouch"):
        compare.require_export(moe.decode_step, "return_routing", "that")


def test_a_dense_architecture_is_compared_as_before():
    assert not compare.is_routed(cell_lib.load_arch({"arch": "opt"}))
    assert compare.is_routed(arch)
