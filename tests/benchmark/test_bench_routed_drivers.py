"""Both drivers take a routed cell through the routed branch of their
check, end to end at a toy size (``toy_routed.py`` run by
``toy_routed_model.py`` through the program's registry, ``Trainer`` and
``DecodeReplica``), and a program that routes wrongly underneath an
otherwise sound run comes out not ``correct``. What the branch decides,
over 50 seeds: ``test_bench_routed.py``."""

import functools
import json

import pytest

import toy_routed_model as standin
from bench_toy import (routed_toy_cell,  # noqa: F401  (fixtures)
                       routed_toy_unregistered, toy_runtime)
from benchmark import run as run_mod
from benchmark.lib import compare


def _events(capsys) -> dict:
    return {e["event"]: e for e in map(
        json.loads, (line for line in capsys.readouterr().out.splitlines()
                     if line.startswith("{")))}


@pytest.mark.parametrize("traffic", ["train_sync", "serve_closed"])
def test_both_drivers_reach_the_routed_branch(
        traffic, toy_runtime, capsys):  # noqa: F811
    cell = routed_toy_cell(traffic)
    result = run_mod.measure(cell, toy_runtime(cell, seconds=1.5))
    check = _events(capsys)["reference_check"]
    assert check["ok"] and check["routing_ok"]
    assert 0 <= check["routing_slack_max"] <= compare.ROUTING_SLACK_MAX
    assert compare.ROUTING_AGREEMENT_MIN <= check["routing_agreement"] <= 1
    assert result["correct"] is True and result["failed"] == 0


@pytest.mark.parametrize("traffic, fault", [
    ("train_sync", "no_bias"), ("train_sync", "repeated_best"),
    ("serve_closed", "repeated_best")])
def test_a_program_that_routes_wrongly_is_not_correct(
        traffic, fault, toy_runtime, monkeypatch, capsys):  # noqa: F811
    """The rest of a run with the path broken underneath: the program
    forgets its selection bias, or takes its best expert k times; every
    other check of the run passes, and ``correct`` comes out false,
    through ``check_against_reference`` and through
    ``check_decode_against_reference``."""
    from distributedmnist_tpu.models import registry
    cell = routed_toy_cell(traffic)
    monkeypatch.setitem(registry._REGISTRY, "toy_routed",
                        functools.partial(standin.build, fault=fault))
    result = run_mod.measure(cell, toy_runtime(cell, seconds=1.0))
    events = _events(capsys)
    assert result["correct"] is False and result["attempted"] > 0
    check = events["reference_check"]
    assert check["routing_ok"] is False
    if fault == "repeated_best":
        assert check["routing_ids_valid"] == 0
        assert result["compared"]["routing_ids_valid"] == [0.0, 1.0]
    window = "train_window" if traffic == "train_sync" else "serve_window"
    checks = events[window]["checks"]
    assert not checks.pop("reference") and all(checks.values())
