"""The decode loop's clock (obsv/timing.LoopClock): cumulative seconds
by phase, read at the boundaries the host spans mark, carried by every
heartbeat as ``loop_s`` and ``loop_wall_s``. Run on the CPU test mesh:
what is checked is that the counters are there, only grow and tile the
loop, never a time as a device metric."""

import json
import threading
import time

import pytest

from distributedmnist_tpu.obsv.schema import validate_event
from distributedmnist_tpu.obsv.timing import LoopClock
from distributedmnist_tpu.servesvc.decode import LOOP_PHASES

from test_spans import LM_MODEL


class _Recorder:
    """A context manager that says when it was entered and left."""

    def __init__(self, log):
        self.log = log

    def __enter__(self):
        self.log.append(("enter", time.perf_counter()))

    def __exit__(self, *exc):
        self.log.append(("exit", time.perf_counter()))


def test_a_phase_adds_what_the_clock_read_inside_its_span():
    clock = LoopClock(("a", "b"))
    assert clock.wall_s() == 0.0 and clock.seconds == {"a": 0.0, "b": 0.0}
    log = []
    with clock.phase("a", _Recorder(log)):
        time.sleep(0.02)
    (_, entered), (_, left) = log
    # read inside the span: the phase is no longer than its span
    once = clock.seconds["a"]
    assert 0.02 <= once <= left - entered
    with clock.phase("b"):
        time.sleep(0.01)
    with clock.phase("a"):
        time.sleep(0.01)
    assert clock.seconds["a"] >= once + 0.01 and clock.seconds["b"] >= 0.01
    # the wall runs from the first phase entered, through what no phase
    # covers
    assert clock.wall_s() >= sum(clock.seconds.values())
    with pytest.raises(KeyError):
        with clock.phase("c"):
            pass


def test_an_exception_still_closes_the_phase_and_its_span():
    clock, log = LoopClock(("a",)), []
    with pytest.raises(RuntimeError):
        with clock.phase("a", _Recorder(log)):
            raise RuntimeError("the step failed")
    assert [what for what, _ in log] == ["enter", "exit"]
    assert clock.seconds["a"] > 0.0


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A started replica (its own batcher thread) that served twelve
    requests on three slots and then sat idle: its heartbeats."""
    from distributedmnist_tpu.core.config import (DecodeConfig,
                                                  ExperimentConfig,
                                                  ServeConfig)
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.parallel.api import init_train_state
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    from distributedmnist_tpu.train.checkpoint import save_checkpoint

    tmp = tmp_path_factory.mktemp("loop_clock")
    cfg = ExperimentConfig.from_dict({
        "model": dict(LM_MODEL), "train": {"train_dir": str(tmp / "pub")}})
    state = init_train_state(get_model(cfg.model), cfg)
    save_checkpoint(tmp / "pub", state, 0, extra={"config": cfg.to_dict()})
    rep = DecodeReplica(
        tmp / "pub", serve_dir=tmp / "replica",
        scfg=ServeConfig(poll_secs=0.05),
        dcfg=DecodeConfig(decode_slots=3, block_size=8, num_blocks=32,
                          max_prompt_len=16, max_new_tokens=10), cfg=cfg)
    rep.start()
    try:
        client = ServeClient([("127.0.0.1", rep.bound_port)],
                             deadline_s=60.0)
        outs = []

        def ask(i):
            outs.append(client.generate([1 + i % 7, 2, 3], request_id=i,
                                        max_tokens=4 + i % 5))

        for wave in range(3):
            threads = [threading.Thread(target=ask, args=(4 * wave + k,))
                       for k in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            time.sleep(0.4)              # idle between the waves
        assert [o["status"] for o in outs] == ["ok"] * 12
    finally:
        rep.stop()
    beats = [json.loads(line) for line in
             (rep.serve_dir / "train_log.jsonl").read_text().splitlines()]
    return rep, beats


def test_every_heartbeat_carries_the_loop_clock(served):
    rep, beats = served
    assert len(beats) >= 4
    for b in beats:
        # conftest validates every record as it is written; once more
        # here, by name
        assert validate_event(b) == []
        assert tuple(b["loop_s"]) == LOOP_PHASES
        assert all(isinstance(v, float) and v >= 0.0
                   for v in b["loop_s"].values())
        assert b["loop_wall_s"] >= 0.0


def test_the_phases_only_grow_and_tile_the_loop(served):
    rep, beats = served
    for a, b in zip(beats, beats[1:]):
        assert b["decode_steps"] >= a["decode_steps"]
        assert b["loop_wall_s"] >= a["loop_wall_s"]
        for phase in LOOP_PHASES:
            assert b["loop_s"][phase] >= a["loop_s"][phase], phase
    last = beats[-1]
    named = sum(last["loop_s"].values())
    # what the named phases leave (the swap's lock, the deadline sweep,
    # the heartbeat's write) is under 2% of the loop
    assert 0.98 * last["loop_wall_s"] <= named <= last["loop_wall_s"] + 1e-5
    # every phase of a request's life was entered
    for phase in ("idle", "admit", "prefill", "inputs", "dispatch", "fetch",
                  "emit"):
        assert last["loop_s"][phase] > 0.0, phase
    # two heartbeats give ms an iteration by phase
    first = next(b for b in beats if b["decode_steps"] > 0)
    steps = last["decode_steps"] - first["decode_steps"]
    assert steps > 0
    per_iter = {p: 1e3 * (last["loop_s"][p] - first["loop_s"][p]) / steps
                for p in LOOP_PHASES}
    assert per_iter["fetch"] > 0.0 and per_iter["dispatch"] > 0.0


def test_the_schema_refuses_a_misspelt_field(served):
    _, beats = served
    beat = dict(beats[-1])
    beat["loop_wal_s"] = beat.pop("loop_wall_s")
    [problem] = validate_event(beat)
    assert "loop_wal_s" in problem
    beat = dict(beats[-1])
    beat["loops_s"] = beat.pop("loop_s")
    [problem] = validate_event(beat)
    assert "loops_s" in problem
