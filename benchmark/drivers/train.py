"""Traffic kind ``train``: the job, not the kernel. ``launch train``'s
bring-up and ``Trainer(cfg, datasets=...).run()`` with the program's
loader, device prefetch, logging and every other default running, on
full sequences made from ``--seed``, under the sync discipline the
traffic file names.

The window is a whole number of log windows of the trainer
(``train.log_every_steps`` steps each). ``Trainer.run`` fetches the
window's losses when it flushes one, which drains the device, and then
calls ``step_callback``: those calls are the drained points the rate is
timed between. The window closes at the drained point nearest to
``--seconds``; the driver then stops the loop by raising from the
callback, which skips the final save (6.5 GB at this size: a job saves
once in hours, a benchmark run would save every minute; the save is a
cell of its own in PERF.md's list).

``correct``: before the loop, on two seeded sequences, the system's
loss and its logits at the last 256 positions against the reference of
the cell's architecture (``benchmark/archs/<arch>.py``); after it,
every step's loss finite, the mean of the window's last five below that
of its first five, no NaN rollback, no compilation inside the window,
and under quorum exactly k contributors in every step with every
parameter on every chip."""

from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np

from benchmark.lib import tokens
from benchmark.lib.cell import BenchmarkError
from benchmark.lib.compare import max_rel_err

#: Largest error over the reference's largest magnitude. The system
#: feeds the MXU bf16 operands and keeps bf16 activations (8 mantissa
#: bits, 2^-8 = 4e-3 a rounding); through three blocks and the head a
#: few roundings stack. It measured 7.3e-3 to 8.3e-3 on the chip at
#: these widths (PERF.md, PR 22; PR 21 had 5e-3 to 8e-3 on single
#: kernels); 2e-2 holds that and is far below what a wrong mask or index
#: (O(1)) or an 8-bit float path (6e-2 a rounding) would give.
LOGITS_TOL = 2e-2
#: The loss averages 4,094 positions, so rounding noise largely cancels:
#: relative to a loss of ~11 it measured 1e-5 to 7e-5 (PERF.md, PR 22).
LOSS_TOL = 2e-3
CHECK_SEQUENCES = 2
CHECK_LAST = 256


class _WindowDone(Exception):
    """Raised from ``step_callback`` to end ``Trainer.run``."""


def experiment(cell, rt) -> dict:
    """The program's configuration for this cell: sizes from the
    configuration file, the discipline from the traffic file, nothing
    else moved off its default."""
    train = cell.config["train"]
    return {
        "name": cell.name,
        "data": {"dataset": "synthetic_lm",
                 "batch_size": train["sequences_per_step_per_chip"]
                 * cell.chips},
        "model": {**cell.arch.model_section(cell.config),
                  "init_seed": rt.seed},
        "optim": train["optim"],
        "sync": cell.traffic["sync"],
        "train": {"train_dir": str(rt.workdir / "train"), "seed": rt.seed,
                  # the loop ends when the window does; no save inside it
                  "max_steps": 1_000_000, "save_interval_steps": 0},
    }


def _datasets(cell, rt, seq_len: int, vocab: int):
    from distributedmnist_tpu.data.datasets import ArrayDataset, Datasets

    per_chip = int(cell.traffic["data"]["sequences_per_chip"])
    toks = tokens.make_lm_tokens(
        rt.seed, per_chip * cell.chips + CHECK_SEQUENCES, seq_len, vocab,
        cell.traffic["data"]["zipf_exponent"],
        cell.traffic["data"]["bigram_share"])
    held, train = toks[:CHECK_SEQUENCES], toks[CHECK_SEQUENCES:]
    as_set = lambda a: ArrayDataset(a, a.copy())  # noqa: E731
    return Datasets(train=as_set(train), validation=as_set(held),
                    test=as_set(held)), held


def check_against_reference(trainer, held: np.ndarray, cell) -> dict:
    """Loss and last-positions logits of the system (its own ``apply``
    at its own settings, kernels and all) against the plain reference
    of the cell's architecture, on the weights the run starts from."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(trainer.state.params)
    params = jax.tree.unflatten(
        treedef, [leaf.addressable_shards[0].data for leaf in leaves])
    toks = jax.device_put(jnp.asarray(held), params["embed"].devices().pop())
    model = trainer.model

    @jax.jit
    def system(p, t):
        lg = model.apply(p, t, train=False)
        return model.loss(lg, t), lg[:, -CHECK_LAST:]

    arch, config = cell.arch, cell.config
    ref = jax.jit(lambda p, t: (
        arch.loss(p, t, config),
        arch.logits(p, t, config, last=CHECK_LAST)))
    sys_loss, sys_logits = system(params, toks)
    ref_loss, ref_logits = ref(params, toks)
    logits_err = max_rel_err(sys_logits, ref_logits)
    loss_err = abs(float(sys_loss) - float(ref_loss)) / abs(float(ref_loss))
    return {"loss_system": float(sys_loss), "loss_reference": float(ref_loss),
            "loss_rel_err": loss_err, "logits_max_rel_err": logits_err,
            "ok": bool(logits_err <= LOGITS_TOL and loss_err <= LOSS_TOL
                       and math.isfinite(float(sys_loss)))}


def run(cell, rt) -> dict:
    import jax
    from distributedmnist_tpu.core.mesh import make_topology
    from distributedmnist_tpu.launch.__main__ import _load_cfg_and_bringup
    from distributedmnist_tpu.train.loop import Trainer

    cfg_path = rt.workdir / "config.json"
    cfg_path.write_text(json.dumps(experiment(cell, rt), indent=1))
    # `launch train`'s bring-up: multi-host discovery, the compile cache
    cfg = _load_cfg_and_bringup(
        argparse.Namespace(config=str(cfg_path), overrides=[]))
    model_cfg = cfg.model
    datasets, held = _datasets(cell, rt, model_cfg.seq_len,
                               model_cfg.vocab_size)
    rt.mark("data_made")
    topo = make_topology(cfg.mesh, devices=jax.devices()[:cell.chips])
    trainer = Trainer(cfg, topo=topo, datasets=datasets)
    rt.mark("trainer_built")
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree.leaves(trainer.state.params)}
    check = check_against_reference(trainer, held, cell)
    rt.say(event="reference_check", **check,
           tolerances={"logits": LOGITS_TOL, "loss": LOSS_TOL})
    rt.mark("reference_checked")

    every = max(1, cfg.train.log_every_steps)
    tokens_per_step = cfg.data.batch_size * model_cfg.seq_len
    warm_flushes = int(cell.traffic["warmup_log_windows"])
    records: list[dict] = []
    drained: list[tuple[int, float]] = []   # (step, clock) at each flush
    window: dict = {}
    traced: dict = {}

    def on_step(step: int, record: dict) -> None:
        if not records:
            rt.mark("first_log_window_done")
        records.append(record)
        if step % every:
            return
        now = time.perf_counter()
        drained.append((step, now))
        if "start" not in window:
            if len(drained) >= warm_flushes:
                window["start"] = (step, now)
                rt.window_opens()
            return
        t0 = window["start"][1]
        period = now - drained[-2][1]
        if rt.trace and "stop" not in traced:
            # one log window of steps from the middle of the run; what
            # starting and stopping the profiler costs is taken out of
            # the rate below (a traced run reports no end-to-end metric)
            if "start" in traced:
                rt.stop_trace()
                traced["stop"] = step
                traced["seconds"] = time.perf_counter() - traced.pop("t0")
            elif now - t0 >= 0.25 * rt.seconds:
                traced["start"], traced["t0"] = step, now
                rt.start_trace()
            return
        if now - t0 >= rt.seconds - 0.5 * period:
            window["end"] = (step, now)
            rt.window_closes()
            raise _WindowDone

    try:
        trainer.run(step_callback=on_step)
    except _WindowDone:
        pass
    else:
        raise BenchmarkError("Trainer.run returned before the window closed")

    (s0, t0), (s1, t1) = window["start"], window["end"]
    in_window = [r for r in records if s0 < r["step"] <= s1]
    losses = [r["loss"] for r in in_window]
    # a traced run reports no end-to-end metric; the rate its MFU is
    # built on leaves out the traced log window and the profiler's
    # start and stop around it
    steps = s1 - s0 - (every if traced else 0)
    rate = (steps * tokens_per_step / (t1 - t0 - traced.get("seconds", 0.0))
            / cell.chips)
    k = cell.traffic["sync"].get("num_replicas_to_aggregate")
    journal = rt.workdir / "train" / "recovery_journal.jsonl"
    checks = {
        "reference": check["ok"],
        "losses_finite": all(math.isfinite(v) for v in losses),
        "loss_falls": bool(len(losses) >= 10 and np.mean(losses[-5:])
                           < np.mean(losses[:5])),
        "no_recovery_event": not journal.exists(),
        "no_compile_in_window": rt.compiles_in_window == 0,
        "params_on_every_chip": spans == {cell.chips},
        "quorum_exact": (k is None or all(
            r["num_contributors"] == k and sum(r["flags"]) == k
            for r in in_window)),
    }
    rt.say(event="train_window", steps=s1 - s0, seconds=t1 - t0,
           tokens_per_step=tokens_per_step, first_losses=losses[:5],
           last_losses=losses[-5:],
           loss_by_log_window=[float(np.mean(losses[i:i + every]))
                               for i in range(0, len(losses), every)],
           traced_steps=traced,
           compiles_in_window=rt.compiles_in_window, checks=checks)
    host = trainer.collector.host_step_stats()
    depth = trainer.collector.prefetch_depth_stats()
    return {
        "correct": all(checks.values()),
        "attempted": s1 - s0,
        "failed": sum(1 for v in losses if not math.isfinite(v)),
        "values": {"train_tokens_per_s_per_chip": rate},
        "counters": {
            "host_step_ms_p50": host.percentiles["p50"] * 1e3,
            "prefetch_depth_p50": (depth.percentiles["p50"]
                                   if depth.count else None),
            "tokens_per_s": rate * cell.chips,
            "tokens_per_step": tokens_per_step,
            "steps_per_log_window": every,
            "model_flops_per_token": cell.arch.train_flops_per_token(
                cell.config, model_cfg.seq_len),
            "attention_flops_per_step_per_chip": (
                cell.arch.attention_train_flops_per_token(
                    cell.config, model_cfg.seq_len)
                * tokens_per_step / cell.chips),
        },
    }
