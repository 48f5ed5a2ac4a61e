"""What the two serving drivers share: weights from the seed published
as the checkpoint the replica follows, the check of the decode path
against the plain reference of the cell's architecture
(``benchmark/archs/<arch>.py``), the replica run in this process as
``launch serve --decode`` runs it, and the load generator as a child
that never touches jax.

The parent holds the chip. The child is told nothing but the port and
the plan; it reports the window's wall-clock bounds on its standard
output when the load starts, and the parent's clock is the same
machine's."""

from __future__ import annotations

import dataclasses
import functools
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from . import serve_metrics, traffic as traffic_lib
from .cell import BENCH_DIR, BenchmarkError
from .compare import max_rel_err
from .stats import percentile

#: Largest error of the decode path's logits over the reference's
#: largest magnitude, prefill plus eight decode steps through the paged
#: cache against one full float32 forward. bf16 operands, activations
#: and cache (2^-8 = 4e-3 a rounding) through 24 blocks: it measured
#: 1.1e-2 to 1.3e-2 on the chip (PERF.md, PR 22). 3e-2 holds that with
#: room for another reduction order and is half of what one 8-bit-float
#: rounding gives; a wrong block, offset or mask is O(1).
DECODE_LOGITS_TOL = 3e-2
CHECK_PROMPT = 128
CHECK_STEPS = 8
#: seconds of the window a traced run traces
TRACE_SECONDS = 3.0


def experiment(cell, rt) -> dict:
    serve = cell.config["serve"]
    return {
        "name": cell.name,
        "model": {**cell.arch.model_section(cell.config),
                  "init_seed": rt.seed},
        "precision": serve.get("precision", {}),
        "serve": serve["replica"],
        "decode": serve["decode"],
        "train": {"train_dir": str(rt.workdir / "publish"), "seed": rt.seed},
    }


def check_decode_against_reference(model, params, dcfg, cache_dtype,
                                   vocab: int, cell, seed: int) -> dict:
    """Prefill of a seeded prompt and teacher-forced decode steps
    through a scratch paged cache of the replica's own geometry, by the
    model record's exports (the jitted step is built exactly as
    ``DecodeReplica`` builds it), against the full forward of the
    reference of the cell's architecture at the same positions. Logits,
    never sampled tokens: with random weights the largest logit changes
    on a rounding."""
    import jax
    import jax.numpy as jnp
    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    # 128 and 8 at any real geometry; a toy one (the tests) caps them
    n_prompt = min(CHECK_PROMPT, dcfg.max_prompt_len)
    n_steps = min(CHECK_STEPS, dcfg.max_new_tokens)
    rng = np.random.default_rng([int(seed), 0xC4EC])
    seq = rng.integers(0, vocab, n_prompt + n_steps).astype(np.int32)
    layers, n_heads, head_dim = model.decode_cache_shape
    cache = PagedKVCache(layers, dcfg.num_blocks, dcfg.block_size, n_heads,
                         head_dim, dcfg.max_blocks_per_seq(),
                         dtype=cache_dtype)
    prefill = jax.jit(model.decode_prefill)
    step = jax.jit(functools.partial(model.decode_step,
                                     block_size=dcfg.block_size,
                                     attention_kernel=dcfg.attention_kernel),
                   donate_argnums=(3, 4))
    logits, ks, vs = prefill(params, jnp.asarray(seq[None, :n_prompt]))
    rows = [logits[0, n_prompt - 1]]
    table = cache.alloc_sequence(n_prompt + n_steps)
    cache.write_prompt(table, ks[:, 0], vs[:, 0], n_prompt)
    slots = dcfg.decode_slots
    tables = np.zeros((slots, cache.max_blocks_per_seq), np.int32)
    tables[0] = table
    tables = jnp.asarray(tables)
    for i in range(n_steps):
        pos = n_prompt + i
        vec = lambda v: jnp.zeros(  # noqa: E731
            (slots,), jnp.int32).at[0].set(v)
        out, cache.k, cache.v = step(params, vec(int(seq[pos])), vec(pos),
                                     cache.k, cache.v, tables, vec(pos + 1))
        rows.append(out[0])
    got = jnp.stack(rows)
    del cache, ks, vs, logits, out     # room for the float32 reference
    arch, config = cell.arch, cell.config
    want = jax.jit(lambda p, t: arch.logits(
        p, t, config, last=n_steps + 1))(params, jnp.asarray(seq[None]))[0]
    err = max_rel_err(got, want)
    finite = bool(jnp.isfinite(got).all())
    return {"decode_logits_max_rel_err": err, "positions": n_steps + 1,
            "ok": bool(finite and err <= DECODE_LOGITS_TOL)}


def _prefill_records(serve_dir: Path, lo: float, hi: float) -> list[float]:
    """Durations (ms) of the prefills the replica journalled inside the
    window. The journal calls the field ``ttft_ms``; it is the prefill's
    own duration, from the start of ``_prefill`` to its token."""
    out = []
    with open(serve_dir / "serve_log.jsonl", encoding="utf-8") as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("action") == "prefill" and lo <= rec["time"] < hi:
                out.append(float(rec["ttft_ms"]))
    return out


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.time()
        if left <= 0:
            return
        time.sleep(min(left, 0.5))


def boot_replica(cell, rt):
    """Weights from the seed, checked against the reference, published
    as the checkpoint the replica follows, and the replica serving them.
    Returns (replica, its configuration, the check, the seconds the
    weights took from publish to served)."""
    import jax
    from distributedmnist_tpu.core.compile_cache import \
        enable_persistent_cache
    from distributedmnist_tpu.core.config import (ExperimentConfig,
                                                  effective_model_config)
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.parallel.api import (init_train_state,
                                                   resolved_param_dtype)
    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    from distributedmnist_tpu.train.checkpoint import save_checkpoint

    enable_persistent_cache()            # as `launch serve` does
    cfg = ExperimentConfig.from_dict(experiment(cell, rt))
    model_cfg = effective_model_config(cfg, serving=True)
    model = get_model(model_cfg)
    publish_dir = Path(cfg.train.train_dir)

    # Weights: on the device, in one jitted call, in the dtype they are
    # served in. The key is an argument, so the program is the same for
    # every seed and comes out of the compile cache (with the seed as a
    # constant inside it, each run compiled it anew: 34 s). The state
    # around the weights is the program's own init_train_state, handed
    # a model record whose init returns them.
    stored = resolved_param_dtype(cfg)
    with jax.default_device(jax.devices()[0]):
        params = jax.jit(lambda key: jax.tree.map(
            lambda p: p.astype(stored), model.init(key)))(
                jax.random.PRNGKey(rt.seed))
        state = init_train_state(
            dataclasses.replace(model, init=lambda key: params), cfg)
        jax.block_until_ready(state)
        rt.mark("weights_made")
        check = check_decode_against_reference(
            model, state.params, cfg.decode,
            jax.numpy.dtype(model_cfg.compute_dtype), cfg.model.vocab_size,
            cell, rt.seed)
        del params
    rt.say(event="reference_check", **check, tolerance=DECODE_LOGITS_TOL)
    rt.mark("reference_checked")

    # The replica follows a published checkpoint by design. `launch
    # serve` would first read the artifact once more for its saved
    # configuration (wait_for_run_config: 9 s at this size, PERF.md);
    # the benchmark wrote that configuration and hands it over.
    t_publish = time.time()
    save_checkpoint(publish_dir, state, 0, extra={"config": cfg.to_dict()})
    del state
    rt.mark("published")
    t_restore = time.time()
    rep = DecodeReplica(publish_dir, serve_dir=rt.workdir / "serve",
                        scfg=cfg.serve, dcfg=cfg.decode, cfg=cfg)
    rep.start()
    t_ready = time.time()
    weights = {"publish_s": t_restore - t_publish,
               "restore_s": t_ready - t_restore,
               "weights_ready_s": t_ready - t_publish}
    rt.say(event="weights", **weights, model_step=rep.model_step)
    rt.mark("replica_serving")
    return rep, cfg, check, weights


def drive_load(cell, rt, rep, plan: dict, trace: bool,
               on_window=lambda at: None) -> tuple[dict, dict]:
    """Run the load generator as a child against ``rep`` and wait for
    it. Returns its raw records and what a traced part saw. The child
    warms the shapes up first, then says when the window opens."""
    plan_path, out_path = rt.workdir / "plan.json", rt.workdir / "load.json"
    plan_path.write_text(json.dumps(plan))
    trace_info: dict = {}
    child = subprocess.Popen(
        [sys.executable, str(BENCH_DIR / "lib" / "loadgen.py"),
         str(plan_path), str(out_path)], stdout=subprocess.PIPE, text=True)
    try:
        line = child.stdout.readline()      # after the child's warm-up
        started = json.loads(line) if line.strip() else {}
        if started.get("event") != "load_start":
            raise BenchmarkError(f"the load generator did not start: "
                                 f"{line.strip() or 'no output'}")
        ws, we = started["window_start"], started["window_end"]
        rt.mark("shapes_warm")
        _sleep_until(ws)
        on_window(ws)
        if trace:
            _sleep_until(ws + 0.25 * (we - ws))
            rt.start_trace()
            # the replica's own counter, read where the annotated
            # window opens and closes
            tokens, t0 = rep.tokens_streamed, time.time()
            time.sleep(TRACE_SECONDS)
            trace_info = {"tokens": rep.tokens_streamed - tokens,
                          "t0": t0, "seconds": time.time() - t0}
            rt.stop_trace()
        _sleep_until(we)
        on_window(None)
        rc = child.wait(timeout=float(plan["grace_s"]) + 60.0)
        if rc != 0:
            raise BenchmarkError(f"the load generator exited with {rc}")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    with open(out_path, encoding="utf-8") as f:
        return json.load(f), trace_info


def run_serving(cell, rt, mode: str) -> dict:
    """One serving run: publish, check, serve, load, reduce."""
    rep, cfg, check, weights = boot_replica(cell, rt)
    vocab = cfg.model.vocab_size
    try:
        plan = make_plan(cell.traffic, rt.seed, rt.seconds, mode, vocab,
                         rep.bound_port, cfg.decode.decode_slots)
        load, trace_info = drive_load(
            cell, rt, rep, plan, rt.trace,
            lambda at: rt.window_opens(at=at) if at else rt.window_closes())
    finally:
        rep.stop()
        # 2.6 GB of published weights: not left in the checkout
        shutil.rmtree(cfg.train.train_dir, ignore_errors=True)
    ws, we = load["window_start"], load["window_end"]
    summary = serve_metrics.summarize(load, vocab)
    prefill_ms = _prefill_records(rt.workdir / "serve", ws, we)
    checks = {"reference": check["ok"],
              "every_request_ok": summary["failed"] == 0,
              "no_compile_in_window": rt.compiles_in_window == 0,
              "tokens_flowed": summary["tokens_in_window"] > 0}
    rt.say(event="serve_window", compiles_in_window=rt.compiles_in_window,
           prefills_in_window=len(prefill_ms), traced=trace_info,
           checks=checks, **summary)
    decode_bytes = None
    if trace_info:
        # what one decode step had to move in the middle of the trace
        contexts = serve_metrics.live_contexts(
            load, trace_info["t0"] + trace_info["seconds"] / 2)
        decode_bytes = cell.arch.decode_bytes_per_step(cell.config, contexts)
    return {
        "correct": all(checks.values()),
        "attempted": summary["attempted"], "failed": summary["failed"],
        "values": summary,
        "counters": {
            **weights,
            "decode_bytes_per_step": decode_bytes,
            "peak_hbm_bytes_per_s": rt.peaks.get("hbm_bytes_per_s"),
            "prefill_ms_p50": (percentile(prefill_ms, 0.5)
                               if prefill_ms else None),
            "tokens_in_trace": trace_info.get("tokens"),
            **{k: summary.get(k) for k in (
                "itl_ms_p50", "itl_ms_p99", "ttft_ms_p50", "ttft_ms_p90",
                "loadgen_late_ms_p99")},
        },
    }


def make_plan(tr: dict, seed: int, seconds: float, mode: str, vocab: int,
              port: int, slots: int) -> dict:
    """The load generator's orders, all of it from the traffic file and
    the seed."""
    rng = np.random.default_rng([int(seed), 0x3A83])
    # every shape twice: the second pass runs on the cache arrays the
    # first one left behind, as the window's requests will (a jitted
    # call is compiled again for an argument placed differently)
    lengths = traffic_lib.warmup_lengths(tr)
    warmup = [{"id": f"w{i}", "temperature": 0.0, "max_tokens": 2,
               "prompt": rng.integers(0, vocab, n).tolist(),
               "deadline_ms": 1000.0 * tr["warmup_request_timeout_s"]}
              for i, n in enumerate(lengths + lengths)]
    plan = {"host": "127.0.0.1", "port": port, "mode": mode,
            "warmup": warmup,
            "warmup_timeout_s": tr["warmup_request_timeout_s"],
            "warmup_s": tr["warmup_s"], "seconds": seconds,
            "grace_s": tr.get("grace_s", 0.0)}
    if mode == "open":
        plan["requests"] = traffic_lib.open_schedule(
            tr, seed, seconds, vocab)
    else:
        plan["queues"] = traffic_lib.closed_queues(
            tr, seed, int(tr["clients_per_slot"] * slots), vocab)
    return plan
