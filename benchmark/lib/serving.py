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
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from . import compare, serve_metrics, traffic as traffic_lib
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


#: what a routed model's exports, and a session, return asked for their
#: routing (the words of a refusal)
_PREFILL_SAID = ("decode_prefill(params, tokens, return_routing=True) -> "
                 "(logits, ks, vs, routing)")
_STEP_SAID = ("decode_step(..., return_routing=True) -> (logits, k, v, "
              "routing[routed_layers, slots, k])")
_SESSION_SAID = ("a decode session whose prefill(prompt, "
                 "return_routing=True) and step(token, position, "
                 "return_routing=True) return (logits row, routing)")


class PagedSession:
    """The default decode session (``lib/cell.py`` has the contract): one
    sequence in slot 0 of a scratch paged cache, through the model
    record's ``decode_prefill`` and ``decode_step``. What every record
    without a ``decode_session`` of its own is driven through.

    The cache is built as ``DecodeReplica.__init__`` builds its own:
    ``kv_cache.cache_shapes`` of the record's ``decode_cache_shape``,
    each array's rows as wide as ``kv_cache.stored_head_dim`` answers
    for the device the weights are on, then ``PagedKVCache``, placed
    where the weights are. The step is a named function jitted with the
    cache arrays donated and compiled ahead of time a table width, the
    narrowest of the replica's widths (the quarters of
    ``max_blocks_per_seq``) that holds the position it writes, as
    ``_warm_up`` and ``_table_width`` do. So on a TPU a head's rows are
    whole lanes and ``auto`` takes the arm a served token takes;
    ``said`` names it."""

    def __init__(self, model, params, dcfg, cache_dtype):
        import jax
        import jax.numpy as jnp
        from distributedmnist_tpu.models.transformer import \
            decode_attention_arm
        from distributedmnist_tpu.servesvc.kv_cache import (
            PagedKVCache, cache_shapes, stored_head_dim)

        self.model, self.params, self.dcfg = model, params, dcfg
        dtype = jnp.dtype(cache_dtype)
        at = jax.tree.leaves(params)[0].sharding
        layers, heads, head_dim = model.decode_cache_shape
        shapes = cache_shapes(layers, dcfg.num_blocks, dcfg.block_size,
                              heads, head_dim)
        if isinstance(head_dim, tuple):
            head_dim = tuple(stored_head_dim(shape, dtype, at)
                             for shape in shapes)
        else:
            head_dim = stored_head_dim(shapes[0], dtype, at)
        self.cache = PagedKVCache(layers, dcfg.num_blocks, dcfg.block_size,
                                  heads, head_dim, dcfg.max_blocks_per_seq(),
                                  dtype=dtype)
        self.cache.k, self.cache.v = jax.device_put(
            (self.cache.k, self.cache.v), at)
        full = self.cache.max_blocks_per_seq
        self._widths = sorted({-(-full * k // 4) for k in (1, 2, 3, 4)})
        how = {"block_size": dcfg.block_size,
               "attention_kernel": dcfg.attention_kernel}

        # named functions, not partials: a trace calls a partial
        # `jit__unknown` (servesvc/decode.py)
        def decode_step(params, tokens, positions, k_cache, v_cache,
                        block_tables, lengths):
            return model.decode_step(params, tokens, positions, k_cache,
                                     v_cache, block_tables, lengths, **how)

        def decode_step_asked(params, tokens, positions, k_cache, v_cache,
                              block_tables, lengths):
            return compare.unpack(model.decode_step(
                params, tokens, positions, k_cache, v_cache, block_tables,
                lengths, return_routing=True, **how), 4, _STEP_SAID)

        self._prefill = jax.jit(model.decode_prefill)
        self._prefill_asked = jax.jit(lambda p, t: compare.unpack(
            model.decode_prefill(p, t, return_routing=True), 4,
            _PREFILL_SAID))
        self._step_jit = jax.jit(decode_step, donate_argnums=(3, 4))
        self._step_asked = jax.jit(decode_step_asked, donate_argnums=(3, 4))
        self._steps: dict = {}           # {table width: the executable}
        self._table = None
        self.said = {
            "session": "paged",
            "attention_arm": decode_attention_arm(dcfg.attention_kernel,
                                                  self.cache.k.shape),
            "cache_arrays": [list(self.cache.k.shape),
                             list(self.cache.v.shape)]}

    def prefill(self, prompt, return_routing: bool = False):
        import jax.numpy as jnp
        n = int(prompt.shape[0])
        tokens = jnp.asarray(prompt, jnp.int32)[None]
        if return_routing:
            compare.require_export(self.model.decode_prefill,
                                   "return_routing", _PREFILL_SAID)
            compare.require_export(self.model.decode_step,
                                   "return_routing", _STEP_SAID)
            logits, ks, vs, routing = self._prefill_asked(self.params,
                                                          tokens)
        else:
            logits, ks, vs = self._prefill(self.params, tokens)
        if self._table is None:
            # every block the sequence can need, as an admission does
            self._table = self.cache.alloc_sequence(
                n + self.dcfg.max_new_tokens)
        self.cache.write_prompt(self._table, ks[:, 0], vs[:, 0], n)
        row = logits[0, n - 1]
        return (row, routing[:, 0]) if return_routing else row

    def _inputs(self, token: int, position: int) -> tuple:
        import jax.numpy as jnp
        slots = self.dcfg.decode_slots
        width = next(w for w in self._widths
                     if w * self.cache.block_size >= position + 1)
        vec = lambda v: jnp.zeros(  # noqa: E731
            (slots,), jnp.int32).at[0].set(v)
        tables = np.zeros((slots, width), np.int32)
        tables[0] = self._table[:width]
        return width, (vec(token), vec(position), self.cache.k, self.cache.v,
                       jnp.asarray(tables), vec(position + 1))

    def step(self, token: int, position: int, return_routing: bool = False):
        width, inputs = self._inputs(token, position)
        if return_routing:
            # the same token at the same position writes the same rows
            # again, so the state is as the unasked step left it
            out, self.cache.k, self.cache.v, picked = self._step_asked(
                self.params, *inputs)
            return out[0], picked[:, 0]
        if width not in self._steps:
            self._steps[width] = self._step_jit.lower(
                self.params, *inputs).compile()
            self.said["table_blocks"] = width
            self.said["step_compiled_bytes"] = _compiled_bytes(
                self._steps[width])
        out, self.cache.k, self.cache.v = self._steps[width](
            self.params, *inputs)
        return out[0]


def _compiled_bytes(compiled) -> int | None:
    """Arguments, outputs and temporaries of a compiled program, less
    what it aliases; None where the backend does not say."""
    m = compiled.memory_analysis()
    if m is None:
        return None
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def decode_session(model, params, dcfg, cache_dtype):
    """The session one sequence is driven through: the model record's
    own ``decode_session`` export where it carries one, else
    :class:`PagedSession` over its paged exports."""
    own = getattr(model, "decode_session", None)
    if own is not None:
        return own(params, dcfg, cache_dtype)
    return PagedSession(model, params, dcfg, cache_dtype)


def check_decode_against_reference(model, params, dcfg, cache_dtype,
                                   vocab: int, cell, seed: int, *,
                                   reference_params=None,
                                   said: dict | None = None) -> dict:
    """Prefill of a seeded prompt and teacher-forced decode steps
    through a decode session (:func:`decode_session`: the model
    record's own, else a scratch paged cache built and stepped as
    ``DecodeReplica`` builds and steps its own), against the full
    forward of the reference of the cell's architecture at the same
    positions. Logits, never sampled tokens: with random weights the
    largest logit changes on a rounding. How the state is held is the
    session's; the sequence, the teacher forcing, the reference's call,
    the limits and the verdict are here.

    A routed architecture's reference takes, at each position, the
    experts the program chose where it computed that position: the
    prefill's for the prompt, each decode step's for its own token. So
    a cache path that routes a decoded token otherwise than the prefill
    would have is held to the same scores (``lib/compare.py``). The
    logits compared are those of the session as the replica drives it;
    the choices come from the same calls asked for them, each after the
    unasked one and over the state that one has just written, whose
    logits have to be the same to the bit.

    ``reference_params``: the tree the reference reads where it is not
    the program's (``lib/decode_controls.py``'s faults in the weights).
    ``said``, where given, is filled with what the session says of
    itself (the arm, the cache arrays) for the ``reference_check``
    event; the returned dictionary has the numbers and nothing else."""
    import jax
    import jax.numpy as jnp

    arch, config = cell.arch, cell.config
    routed = compare.is_routed(arch)
    # 128 and 8 at any real geometry; a toy one (the tests) caps them
    n_prompt = min(CHECK_PROMPT, dcfg.max_prompt_len)
    n_steps = min(CHECK_STEPS, dcfg.max_new_tokens)
    rng = np.random.default_rng([int(seed), 0xC4EC])
    seq = rng.integers(0, vocab, n_prompt + n_steps).astype(np.int32)
    session = decode_session(model, params, dcfg, cache_dtype)

    def asked(call, *args):
        got = compare.unpack(call(*args, return_routing=True), 2,
                             _SESSION_SAID)
        return got[0], jnp.asarray(got[1])

    rows = [session.prefill(seq[:n_prompt])]
    chosen, flag_diffs = [], []
    if routed:
        again, routing = asked(session.prefill, seq[:n_prompt])
        chosen.append(routing[:, None])     # [routed_layers, 1, n_prompt, k]
        flag_diffs.append(compare.max_abs_diff((again, rows[0])))
    for pos in range(n_prompt, n_prompt + n_steps):
        rows.append(session.step(int(seq[pos]), pos))
        if routed:
            again, picked = asked(session.step, int(seq[pos]), pos)
            chosen.append(picked[:, None, None])  # [routed_layers, 1, 1, k]
            flag_diffs.append(compare.max_abs_diff((again, rows[-1])))
    got = jnp.stack(rows)
    if said is not None:
        said.update(getattr(session, "said", {"session": "own"}))
    del session, rows                  # room for the float32 reference
    toks = jnp.asarray(seq[None])
    if reference_params is None:
        reference_params = params
    if not routed:
        want = jax.jit(lambda p, t: arch.logits(
            p, t, config, last=n_steps + 1))(reference_params, toks)[0]
        verdict = {}
    else:
        routing = jnp.concatenate(chosen, axis=2)
        want, slack = jax.jit(lambda p, t, r: (
            arch.logits(p, t, config, last=n_steps + 1, routing=r)[0],
            arch.routing_slack(p, t, config, r)))(reference_params, toks,
                                                  routing)
        verdict = compare.routing_verdict(
            slack, routing, compare.routed_experts(arch, config),
            float(np.max(flag_diffs)))
    err = max_rel_err(got, want)
    finite = bool(jnp.isfinite(got).all())
    return {"decode_logits_max_rel_err": err, "positions": n_steps + 1,
            **verdict,
            "ok": bool(finite and err <= DECODE_LOGITS_TOL
                       and verdict.get("routing_ok", True))}


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
        said: dict = {}
        check = check_decode_against_reference(
            model, state.params, cfg.decode,
            jax.numpy.dtype(model_cfg.compute_dtype), cfg.model.vocab_size,
            cell, rt.seed, said=said)
        del params
    rt.say(event="reference_check", **check, tolerance=DECODE_LOGITS_TOL,
           session=said)
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
        "compared": compare.compared(
            {"decode_logits_max_rel_err": (
                check["decode_logits_max_rel_err"], DECODE_LOGITS_TOL)},
            check, checks),
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
