#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts,
compiles and computes on the TPU: ``python chip_smoke.py``, no
arguments, ONE process, exit 0 only if every phase passed on a TPU.

It drives the two entry points a user drives, through the code the CLI
calls, at the full width of the model the repository benchmarks
(transformer d=2048, 16 heads of 128, S=1024, V=1024; depth 4; random
weights from a seed):

* ``train``   — ``launch train``'s bring-up + ``Trainer(cfg).run()``:
  ~20 sync-SGD steps through the Pallas flash kernels in bf16, a
  checkpoint published at the end;
* ``cnn_quorum`` — the paper's own step: the MNIST CNN under
  ``sync.mode=quorum`` with k = n-1 and the lognormal straggler
  profile (the masked psum the system is named for);
* ``serve``   — ``launch serve --decode``'s path: a ``DecodeReplica``
  on the checkpoint ``train`` published answers ``generate`` requests
  over its socket, once per ``decode.attention_kernel`` arm;
* ``kernels`` — both Pallas kernels and the decode step against their
  dense oracles at the smoke's shapes.

Each phase prints one JSON line, then a ``"phase": "summary"`` line
(versions, per-phase seconds, cache counts, ``"claim": null``); the LAST
stdout line is the result, ``{"ok": true, "device": {"platform": ...,
"kind": ..., "count": ...}}`` and nothing else. A phase that fails
raises: the script exits non-zero and prints neither. It refuses (exit 2, no
stdout) when JAX's first device is not a TPU. It needs no network and
no dataset file (``synthetic``/``synthetic_lm`` from seeds) and starts
no process.

The phase functions take their sizes as arguments so that
tests/test_chip_smoke.py can run them tiny on the CPU test mesh (Pallas
interpreted there). The TPU gate and the compiled-not-interpreted
(Mosaic custom call) assertions live in :func:`main`.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

# d=2048 H=16 S=1024 V=1024 at depth 4: the width the flash kernels'
# block table was tuned at (the CPU-era harness removed at PR 48)
FULL_MODEL = {"name": "transformer", "model_dim": 2048, "num_layers": 4,
              "num_heads": 16, "seq_len": 1024, "vocab_size": 1024,
              "attention_impl": "flash", "compute_dtype": "bfloat16"}
# plain SGD at this width: 0.05 falls smoothly from ~7.2 toward the
# unigram entropy 6.93; 0.3 oscillates and 1.0 diverges (probed at
# d=2048, L=4 before the first chip run)
TRAIN_LR = 0.05
SEED = 20260926
# what the serve phase asks of each arm's replica, and what the kernels
# phase decodes again on caches it can compare
SERVE_PROMPT_LENS = (5, 12, 40, 100, 7, 33)
SERVE_NEW_TOKENS = 16


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _mosaic_calls(compiled_text: str) -> dict:
    """Mosaic (compiled Pallas) custom calls in an executable's text.
    An interpreted kernel lowers to plain HLO and leaves none. The
    backward kernels sit under a ``transpose(jvp(...))`` scope."""
    lines = [l for l in compiled_text.splitlines()
             if "tpu_custom_call" in l]
    backward = sum("transpose(" in l for l in lines)
    return {"total": len(lines), "forward": len(lines) - backward,
            "backward": backward}


class _CompileMeter:
    """Seconds this process spent in jax's compile-or-load-from-cache
    path, and in tracing + lowering, since the last :meth:`take`."""

    _EVENTS = {"/jax/core/compile/backend_compile_duration": "compile_s",
               "/jax/core/compile/jaxpr_trace_duration": "trace_lower_s",
               "/jax/core/compile/jaxpr_to_mlir_module_duration":
                   "trace_lower_s"}

    def __init__(self):
        from jax import monitoring
        self._acc = {"compile_s": 0.0, "trace_lower_s": 0.0}
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        key = self._EVENTS.get(event)
        if key is not None:
            self._acc[key] += secs

    def take(self) -> dict:
        got = {k: round(v, 3) for k, v in self._acc.items()}
        self._acc = dict.fromkeys(self._acc, 0.0)
        return got


def _layout_checks(trainer, n_dev: int) -> dict:
    """Read the layout off the ARRAYS, not the config: every parameter
    leaf spans every visible device, and the loop's own batch placement
    yields one equal shard per device."""
    import jax

    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree.leaves(trainer.state.params)}
    _require(spans == {n_dev},
             f"parameter leaves span {sorted(spans)} devices, not {n_dev}")
    img = trainer.datasets.train.images
    rows = trainer.cfg.data.batch_size
    placed = trainer.topo.device_put_batch(
        {"image": np.zeros((rows, *img.shape[1:]), img.dtype)},
        seq_sharded=trainer.seq_sharded)["image"]
    shards = placed.addressable_shards
    _require(len(shards) == n_dev
             and {s.data.shape[0] for s in shards} == {rows // n_dev}
             and len({s.device for s in shards}) == n_dev,
             f"batch of {rows} rows is not one equal shard on each of "
             f"{n_dev} devices")
    return {"param_leaf_devices": n_dev, "batch_shards": len(shards),
            "rows_per_shard": rows // n_dev}


def _run_trainer(workdir: Path, name: str, *, per_device_batch: int,
                 steps: int, log_every: int, train_batches: int,
                 data: dict, **sections):
    """``launch train`` as the CLI does it — the config from a file
    through ``_load_cfg_and_bringup`` (multi-host discovery, the
    compile-cache rule), then ``Trainer(cfg).run()`` — plus the checks
    every trainer phase shares. Returns (trainer, summary, step records,
    result fields). A simulated mesh is refused: this script only runs
    on the devices that are there."""
    import jax
    from distributedmnist_tpu.launch.__main__ import _load_cfg_and_bringup
    from distributedmnist_tpu.obsv.report import load_jsonl
    from distributedmnist_tpu.train.loop import Trainer

    _require(sections.get("mesh", {}).get("simulate_devices", 0) == 0,
             "chip_smoke refuses mesh.simulate_devices > 0")
    n_dev = len(jax.devices())
    batch = per_device_batch * n_dev
    train_dir = workdir / name
    cfg_path = workdir / f"{name}_cfg.json"
    cfg_path.write_text(json.dumps({
        "name": f"chip_smoke_{name}",
        # no dataset file, no network; and the checkout builds no
        # native library, so the Python loader it is
        "data": {"batch_size": batch,
                 "synthetic_train_size": train_batches * batch,
                 "use_native_pipeline": False, **data},
        "train": {"max_steps": steps, "train_dir": str(train_dir),
                  "seed": SEED, "log_every_steps": log_every,
                  "save_interval_steps": 0, "save_results_period": 0,
                  "summary_every_steps": 0},
        **sections}, indent=1))
    cfg = _load_cfg_and_bringup(
        argparse.Namespace(config=str(cfg_path), overrides=[]))
    trainer = Trainer(cfg)
    layout = _layout_checks(trainer, n_dev)
    summary = trainer.run()

    log = train_dir / "train_log.jsonl"
    step_recs, compile_recs = load_jsonl(log, "step"), load_jsonl(log, "compile")
    _require(len(compile_recs) == 1, f"compile records: {compile_recs}")
    comp = compile_recs[0]
    # Trainer.run() keeps an inline-compile fallback for production; the
    # smoke reads the record and fails on it
    _require(comp.get("source") not in (None, "inline")
             and "error" not in comp, f"precompile fell back: {comp}")
    _require(len(step_recs) == steps, f"{len(step_recs)} step records "
                                      f"of {steps}")
    _require(all(math.isfinite(r["loss"]) for r in step_recs),
             f"non-finite loss in {[r['loss'] for r in step_recs]}")
    # the NaN guard must not paper over a bad kernel
    _require(summary["nan_rollbacks"] == 0
             and not (train_dir / "recovery_journal.jsonl").exists(),
             "recovery journal is not empty (rollback or failed save)")
    return trainer, step_recs, {
        "steps": steps, "global_batch": batch, "replicas": n_dev,
        "first_loss": step_recs[0]["loss"],
        "last_loss": step_recs[-1]["loss"], "compile_record": comp,
        "layout": layout, "loader": type(trainer.train_iter).__name__,
        "train_dir": str(train_dir)}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_train(workdir: Path, *, model: dict, per_device_batch: int,
                steps: int, lr: float = TRAIN_LR) -> dict:
    """``launch train``: bring-up, ``Trainer(cfg).run()``, evaluate. The
    train dir ends with the checkpoint the serve phase follows."""
    trainer, _, out = _run_trainer(
        workdir, "train", per_device_batch=per_device_batch, steps=steps,
        log_every=max(1, steps // 4), train_batches=4,
        data={"dataset": "synthetic_lm", "synthetic_test_size": 64},
        model=model, sync={"mode": "sync"},
        optim={"initial_learning_rate": lr,
               "learning_rate_decay_factor": 1.0})
    test = trainer.evaluate("test")
    _require(out["last_loss"] < out["first_loss"],
             f"loss did not fall: first {out['first_loss']}, "
             f"last {out['last_loss']}")
    _require(math.isfinite(test["loss"]), f"test loss {test['loss']}")
    _require((Path(out["train_dir"]) / "checkpoint.json").exists(),
             "no checkpoint published at the end of the run")
    return {**out, "test_loss": round(test["loss"], 4),
            "tokens_per_step": out["global_batch"] * model["seq_len"],
            "mosaic_calls": _mosaic_calls(
                trainer.step_fn.executable().as_text())}


def phase_cnn_quorum(workdir: Path, *, per_device_batch: int,
                     steps: int, compute_dtype: str = "bfloat16") -> dict:
    """The paper's own step: ``ModelConfig()`` defaults (the MNIST CNN)
    under quorum k = max(1, n-1) with the lognormal straggler profile."""
    import jax

    n_dev = len(jax.devices())
    k = max(1, n_dev - 1)
    _, step_recs, out = _run_trainer(
        workdir, "cnn", per_device_batch=per_device_batch, steps=steps,
        log_every=steps, train_batches=2,
        data={"dataset": "synthetic", "synthetic_test_size": 256},
        model={"compute_dtype": compute_dtype},
        sync={"mode": "quorum", "num_replicas_to_aggregate": k,
              "straggler_profile": "lognormal"})
    for r in step_recs:
        _require(r["num_contributors"] == k and sum(r["flags"]) == k,
                 f"step {r['step']}: {r['num_contributors']} contributors "
                 f"(flags {r['flags']}), want exactly k={k} of {n_dev}")
    return {**out, "quorum_k": k,
            "num_contributors": step_recs[-1]["num_contributors"],
            "flags_last_step": step_recs[-1]["flags"]}


def _serve_arm(train_dir: Path, serve_dir: Path, cfg, kernel: str,
               prompts: list[list[int]], *, max_new_tokens: int,
               max_prompt_len: int, block_size: int, num_blocks: int,
               decode_slots: int, concurrency: int) -> dict:
    """One ``launch serve --decode --attention-kernel KERNEL`` replica,
    in this process, answering ``prompts`` over its socket."""
    import jax
    import jax.numpy as jnp
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.servesvc.decode import DecodeReplica

    dcfg = dataclasses.replace(
        cfg.decode, attention_kernel=kernel, block_size=block_size,
        num_blocks=num_blocks, decode_slots=decode_slots,
        max_prompt_len=max_prompt_len, max_new_tokens=max_new_tokens)
    rep = DecodeReplica(train_dir, serve_dir=serve_dir, scfg=cfg.serve,
                        dcfg=dcfg, cfg=cfg)
    t0 = time.time()
    rep.start()
    try:
        # one attempt, a deadline that outlasts a cold prefill compile:
        # a retry would hide a failed request
        client = ServeClient([("127.0.0.1", rep.bound_port)],
                             deadline_s=900.0, max_attempts=1)
        with ThreadPoolExecutor(concurrency) as pool:
            outs = list(pool.map(
                lambda ip: client.generate(
                    ip[1], request_id=f"{kernel}-{ip[0]}",
                    max_tokens=max_new_tokens, temperature=0.0),
                enumerate(prompts)))
    finally:
        rep.stop()
    wall = time.time() - t0
    for i, out in enumerate(outs):
        _require(out.get("status") == "ok"
                 and len(out.get("tokens") or []) == max_new_tokens
                 and out.get("finish_reason") == "max_tokens",
                 f"{kernel} request {i}: {out}")
    # The programs the replica ran, re-lowered at its own shapes (after
    # stop(): the loop thread donated these buffers while it ran). The
    # persistent cache makes each a read, not a compile.
    slots, width = dcfg.decode_slots, rep.cache.max_blocks_per_seq
    zi = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    step_text = rep._decode_jit.lower(
        rep._params, zi(slots), zi(slots), rep.cache.k, rep.cache.v,
        zi(slots, width), zi(slots)).compile().as_text()
    buckets = sorted({rep._bucket(len(p), max_prompt_len) for p in prompts})
    prefill_text = rep._prefill_jit.lower(
        rep._params, zi(1, buckets[-1])).compile().as_text()
    device = next(iter(jax.tree.leaves(rep._params)[0].sharding.device_set))
    return {"kernel": kernel, "requests": len(outs),
            "tokens": [o["tokens"] for o in outs],
            "ttft_ms": [o.get("ttft_ms") for o in outs],
            "tokens_streamed": rep.tokens_streamed,
            "prefill_buckets": buckets, "seconds": round(wall, 2),
            "model_step": rep.model_step,
            "device": f"{device.platform}:{device.id}",
            "step_mosaic_calls": _mosaic_calls(step_text),
            "prefill_mosaic_calls": _mosaic_calls(prefill_text)}


def phase_serve(train_dir: Path, workdir: Path, *, prompt_lens: list[int],
                max_new_tokens: int, max_prompt_len: int,
                block_size: int = 16, num_blocks: int = 128,
                decode_slots: int = 4, concurrency: int = 3) -> dict:
    """``launch serve --decode`` on the published checkpoint, once per
    ``decode.attention_kernel`` arm, same prompts, greedy."""
    from distributedmnist_tpu.servesvc.server import wait_for_run_config

    cfg = wait_for_run_config(train_dir)  # as the serve CLI adopts it
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.model.vocab_size, n).tolist()
               for n in prompt_lens]
    arms = {kernel: _serve_arm(
        train_dir, workdir / f"serve_{kernel}", cfg, kernel, prompts,
        max_new_tokens=max_new_tokens, max_prompt_len=max_prompt_len,
        block_size=block_size, num_blocks=num_blocks,
        decode_slots=decode_slots, concurrency=concurrency)
        for kernel in ("dense", "paged")}
    dense, paged = arms["dense"]["tokens"], arms["paged"]["tokens"]
    # Token i+1 is conditioned on token i, so the arms are compared by
    # the length of each request's common prefix. The first token comes
    # from the prefill (one program for both arms) and must be equal;
    # after it the arms differ only in how the cache is read. In bf16 a
    # near-tie in the logits can flip a greedy pick (the logits
    # tolerance is pinned in phase_kernels), so the gate is on the mean
    # common-prefix share: a broken kernel diverges at the first decode
    # step and scores 1/max_new_tokens.
    prefix = [_common_prefix(d, p) for d, p in zip(dense, paged)]
    _require(all(n >= 1 for n in prefix),
             f"first (prefill) tokens differ between arms: {dense} {paged}")
    share = sum(prefix) / (len(prefix) * max_new_tokens)
    _require(share >= 0.75,
             f"dense and paged arms agree on only {share:.2f} of the "
             f"greedy tokens (common prefixes {prefix} of "
             f"{max_new_tokens})")
    for arm in arms.values():
        del arm["tokens"]
    return {"requests": 2 * len(prompts), "prompt_lens": prompt_lens,
            "max_new_tokens": max_new_tokens, "block_size": block_size,
            "common_prefix": prefix, "token_agreement": round(share, 4),
            "identical": dense == paged, "arms": arms,
            "replica_devices": "one: jax.devices()[:1] by design "
                               "(servesvc/server.py)"}


def _common_prefix(a, b) -> int:
    """How many leading tokens two equally long sequences share."""
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), len(a))


def _max_err(got, want) -> float:
    """Largest error relative to the oracle's largest magnitude."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def phase_kernels(*, model: dict, batch: int, block_size: int = 16,
                  context: int = 100, tol: float = 2e-2,
                  prompt_lens: tuple[int, ...] = SERVE_PROMPT_LENS,
                  steps: int = SERVE_NEW_TOKENS) -> dict:
    """Both Pallas kernels, and the decode step that uses the paged one,
    against their dense oracles at the smoke's shapes.

    ``tol`` bounds the largest error relative to the oracle's largest
    value. bf16 carries 8 mantissa bits (2^-8 = 4e-3 per rounding); the
    kernel and the oracle round p and the output at different points
    and the TPU's default matmul precision feeds the MXU bf16 operands,
    so a few roundings stack: 2e-2 holds that and is 50x below an O(1)
    masking or indexing error."""
    import jax
    import jax.numpy as jnp
    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.ops.pallas_attention import flash_attention_bshd
    from distributedmnist_tpu.ops.pallas_paged_attention import (
        paged_attention, paged_attention_dense)
    from distributedmnist_tpu.ops.ring_attention import local_self_attention
    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    dtype = jnp.dtype(model["compute_dtype"])
    heads, seq = model["num_heads"], model["seq_len"]
    hd = model["model_dim"] // heads
    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)
    out: dict = {"tolerance": tol, "dtype": str(dtype)}

    # (a) flash forward + backward vs the dense einsum oracle
    q, k, v = (jax.random.normal(kk, (batch, seq, heads, hd), dtype)
               for kk in keys[:3])
    cot = jax.random.normal(keys[3], (batch, seq, heads, hd), dtype)
    bhsd = lambda t: t.transpose(0, 2, 1, 3)  # noqa: E731

    def run(attn, *qkv):
        o, vjp = jax.vjp(attn, *qkv)
        return (o, *vjp(cot))

    flash = jax.jit(lambda *qkv: run(flash_attention_bshd, *qkv))
    dense = jax.jit(lambda *qkv: run(
        lambda a, b, c: bhsd(local_self_attention(
            bhsd(a), bhsd(b), bhsd(c), causal=True)), *qkv))
    errs = [_max_err(g, w) for g, w in zip(flash(q, k, v), dense(q, k, v))]
    out["flash_vs_dense"] = dict(zip(("out", "dq", "dk", "dv"),
                                     (round(e, 5) for e in errs)))
    _require(max(errs) <= tol, f"flash vs dense: {out['flash_vs_dense']}")
    out["flash_mosaic_calls"] = _mosaic_calls(
        flash.lower(q, k, v).compile().as_text())

    # (b) paged attention vs the dense gather, a mixed slot batch:
    # fresh, mid-block, multi-block, idle
    width = -(-context // block_size) + 1
    nblocks = 4 * width + 1
    kp, vp = (jax.random.normal(kk, (nblocks, block_size, heads, hd), dtype)
              for kk in keys[4:6])
    tables = np.zeros((4, width), np.int32)
    lengths = np.asarray([1, block_size + 3, context, 0], np.int32)
    nxt = 1
    for s, n in enumerate(lengths):
        used = -(-int(n) // block_size)
        tables[s, :used] = np.arange(nxt, nxt + used)
        nxt += used
    qd = jax.random.normal(keys[6], (4, heads, hd), dtype)
    args = (qd, kp, vp, jnp.asarray(tables), jnp.asarray(lengths))
    got, want = paged_attention(*args), paged_attention_dense(*args)
    err = _max_err(got[:3], want[:3])
    out["paged_vs_dense"] = round(err, 5)
    _require(err <= tol, f"paged vs dense gather: {err}")
    _require(not np.asarray(got[3]).any(), "idle slot is not exact zeros")
    out["paged_mosaic_calls"] = _mosaic_calls(
        paged_attention.lower(*args).compile().as_text())

    # (c) the decode step's logits, dense arm vs paged arm, on a cache
    # seeded by the model's own prefill (random weights from the seed)
    mdl = get_model(ModelConfig(**model))
    params = mdl.init(keys[7])
    layers, _, _ = mdl.decode_cache_shape
    cache = PagedKVCache(layers, nblocks, block_size, heads, hd,
                         max_blocks_per_seq=width, dtype=dtype)
    plen = min(context, seq - 1)
    bucket = 1 << (plen - 1).bit_length()
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :plen] = np.random.default_rng(SEED).integers(
        0, model["vocab_size"], plen)
    logits, ks, vs = jax.jit(mdl.decode_prefill)(params, jnp.asarray(toks))
    table = cache.alloc_sequence(plen + 1)
    cache.write_prompt(table, ks[:, 0], vs[:, 0], plen)
    step_in = (params, jnp.asarray([int(jnp.argmax(logits[0, plen - 1])), 0],
                                   jnp.int32),
               jnp.asarray([plen, 0], jnp.int32), cache.k, cache.v,
               jnp.asarray(np.stack([table, np.zeros_like(table)])),
               jnp.asarray([plen + 1, 0], jnp.int32))
    lg = {kern: jax.jit(lambda *a, kern=kern: mdl.decode_step(
              *a, block_size=block_size, attention_kernel=kern))(*step_in)[0]
          for kern in ("dense", "paged")}
    err = _max_err(lg["paged"][0], lg["dense"][0])
    out["decode_step_logits_paged_vs_dense"] = round(err, 5)
    _require(np.isfinite(np.asarray(lg["paged"][0])).all()
             and err <= tol, f"decode step logits, paged vs dense: {err}")
    out["decode_step_argmax_equal"] = bool(
        int(jnp.argmax(lg["paged"][0])) == int(jnp.argmax(lg["dense"][0])))
    out["latent_routed_block"] = _latent_routed_arm(model, batch)
    out["wide_cache_rows"] = _wide_cache_arm(model, block_size, context)
    out["rows_written_in_the_kernel"] = _rows_written_arm(
        model, block_size, list(prompt_lens), steps, tol)
    out["latent_decode"] = _latent_decode_arm(model, block_size, tol)
    return out


def _wide_cache_arm(model: dict, block_size: int, context: int,
                    steps: int = 8) -> dict:
    """(e) the cache stored as wide as the device keeps its rows whole
    (``stored_head_dim``, what the decode replica builds) against the
    cache of the head's own width, at 64-wide heads, where a TPU's
    default layout makes the block index minor and a step transposes
    both arrays in and out: a written prompt and ``steps`` greedy steps
    through ``write_prompt`` and ``jax.jit(model.decode_step)`` on each.
    Same logits to the bit, the same bytes read back from both caches,
    and no copy of a whole cache array in the wide one's step. On a CPU
    both widths are the head's and the arm proves the plumbing only."""
    import functools
    import re

    import jax
    import jax.numpy as jnp
    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.models.transformer import decode_attention_arm
    from distributedmnist_tpu.servesvc.kv_cache import (PagedKVCache,
                                                        stored_head_dim)

    heads = max(model["model_dim"] // 64, 1)
    mdl = get_model(ModelConfig(**{**model, "num_heads": heads}))
    params = mdl.init(jax.random.PRNGKey(SEED))
    layers, _, hd = mdl.decode_cache_shape
    plen = min(context, model["seq_len"] - steps - 1)
    width = -(-(plen + steps + 1) // block_size)
    slots, nblocks = 2, 16 * width + 1
    dtype = jnp.dtype(model["compute_dtype"])
    wide = stored_head_dim((layers, nblocks, block_size, heads, hd), dtype)
    toks = np.zeros((1, 1 << (plen - 1).bit_length()), np.int32)
    toks[0, :plen] = np.random.default_rng(SEED + 1).integers(
        0, model["vocab_size"], plen)
    logits, ks, vs = jax.jit(mdl.decode_prefill)(params, jnp.asarray(toks))
    first = int(jnp.argmax(logits[0, plen - 1]))
    # the gather by name: the same arithmetic on either width, which is
    # what "to the bit" says (``auto`` takes the kernel on whole rows)
    step = jax.jit(functools.partial(mdl.decode_step, block_size=block_size,
                                     attention_kernel="dense"),
                   donate_argnums=(3, 4))

    def decode(head_dim):
        cache = PagedKVCache(layers, nblocks, block_size, heads, head_dim,
                             max_blocks_per_seq=width, dtype=dtype)
        dims = re.escape(f"[{','.join(map(str, cache.k.shape))}]")
        ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        text = step.lower(params, ints(slots), ints(slots), cache.k, cache.v,
                          ints(slots, width), ints(slots)).compile().as_text()
        said = {"cache_shape": list(cache.k.shape),
                "cache_layout": str(cache.k.format.layout),
                "cache_device_bytes": cache.k.on_device_size_in_bytes(),
                "whole_cache_copies": len(re.findall(
                    rf"= \w+{dims}\{{[^}}]*\}} copy\(", text))}
        table = cache.alloc_sequence(plen + steps)
        cache.write_prompt(table, ks[:, 0], vs[:, 0], plen)
        tables = jnp.asarray(np.stack([table, np.zeros_like(table)]))
        tok, rows = first, []
        for pos in range(plen, plen + steps):
            out, cache.k, cache.v = step(
                params, jnp.asarray([tok, 0], jnp.int32),
                jnp.asarray([pos, 0], jnp.int32), cache.k, cache.v,
                tables, jnp.asarray([pos + 1, 0], jnp.int32))
            rows.append(np.asarray(out[0]))
            tok = int(rows[-1].argmax())
        # the paged kernel takes the rows as stored, one step more
        paged = jax.jit(functools.partial(
            mdl.decode_step, block_size=block_size,
            attention_kernel="paged"))(
                params, jnp.asarray([tok, 0], jnp.int32),
                jnp.asarray([plen + steps, 0], jnp.int32), cache.k, cache.v,
                tables, jnp.asarray([plen + steps + 1, 0], jnp.int32))[0]
        kv = [a[..., :hd] for a in cache.gather_dense(table, plen + steps)]
        return np.stack(rows), kv, np.asarray(paged[0]), said

    want, want_kv, want_paged, plain_said = decode(hd)
    got, got_kv, got_paged, wide_said = decode(wide)
    out = {"head_dim": hd, "stored_head_dim": wide, "steps": steps,
           "head_wide": plain_said, "stored_wide": wide_said,
           "logits_bit_equal": bool(np.array_equal(got, want)),
           "logits_max_abs_diff": float(np.abs(
               got.astype(np.float32) - want.astype(np.float32)).max()),
           "cache_bytes_equal": all(
               np.array_equal(g, w) for g, w in zip(got_kv, want_kv)),
           "paged_logits_bit_equal": bool(
               np.array_equal(got_paged, want_paged)),
           # what a step takes when nobody names an arm
           "auto_arm": {"head_wide": decode_attention_arm(
                            "auto", tuple(plain_said["cache_shape"])),
                        "stored_wide": decode_attention_arm(
                            "auto", tuple(wide_said["cache_shape"]))}}
    on_tpu = jax.devices()[0].platform == "tpu"
    _require(np.isfinite(got).all() and out["logits_bit_equal"]
             and out["cache_bytes_equal"]
             and _max_err(got_paged, want_paged) <= 2e-2
             and wide_said["whole_cache_copies"] == 0
             and out["auto_arm"] == {
                 "head_wide": "gather",
                 "stored_wide": "paged" if on_tpu else "gather"},
             f"the cache with whole rows decodes otherwise: {out}")
    return out


def _rows_written_arm(model: dict, block_size: int, prompt_lens: list[int],
                      steps: int, tol: float) -> dict:
    """(f) the new token's keys and values, written by the paged kernel
    itself (``paged_attention_write``: the paged arm's step) against the
    scatter through XLA: the serve phase's prompts in one batch of slots
    (two more idle), ``steps`` greedy steps each, at 64-wide heads in
    128-wide rows. Three decodes of the
    same prompts, each on a cache of its own:

    * ``paged``: the step as the replica runs it on a TPU;
    * ``scattered``: the same step with the kernel's writing form
      replaced, here and nowhere in the program, by the scatter and
      then the read-only form. Same rows, same read, so everything must
      be equal to the bit: every step's logits and both cache arrays
      whole, every layer, block and lane;
    * ``dense``: the gather arm. Its attention is other arithmetic, so
      a deeper layer's rows differ in a last bit here and there and a
      near-tie can flip a greedy pick: the tokens agree as far as the
      serve phase asks, layer 0's rows (a function of the tokens alone)
      are equal to the bit over each slot's common prefix, and the
      deeper layers' within ``tol``."""
    import functools
    from unittest import mock

    import jax
    import jax.numpy as jnp
    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.ops import pallas_paged_attention as ppa
    from distributedmnist_tpu.servesvc.decode import while_loops
    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    heads = max(model["model_dim"] // 64, 1)
    mdl = get_model(ModelConfig(**{**model, "num_heads": heads}))
    params = mdl.init(jax.random.PRNGKey(SEED + 2))
    layers, _, hd = mdl.decode_cache_shape
    dtype = jnp.dtype(model["compute_dtype"])
    live, slots = len(prompt_lens), len(prompt_lens) + 2
    width = -(-(max(prompt_lens) + steps) // block_size)
    nblocks = live * width + 1
    # rows of whole lanes, as the replica's cache has them on a TPU at a
    # served model's size (this one's few blocks would stay 64 wide, and
    # the kernel, compiled, takes whole lanes)
    wide = -(-hd // 128) * 128
    rng = np.random.default_rng(SEED + 2)
    prefill = jax.jit(mdl.decode_prefill)
    prompts = []
    for n in prompt_lens:
        toks = np.zeros((1, 1 << max(n - 1, 1).bit_length()), np.int32)
        toks[0, :n] = rng.integers(0, model["vocab_size"], n)
        logits, ks, vs = prefill(params, jnp.asarray(toks))
        prompts.append((ks[:, 0], vs[:, 0], int(jnp.argmax(logits[0, n - 1]))))

    def scatter_then_read(q, k_new, v_new, k_pages, v_pages, tables, lengths,
                          *, layer, scale):
        k_pages, v_pages = (
            ppa._scattered(pages, new, tables, lengths, layer)
            for pages, new in ((k_pages, k_new), (v_pages, v_new)))
        return (ppa.paged_attention(q, k_pages, v_pages, tables, lengths,
                                    layer=layer, scale=scale),
                k_pages, v_pages)

    def decode(kernel, write=None):
        cache = PagedKVCache(layers, nblocks, block_size, heads, wide,
                             max_blocks_per_seq=width, dtype=dtype)
        tables = np.zeros((slots, width), np.int32)
        for slot, (n, (ks, vs, _)) in enumerate(zip(prompt_lens, prompts)):
            tables[slot] = cache.alloc_sequence(n + steps)
            cache.write_prompt(tables[slot], ks, vs, n)
        step = jax.jit(functools.partial(mdl.decode_step,
                                         block_size=block_size,
                                         attention_kernel=kernel),
                       donate_argnums=(3, 4))
        idle = [0] * (slots - live)
        toks, picked, rows = [first for _, _, first in prompts], [], []
        # where a prompt's padding went; an idle slot's row goes there
        # through the scatter, and nowhere through the kernel
        null_before = [np.asarray(a[:, 0].astype(jnp.float32))
                       for a in (cache.k, cache.v)]
        with mock.patch.object(ppa, "paged_attention_write",
                               write or ppa.paged_attention_write):
            text = step.lower(
                params, *(jax.ShapeDtypeStruct((slots,), jnp.int32),) * 2,
                cache.k, cache.v, jnp.asarray(tables),
                jax.ShapeDtypeStruct((slots,), jnp.int32)).compile().as_text()
            for i in range(steps):
                pos = [n + i for n in prompt_lens]
                out, cache.k, cache.v = step(
                    params, jnp.asarray([*toks, *idle], jnp.int32),
                    jnp.asarray([*pos, *idle], jnp.int32), cache.k, cache.v,
                    jnp.asarray(tables),
                    jnp.asarray([*(p + 1 for p in pos), *idle], jnp.int32))
                rows.append(np.asarray(out[:live].astype(jnp.float32)))
                toks = [int(t) for t in rows[-1].argmax(-1)]
                picked.append(toks)
        return {"tokens": np.asarray(picked).T, "logits": np.stack(rows),
                "k": np.asarray(cache.k.astype(jnp.float32)),
                "v": np.asarray(cache.v.astype(jnp.float32)),
                "tables": tables, "text": text, "null_before": null_before}

    paged, scattered = decode("paged"), decode("paged", scatter_then_read)
    dense = decode("dense")
    same_as_scattered = {
        "logits": bool(np.array_equal(paged["logits"], scattered["logits"])),
        "k": bool(np.array_equal(paged["k"], scattered["k"])),
        "v": bool(np.array_equal(paged["v"], scattered["v"]))}
    # against the gather arm, each slot as far as the two agree on its
    # tokens: the row at position n + i is token i's, and token 0 is the
    # prefill's (one program for both)
    prefix = [_common_prefix(d, p)
              for d, p in zip(dense["tokens"], paged["tokens"])]
    first_layer_equal, deeper = True, 0.0
    for slot, (n, agreed) in enumerate(zip(prompt_lens, prefix)):
        at = [(paged["tables"][slot, p // block_size], p % block_size)
              for p in range(n + min(agreed + 1, steps))]
        blocks, offs = (np.asarray(x) for x in zip(*at))
        for name in ("k", "v"):
            got = paged[name][:, blocks, offs]
            want = dense[name][:, blocks, offs]
            first_layer_equal &= bool(np.array_equal(got[0], want[0]))
            deeper = max(deeper, _max_err(got[1:], want[1:]))
    share = sum(prefix) / (live * steps)
    whiles = {name: while_loops(run["text"])
              for name, run in (("paged", paged), ("scattered", scattered),
                                ("dense", dense))}
    out = {"head_dim": hd, "stored_head_dim": wide, "steps": steps,
           "prompt_lens": prompt_lens, "slots": slots,
           "paged_equals_scattered_to_the_bit": same_as_scattered,
           "rows_beside_the_head_are_zero": not (
               paged["k"][..., hd:].any() or paged["v"][..., hd:].any()),
           "null_block_untouched": all(
               np.array_equal(paged[name][:, 0], was) for name, was
               in zip(("k", "v"), paged["null_before"])),
           "dense_common_prefix": prefix,
           "dense_token_agreement": round(share, 4),
           "dense_first_layer_rows_equal_to_the_bit": first_layer_equal,
           "dense_deeper_layers_max_rel_diff": round(deeper, 5),
           "step_while_loops": whiles,
           "paged_step_mosaic_calls": _mosaic_calls(paged["text"])["total"]}
    _require(all(same_as_scattered.values())
             and out["rows_beside_the_head_are_zero"]
             and out["null_block_untouched"]
             and np.isfinite(paged["logits"]).all()
             and first_layer_equal and deeper <= tol and share >= 0.75,
             f"the kernel's row copies leave another cache than the "
             f"scatter: {out}")
    return out


def _latent_routed_arm(model: dict, batch: int) -> dict:
    """(d) one train step's loss and gradients of the block with latent
    attention, per-token routing over a held share, four residual
    streams and the next-next-token module, at the smoke's width cut
    eightfold, two layers: flash through the padded 24 + 8 | 16 heads,
    the grouped product's loop, the Sinkhorn iteration. Finite loss and
    gradients, every position's ids different experts in range, the
    flag's logits the unflagged to the bit."""
    import jax
    import jax.numpy as jnp
    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model

    d, heads = max(model["model_dim"] // 8, 32), 4
    seq = min(model["seq_len"], 256)
    mdl = get_model(ModelConfig(**{
        **model, "model_dim": d, "num_heads": heads, "num_layers": 2,
        "seq_len": seq, "q_latent_dim": d // 2, "kv_latent_dim": d // 4,
        "qk_nope_dim": 24, "qk_rope_dim": 8, "v_head_dim": 16,
        "rope_factor": 4.0, "rope_original_len": seq // 4,
        "rope_mscale_all_dim": 1.0, "ffn_dim": 2 * d, "routed_experts": 16,
        "held_experts": 8, "experts_per_token": 4, "shared_experts": 1,
        "expert_ffn_dim": d // 2, "routed_scaling": 2.0, "dense_layers": 1,
        "residual_streams": 4, "nextn_layers": 1, "remat": True}))
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), 2)
    params = mdl.init(keys[0])
    toks = jax.random.randint(keys[1], (batch, seq), 0, model["vocab_size"])

    def loss_of(p):
        logits, aux = mdl.apply(p, toks, train=True, return_aux=True)
        return mdl.loss(logits, toks) + aux["loss"], aux

    step = jax.jit(jax.value_and_grad(loss_of, has_aux=True))
    (loss, aux), grads = step(params)
    ids = np.sort(np.asarray(aux["routing"]), axis=-1)
    finite = all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    flagged = jax.jit(lambda p: mdl.apply(p, toks, return_aux=True)[0])
    same = bool(jnp.array_equal(flagged(params),
                                jax.jit(lambda p: mdl.apply(p, toks))(params)))
    _require(bool(jnp.isfinite(loss)) and finite,
             f"latent routed block: loss {float(loss)}, gradients finite: "
             f"{finite}")
    _require(ids.min() >= 0 and ids.max() < 16
             and (ids[..., 1:] != ids[..., :-1]).all() and same,
             "latent routed block: a position's experts repeat or leave "
             f"the range, or the routing flag moved the logits ({same})")
    return {"loss": round(float(loss), 4),
            "pairs_held": int(np.asarray(aux["counts"]).sum()),
            "pairs": int(ids.size),
            "mosaic_calls": _mosaic_calls(
                step.lower(params).compile().as_text())}


def _latent_decode_arm(model: dict, block_size: int, tol: float) -> dict:
    """(e) the absorbed decode step against the expanded form, and the
    latent paged kernel against the gather: a block with latent
    attention, sandwich norms and per-token routing over a held share
    (one residual stream: the kind with a decode export), at the smoke's
    width cut eightfold with a latent of 128, two layers. A prompt's
    latents and rotated keys written into a paged cache stored as the
    device keeps its rows whole (128 | 128 on a v5e, where ``auto``
    answers ``paged`` and the compiled step holds a Mosaic call of
    ``paged_latent_decode`` a layer: main() requires both), 8 greedy
    steps through it under ``paged``, each step's logits against the
    full forward's at that position (flash, the expanded form) within
    the kernels' tolerance; the same tokens through ``dense`` on a cache
    of its own: logits within the tolerance, layer 0's rows of both
    arrays to the bit (deeper layers' rows come from rounded
    attention); every step's expert ids valid."""
    import re

    import jax
    import jax.numpy as jnp
    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.models.transformer import decode_attention_arm
    from distributedmnist_tpu.servesvc.kv_cache import (PagedKVCache,
                                                        cache_shapes,
                                                        stored_head_dim)

    d, heads, plen, steps, slots = max(model["model_dim"] // 8, 32), 4, 40, 8, 4
    seq = 64
    mdl = get_model(ModelConfig(**{
        **model, "model_dim": d, "num_heads": heads, "num_layers": 2,
        "seq_len": seq, "q_latent_dim": d // 2, "kv_latent_dim": 128,
        "qk_nope_dim": 24, "qk_rope_dim": 8, "v_head_dim": 16,
        "rope_theta": 25.6e6, "ffn_dim": 2 * d, "routed_experts": 16,
        "held_experts": 8, "experts_per_token": 4, "shared_experts": 1,
        "expert_ffn_dim": d // 2, "routed_scaling": 2.5, "dense_layers": 1,
        "sandwich_norm": True, "norm_eps": 1e-5}))
    params = mdl.init(jax.random.PRNGKey(SEED + 2))
    dtype = jnp.dtype(model["compute_dtype"])
    layers, one, widths = mdl.decode_cache_shape
    blocks = slots * seq // block_size + 1
    stored = tuple(
        stored_head_dim(shape, dtype) for shape in cache_shapes(
            layers, blocks, block_size, one, widths))
    toks = np.zeros((1, seq), np.int32)
    toks[0, :plen] = np.random.default_rng(SEED + 2).integers(
        0, model["vocab_size"], plen)
    logits, cs, krs = jax.jit(mdl.decode_prefill)(params,
                                                  jnp.asarray(toks[:, :plen]))
    first = int(jnp.argmax(logits[0, plen - 1]))
    vec = lambda v: jnp.zeros((slots,), jnp.int32).at[1].set(v)  # noqa: E731

    def decoded(kernel, tokens=None):
        """``steps`` steps under ``kernel`` on a cache of its own, greedy
        or fed ``tokens``: the logits rows, the cache, the compiled
        step's text, whether every expert id was valid."""
        cache = PagedKVCache(layers, blocks, block_size, one, stored,
                             seq // block_size, dtype=dtype)
        table = cache.alloc_sequence(plen + steps)
        cache.write_prompt(table, cs[:, 0], krs[:, 0], plen)
        tables = np.zeros((slots, seq // block_size), np.int32)
        tables[1] = table
        step = jax.jit(lambda *a: mdl.decode_step(
            *a, block_size=block_size, attention_kernel=kernel,
            return_routing=True), donate_argnums=(3, 4))
        tok, rows, fed, valid, text = first, [], [], True, None
        for i, pos in enumerate(range(plen, plen + steps)):
            tok = tok if tokens is None else tokens[i]
            fed.append(tok)
            args = (params, vec(tok), vec(pos), cache.k, cache.v,
                    jnp.asarray(tables), vec(pos + 1))
            if text is None:
                text = step.lower(*args).compile().as_text()
            out, cache.k, cache.v, ids = step(*args)
            rows.append(out[1])
            ids = np.sort(np.asarray(ids[:, 1]), axis=-1)
            valid = valid and bool(ids.min() >= 0 and ids.max() < 16
                                   and (ids[:, 1:] != ids[:, :-1]).all())
            tok = int(jnp.argmax(out[1]))
        return jnp.stack(rows), cache, fed, valid, text

    rows, cache, fed, valid, text = decoded("paged")
    toks[0, plen:plen + steps] = fed
    arm = decode_attention_arm("auto", cache.k.shape, cache.v.shape)
    calls = len(re.findall(r"%paged_latent_decode[.\d]* = [^\n]*"
                           r"custom_call_target=\"tpu_custom_call\"", text))
    want = jax.jit(mdl.apply)(params, jnp.asarray(toks[:, :plen + steps]))
    err = _max_err(rows, want[0, plen:plen + steps])
    _require(np.isfinite(np.asarray(rows)).all() and err <= tol and valid,
             f"latent decode: absorbed against expanded {err}, expert ids "
             f"valid: {valid}")
    rows_d, cache_d, _, valid_d, text_d = decoded("dense", fed)
    err_d = _max_err(rows, rows_d)
    # (the gather's scatter sends an idle slot's row to the null block)
    same = all(bool(jnp.array_equal(a[0, 1:], b[0, 1:])) for a, b in
               ((cache.k, cache_d.k), (cache.v, cache_d.v)))
    _require(err_d <= tol and same and valid_d
             and "paged_latent_decode" not in text_d,
             f"latent decode: the kernel against the gather {err_d}, layer "
             f"0's rows equal: {same}")
    return {"absorbed_vs_expanded": round(err, 5),
            "paged_vs_dense": round(err_d, 5), "layer0_rows_equal": same,
            "auto_arm": arm, "paged_calls": calls, "steps": steps,
            "cache_arrays": [list(cache.k.shape), list(cache.v.shape)],
            "cache_row_widths_stored": list(stored)}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _gate() -> dict:
    """Refuse anything but a TPU before a single thing is built."""
    import importlib.metadata
    import os

    import jax
    import jaxlib

    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform="
              f"{d0.platform!r} ({d0.device_kind}, {len(devs)} device(s); "
              f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
              file=sys.stderr)
        sys.exit(2)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {"device": device,
            "versions": {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                         "python": sys.version.split()[0]},
            "env": {k: os.environ.get(k) for k in (
                "JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS",
                "TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS")}}


def main() -> None:
    try:
        import distributedmnist_tpu  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the program is not here ({e}); run from the "
              "root of a checkout", file=sys.stderr)
        sys.exit(3)
    gate = _gate()
    print(json.dumps({"phase": "gate", **gate}), flush=True)

    from distributedmnist_tpu.core.compile_cache import (
        cache_stats, enable_persistent_cache)
    enable_persistent_cache()
    meter = _CompileMeter()
    n_dev = gate["device"]["count"]
    results: dict[str, dict] = {}

    def run(name: str, fn, **kw) -> dict:
        before, t0 = cache_stats(), time.time()
        meter.take()
        got = fn(**kw)
        after = cache_stats()
        got = {"phase": name, "ok": True,
               "seconds": round(time.time() - t0, 2), **meter.take(),
               "cache": {"hits": after["hits"] - before["hits"],
                         "misses": after["misses"] - before["misses"]},
               "device_kind": gate["device"]["kind"], **got}
        print(json.dumps(got), flush=True)
        results[name] = got
        return got

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
        workdir = Path(td)
        train = run("train", phase_train, workdir=workdir,
                    model=FULL_MODEL, per_device_batch=16, steps=20)
        # compiled, not interpreted: every layer's flash forward AND
        # backward must be a Mosaic custom call in the step itself
        layers = FULL_MODEL["num_layers"]
        calls = train["mosaic_calls"]
        _require(calls["forward"] >= layers and calls["backward"] >= layers,
                 f"train step has Mosaic calls {calls}, want >= {layers} "
                 "forward and backward (kernel interpreted?)")
        run("cnn_quorum", phase_cnn_quorum, workdir=workdir,
            per_device_batch=4096, steps=5)
        serve = run("serve", phase_serve,
                    train_dir=Path(train["train_dir"]), workdir=workdir,
                    prompt_lens=list(SERVE_PROMPT_LENS),
                    max_new_tokens=SERVE_NEW_TOKENS, max_prompt_len=128)
        _require(len(serve["arms"]["dense"]["prefill_buckets"]) > 1,
                 "only one prefill bucket compiled")
        for kernel, arm in serve["arms"].items():
            _require(arm["prefill_mosaic_calls"]["total"] >= layers,
                     f"{kernel} prefill is not on the flash kernel: {arm}")
        paged_calls = serve["arms"]["paged"]["step_mosaic_calls"]["total"]
        _require(paged_calls >= layers
                 and serve["arms"]["dense"]["step_mosaic_calls"]["total"]
                 == 0, f"paged step has {paged_calls} Mosaic calls: "
                       f"{serve['arms']}")
        kern = run("kernels", phase_kernels, model=FULL_MODEL, batch=2)
        _require(kern["flash_mosaic_calls"]["forward"] >= 1
                 and kern["flash_mosaic_calls"]["backward"] >= 1
                 and kern["paged_mosaic_calls"]["total"] >= 1,
                 f"a kernel ran interpreted: {kern}")
        latent = kern["latent_decode"]
        _require(latent["auto_arm"] == "paged" and latent["paged_calls"] == 2,
                 f"the latent step is not on its paged kernel: {latent}")

    print(json.dumps({
        "phase": "summary", "versions": gate["versions"],
        "phases": {n: {"seconds": r["seconds"],
                       "compile_s": r["compile_s"]}
                   for n, r in results.items()},
        "compile_cache": cache_stats(),
        "first_loss": train["first_loss"], "last_loss": train["last_loss"],
        "token_agreement": serve["token_agreement"],
        "data_parallel_devices": n_dev,
        "claim": None}), flush=True)
    # the result, last: these two keys and no others
    print(json.dumps({"ok": True, "device": gate["device"]}), flush=True)


if __name__ == "__main__":
    main()
