#!/usr/bin/env python
"""Benchmark entry point — prints ONE self-contained JSON line on
stdout, LAST:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "cases": [<every additional case record>]}

The top-level metric is the headline: steady-state training throughput
(images/sec) of the flagship MNIST CNN under sync-replica SGD
semantics on whatever devices are visible (one TPU chip under the
driver; the virtual CPU mesh works too). ``vs_baseline`` ratchets
against the round-1 number recorded in BASELINE.json.published — a
regression shows up as < 1.0, not as a silent 1.0.

``cases`` carries the rest, so the artifact is verifiable from the one
stdout line alone (VERDICT weak #2: the old layout printed the
headline first and cases on stderr, and the driver's last-bytes
capture lost the headline entirely):
  * transformer+flash-attention train step, model TFLOP/s
  * quorum / cdf aggregation-discipline overhead vs plain sync,
    median-gated over interleaved repeats (SURVEY §7: timing capture
    must not cost scaling efficiency)
  * native C++ prefetch loader vs the pure-python batch pipeline

Per-case records still stream to stderr as they complete (progress for
a human following the run); stdout is reserved for the final artifact.

The reference publishes no numbers (README.md:1 is bare — SURVEY §6);
the baseline is this repo's own round-1 measurement.
"""

import json
import statistics
import sys
import time

import jax
import numpy as np
from jax import lax


def _drain(metrics) -> None:
    # A scalar fetch; on the v5e it agrees with jax.block_until_ready
    # (289.9 vs 289.4 ms per 50-step CNN chunk, chip run of PR 21).
    float(jax.tree.leaves(metrics)[0])


def _case(record: dict) -> None:
    print(json.dumps(record), file=sys.stderr)


def _published(key: str):
    """A ratchet anchor from BASELINE.json.published — anchored to this
    file, not the cwd (a cwd-relative read would silently turn the
    ratchet back into a constant 1.0)."""
    try:
        from pathlib import Path
        with open(Path(__file__).parent / "BASELINE.json") as f:
            return json.load(f).get("published", {}).get(key)
    except (OSError, json.JSONDecodeError):
        return None


def _vs(value: float, anchor, what: str):
    """Ratchet ratio, or None (plus a loud stderr note) when the anchor
    is missing — a corrupted BASELINE.json must not silently turn the
    ratchet back into a constant 1.0."""
    if not anchor:
        print(f"# WARNING: no published anchor for {what}; "
              "vs_baseline unavailable", file=sys.stderr)
        return None
    return round(value / anchor, 3)


def _env_stamp() -> dict:
    """Where this artifact was actually measured — every record
    carries the platform identity, so a slow capture can be told from
    a capture on another backend."""
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "num_devices": len(jax.devices()),
            "jax_version": jax.__version__}


class _ChunkTimer:
    """Persistent jitted runner for an ON-DEVICE ``lax.scan`` of
    ``chunk_len`` training steps: compile + warm ONCE, then
    :meth:`measure` any number of times.

    With one host dispatch per step an artifact cannot separate device
    throughput from host dispatch and fetch latency. Scanning the step
    on-device makes the timed region one XLA program per chunk: the
    per-dispatch cost amortizes over ``chunk_len`` steps, and the
    per-chunk spread (reported as a histogram) shows contention
    instead of hiding it. ≙ the steady-
    state throughput the reference reports from in-run step timing
    (src/distributed_train.py:365-371).

    Persistence is what makes interleaved-repeat gates affordable
    (VERDICT weak #1): re-measuring a mode costs only its timed chunks,
    not a recompile, so sync/quorum/cdf can alternate on the same chip
    and drift lands on every mode equally.
    """

    def __init__(self, step_fn, state, gbatch, chunk_len: int):
        def chunk(st, batch):
            def body(carry, _):
                new_state, metrics = step_fn(carry, batch)
                return new_state, metrics["loss"]
            final, losses = lax.scan(body, st, None, length=chunk_len)
            return final, losses[-1]

        self.chunk_len = chunk_len
        self._gbatch = gbatch
        self._run = jax.jit(chunk, donate_argnums=0)
        t0 = time.perf_counter()
        state, loss = self._run(state, gbatch)
        float(loss)  # drain (see _drain)
        self.compile_s = time.perf_counter() - t0
        # One untimed warm chunk: the first post-compile dispatch pays
        # a host-side ramp (measured 4-14 ms/step of pure jitter at
        # the flash shape — two runs of identical code differed only
        # there). Steady-state device throughput is the quantity every
        # case reports; the warm chunk is excluded from the timed
        # window uniformly, and per_step_ms_by_chunk shows the spread.
        state, loss = self._run(state, gbatch)
        float(loss)
        self.state = state

    def measure(self, n_chunks: int) -> list[float]:
        """Per-chunk wall seconds for ``n_chunks`` timed chunks.

        Dispatch every chunk before fetching any: the device queue runs
        the chunks back-to-back while each fetch overlaps the next
        chunk's compute, so exactly ONE fetch latency lands in the
        timed window instead of one per chunk.
        """
        losses = []
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            self.state, loss = self._run(self.state, self._gbatch)
            losses.append(loss)
        times, prev = [], t0
        for loss in losses:
            float(loss)  # returns when that chunk has drained
            now = time.perf_counter()
            times.append(now - prev)
            prev = now
        return times


def _scan_chunks(step_fn, state, gbatch, chunk_len: int, n_chunks: int):
    """One-shot compile → warm → time ``n_chunks`` chunks.

    Returns (chunk_seconds list, compile_seconds, final_state).
    """
    timer = _ChunkTimer(step_fn, state, gbatch, chunk_len)
    times = timer.measure(n_chunks)
    return times, timer.compile_s, timer.state


def _build(cfg_dict: dict, topo=None):
    from distributedmnist_tpu.core.config import (ExperimentConfig,
                                                  effective_model_config)
    from distributedmnist_tpu.core.mesh import make_topology
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.parallel.api import (build_train_step,
                                                   init_train_state)
    from distributedmnist_tpu.train.lr_schedule import (
        constant, warmup_polynomial_decay)

    cfg = ExperimentConfig.from_dict(cfg_dict)
    topo = topo or make_topology()
    # same resolutions the Trainer applies: precision.compute_dtype
    # through the shared helper, and the configured schedule — a case
    # whose recipe names warmup/poly must actually MEASURE it
    model = get_model(effective_model_config(cfg))
    if cfg.optim.schedule == "polynomial":
        schedule = warmup_polynomial_decay(
            cfg.optim.initial_learning_rate, cfg.optim.warmup_steps,
            cfg.optim.decay_total_steps or cfg.train.max_steps,
            cfg.optim.end_learning_rate, cfg.optim.poly_power)
    else:
        schedule = constant(8e-4)  # throughput cases: fixed, decay-free
    state = topo.device_put_replicated(init_train_state(model, cfg))
    step_fn = build_train_step(model, cfg, topo, schedule)
    return cfg, topo, model, state, step_fn


def bench_cnn_sync() -> dict:
    """Headline: flagship CNN, plain sync mode. The timed region is an
    on-device scan (one dispatch per chunk of steps) so the number is
    device throughput, not host round-trip pacing."""
    from distributedmnist_tpu.data.datasets import make_synthetic

    n_dev = len(jax.devices())
    batch = 4096 * max(1, n_dev)
    cfg, topo, model, state, step_fn = _build({
        "data": {"dataset": "synthetic", "batch_size": batch},
        "model": {"compute_dtype": "bfloat16"},
        "sync": {"mode": "sync"},
    })
    ds = make_synthetic(num_train=batch, num_test=256)
    gbatch = topo.device_put_batch(
        {"image": ds.train.images[:batch], "label": ds.train.labels[:batch]})
    chunk_len, n_chunks = 50, 6
    times, compile_s, _ = _scan_chunks(step_fn, state, gbatch,
                                       chunk_len, n_chunks)
    dt = sum(times)
    timed = chunk_len * n_chunks
    images_per_sec = timed * batch / dt
    per_chip = images_per_sec / n_dev
    step_ms = [round(t / chunk_len * 1e3, 3) for t in times]

    vs = _vs(per_chip, _published("images_per_sec_per_chip"),
             "images_per_sec_per_chip")
    print(f"# devices={n_dev} global_batch={batch} steps={timed} "
          f"wall={dt:.3f}s total={images_per_sec:.0f} img/s "
          f"compile={compile_s:.1f}s", file=sys.stderr)
    record = {
        "metric": "mnist_cnn_sync_sgd_images_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "images/sec/chip",
        "vs_baseline": vs,
        "detail": {**_env_stamp(), "compile_s": round(compile_s, 2),
                   "chunk_len": chunk_len,
                   "per_step_ms_by_chunk": step_ms},
    }
    if vs is not None and vs < 0.5:
        record["degraded"] = True  # loud: the chip ran far below the
        # committed ratchet — see detail for platform/contention evidence
    return record


def bench_transformer_flash() -> dict:
    """Transformer with the Pallas flash-attention kernels (fwd+bwd):
    model TFLOP/s per chip — the committed artifact for the kernel
    path's performance claims."""
    n_dev = len(jax.devices())
    d, L, H, S, V = 2048, 4, 16, 1024, 1024
    B = 16 * max(1, n_dev)
    cfg, topo, model, state, step_fn = _build({
        "data": {"dataset": "synthetic_lm", "batch_size": B},
        "model": {"name": "transformer", "model_dim": d, "num_layers": L,
                  "num_heads": H, "seq_len": S, "vocab_size": V,
                  "attention_impl": "flash", "compute_dtype": "bfloat16"},
        "sync": {"mode": "sync"},
    })
    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, (B, S), dtype=np.int32)
    gbatch = topo.device_put_batch({"image": toks, "label": toks.copy()})
    # 50 timed steps: the one fetch latency that necessarily lands in
    # the timed window must stay a small share of it
    chunk_len, n_chunks = 10, 5
    times, compile_s, _ = _scan_chunks(step_fn, state, gbatch,
                                       chunk_len, n_chunks)
    dt = sum(times)
    timed = chunk_len * n_chunks

    # Matmul FLOPs per token, fwd: qkv 6d² + out-proj 2d² + MLP 16d²
    # per layer, plus causal attention 2·(2·S·d)·½ per layer, plus the
    # tied head 2dV. Train step ≈ 3× fwd (bwd ≈ 2× fwd).
    fwd_per_token = L * (24 * d * d + 2 * S * d) + 2 * d * V
    flops = 3 * fwd_per_token * B * S * timed
    tflops = flops / dt / 1e12 / n_dev
    vs = _vs(tflops, _published("transformer_flash_tflops_per_chip"),
             "transformer_flash_tflops_per_chip")
    record = {"metric": "transformer_flash_train_tflops_per_chip",
              "value": round(tflops, 2), "unit": "TFLOP/s/chip",
              "vs_baseline": vs,
              "detail": {"dims": {"d": d, "L": L, "H": H, "S": S, "V": V,
                                  "B": B},
                         "steps_per_sec": round(timed / dt, 3),
                         "tokens_per_sec": round(timed * B * S / dt, 1),
                         "compile_s": round(compile_s, 2),
                         "per_step_ms_by_chunk": [
                             round(t / chunk_len * 1e3, 2) for t in times],
                         **_env_stamp()}}
    if vs is not None and vs < 0.5:
        record["degraded"] = True
    return record


def bench_flash_long_context() -> dict:
    """Long-context case: flash attention at S=8192 on one chip, where
    the attention term (2·S·d per token per layer) rivals the matmul
    FLOPs — the regime ring/Ulysses SP extends across chips. Exercises
    the Pallas kernels' tiling at depth (fwd + bwd), with remat on —
    the long-sequence HBM recipe the framework ships."""
    n_dev = len(jax.devices())
    d, L, H, S, V = 1024, 2, 8, 8192, 1024
    B = 2 * max(1, n_dev)
    cfg, topo, model, state, step_fn = _build({
        "data": {"dataset": "synthetic_lm", "batch_size": B},
        "model": {"name": "transformer", "model_dim": d, "num_layers": L,
                  "num_heads": H, "seq_len": S, "vocab_size": V,
                  "attention_impl": "flash", "remat": True,
                  "compute_dtype": "bfloat16"},
        "sync": {"mode": "sync"},
    })
    rng = np.random.default_rng(0)
    toks = rng.integers(0, V, (B, S), dtype=np.int32)
    gbatch = topo.device_put_batch({"image": toks, "label": toks.copy()})
    chunk_len, n_chunks = 8, 4
    times, compile_s, _ = _scan_chunks(step_fn, state, gbatch,
                                       chunk_len, n_chunks)
    dt = sum(times)
    timed = chunk_len * n_chunks

    # the shipped selective-remat policy (save_attn: attention
    # residuals stay resident, only norms/projections/MLP recompute) —
    # measured at the same shape so the artifact records the policy's
    # win without changing the anchor metric's full-remat definition
    cfg2, topo2, model2, state2, step_fn2 = _build({
        "data": {"dataset": "synthetic_lm", "batch_size": B},
        "model": {"name": "transformer", "model_dim": d, "num_layers": L,
                  "num_heads": H, "seq_len": S, "vocab_size": V,
                  "attention_impl": "flash", "remat": True,
                  "remat_policy": "save_attn",
                  "compute_dtype": "bfloat16"},
        "sync": {"mode": "sync"},
    }, topo)
    gbatch2 = topo2.device_put_batch({"image": toks, "label": toks.copy()})
    times2, _, _ = _scan_chunks(step_fn2, state2, gbatch2, chunk_len, 3)
    tok_full = timed * B * S / dt
    tok_sa = chunk_len * 3 * B * S / sum(times2)

    fwd_per_token = L * (24 * d * d + 2 * S * d) + 2 * d * V
    # remat recomputes each block's forward in the backward: ≈4× fwd
    # of model FLOPs per train step instead of 3× — report the
    # EXECUTED rate (hardware utilization), with the algorithmic 3×
    # rate alongside
    flops_exec = 4 * fwd_per_token * B * S * timed
    tflops = flops_exec / dt / 1e12 / n_dev
    vs = _vs(tflops, _published("flash_long_context_tflops_per_chip"),
             "flash_long_context_tflops_per_chip")
    record = {"metric": "flash_long_context_train_tflops_per_chip",
              "value": round(tflops, 2),
              "unit": "TFLOP/s/chip",
              "vs_baseline": vs,
              "detail": {
                  "dims": {"d": d, "L": L, "H": H, "S": S, "V": V, "B": B},
                  "attention_fraction": round(
                      2 * S / (24 * d + 2 * S + 2 * V / L), 3),
                  "model_tflops_per_chip": round(
                      3 * fwd_per_token * B * S * timed / dt / 1e12
                      / n_dev, 2),
                  "tokens_per_sec": round(tok_full, 1),
                  "save_attn_policy": {
                      "tokens_per_sec": round(tok_sa, 1),
                      "speedup_vs_full_remat": round(tok_sa / tok_full, 3)},
                  "compile_s": round(compile_s, 2),
                  **_env_stamp()}}
    if vs is not None and vs < 0.5:
        record["degraded"] = True
    return record


def bench_mode_overhead() -> list[dict]:
    """Aggregation-discipline tax: quorum and cdf modes vs plain sync
    on the same model/batch. The masks, timing model, rank reduction
    and [n]-vector gathers must stay within a 10% throughput budget
    (SURVEY §7 'timing capture must not cost scaling efficiency').

    The gate is the MEDIAN over ≥3 INTERLEAVED repeats — one
    sync/quorum/cdf rotation per repeat, so shared-chip drift hits all
    modes alike and a single noisy window cannot flip the verdict
    (VERDICT weak #1: round 5's 11.82% "failure" re-measured at -1.84%
    the same day; history 0.14 → 2.95 → 11.82 → -1.84%). All repeats
    land in the artifact. ≙ the stats discipline the reference applies
    to worker step times, tools/benchmark.py:86-111, applied to the
    harness itself.
    """
    from distributedmnist_tpu.data.datasets import make_synthetic

    n_dev = len(jax.devices())
    batch = 1024 * max(1, n_dev)
    ds = make_synthetic(num_train=batch, num_test=256)
    host_batch = {"image": ds.train.images[:batch],
                  "label": ds.train.labels[:batch]}

    k = max(1, n_dev - 1)
    modes = {
        "sync": {"mode": "sync"},
        "quorum": {"mode": "quorum", "num_replicas_to_aggregate": k,
                   "straggler_profile": "lognormal"},
        "cdf": {"mode": "cdf"},
    }
    chunk_len, n_chunks, n_repeats = 20, 2, 3

    timers: dict[str, _ChunkTimer] = {}
    programs: dict[str, dict] = {}
    for name, sync_cfg in modes.items():
        cfg, topo, model, state, step_fn = _build({
            "data": {"dataset": "synthetic", "batch_size": batch},
            "model": {"compute_dtype": "bfloat16"},
            "sync": sync_cfg,
        })
        gbatch = topo.device_put_batch(host_batch)
        try:
            # structural evidence BEFORE the timer donates the state:
            # the lowered per-step program, hashed. The per-worker CDF
            # instrumentation (the [n] step-time vector + contribution
            # flags) is emitted in EVERY mode including sync, and cdf's
            # full-barrier flag is the same constant as sync's — so the
            # cdf program is byte-identical StableHLO to sync's, and
            # any measured "cdf overhead" is capture noise by
            # construction (the r05 11.82% reading; gated since by the
            # interleaved-repeat median below).
            import hashlib
            txt = step_fn.jitted.lower(
                state, gbatch, topo.zeros_measured(),
                step_fn.default_discipline()).as_text()
            programs[name] = {
                "stablehlo_lines": txt.count("\n"),
                "stablehlo_sha256": hashlib.sha256(
                    txt.encode()).hexdigest()[:16]}
        except Exception as e:  # evidence is best-effort, never fatal
            programs[name] = {"error": f"{type(e).__name__}: {e}"}
        timers[name] = _ChunkTimer(step_fn, state, gbatch, chunk_len)

    rates: dict[str, list[float]] = {name: [] for name in modes}
    for _ in range(n_repeats):
        for name, timer in timers.items():  # one rotation per repeat
            dt = sum(timer.measure(n_chunks))
            rates[name].append(chunk_len * n_chunks * batch / dt)

    med = {name: statistics.median(r) for name, r in rates.items()}
    records = []
    for mode in ("quorum", "cdf"):
        by_repeat = [round((s - m) / s * 100, 2)
                     for s, m in zip(rates["sync"], rates[mode])]
        overhead = (med["sync"] - med[mode]) / med["sync"]
        same_program = (programs.get(mode) == programs.get("sync")
                        and "error" not in programs.get(mode, {"error": 1}))
        records.append({
            "metric": f"{mode}_mode_overhead_vs_sync",
            "value": round(overhead * 100, 2), "unit": "percent",
            "within_10pct_budget": bool(overhead < 0.10),
            "detail": {
                "gate": f"median of {n_repeats} interleaved repeats",
                "overhead_pct_by_repeat": by_repeat,
                "sync_img_per_sec_median": round(med["sync"], 1),
                f"{mode}_img_per_sec_median": round(med[mode], 1),
                # compiled-program identity: when this mode's lowered
                # StableHLO hashes equal to sync's, the instrumentation
                # adds literally zero ops and nonzero "overhead"
                # readings are wall-clock capture noise
                "program": programs.get(mode),
                "program_identical_to_sync": same_program,
                "img_per_sec_by_repeat": {
                    "sync": [round(r, 1) for r in rates["sync"]],
                    mode: [round(r, 1) for r in rates[mode]]}}})
    return records


def bench_native_loader() -> dict:
    """Native C++ data path vs pure python, measured at its two real
    jobs: (a) cold idx decode throughput (gunzip + parse — what the C++
    decoder exists for), and (b) steady-state pipeline rate with an
    overlapping consumer (~2 ms of work per batch, the realistic shape:
    prefetch hides batch prep behind device compute; a zero-work drain
    loop would only measure thread handoff against itself)."""
    import tempfile
    from pathlib import Path

    from distributedmnist_tpu.core.config import DataConfig
    from distributedmnist_tpu.data import datasets as dsm
    from distributedmnist_tpu.data.datasets import make_synthetic
    from distributedmnist_tpu.data.pipeline import make_train_iterator

    # (a) decode throughput on an archive-sized idx.gz (60k×28×28)
    ds = make_synthetic(num_train=60000, num_test=256)
    u8 = np.clip(np.round((ds.train.images[..., 0] + 0.5) * 255),
                 0, 255).astype(np.uint8)
    with tempfile.TemporaryDirectory() as td:
        path = Path(td) / "train-images-idx3-ubyte.gz"
        dsm.write_idx_ubyte(path, u8)
        nbytes = u8.nbytes
        decode = {}
        try:
            from distributedmnist_tpu.data.native_loader import read_idx
            t0 = time.perf_counter()
            read_idx(path)
            decode["native_MBps"] = round(nbytes / (time.perf_counter() - t0)
                                          / 1e6, 1)
        except ImportError:
            decode["native_MBps"] = None
        import gzip as _gz
        import struct as _st
        t0 = time.perf_counter()
        with _gz.open(path, "rb") as f:  # the pure-python fallback path
            magic = _st.unpack(">HBB", f.read(4))
            dims = _st.unpack(f">{magic[2]}I", f.read(4 * magic[2]))
            np.frombuffer(f.read(int(np.prod(dims))),
                          dtype=np.uint8).reshape(dims)
        decode["python_MBps"] = round(nbytes / (time.perf_counter() - t0)
                                      / 1e6, 1)

    # (b) pipeline rate under TWO consumer shapes. Construct both
    # iterators DIRECTLY — make_train_iterator's gate would silently
    # hand back the python pipeline for "native" and this case would
    # benchmark python against itself.
    #
    #   * cpu_busy: ≈2 ms of numpy per batch — models CPU-mesh
    #     training, where the consumer's compute owns the host core and
    #     a prefetch thread just fights it for cycles (the measured net
    #     slowdown behind make_train_iterator's CPU-backend gate).
    #   * device_blocked: the TRAIN LOOP's real shape on a TPU host —
    #     per batch a jitted dispatch (cheap), every log-cadence a
    #     scalar fetch that parks the host thread GIL-FREE in PJRT
    #     until the device drains. That parked window is where a
    #     1-core host genuinely has spare cycles for the prefetch
    #     thread — the case that decides the production gate.
    import os

    from distributedmnist_tpu.data.pipeline import BatchIterator

    n_batches, batch, cadence = 120, 4096, 10
    work = np.zeros((256, 256), np.float32)
    dev_w = jax.device_put(np.zeros((128, 128), np.float32))
    dev_step = jax.jit(lambda a: (a @ a).sum())
    float(dev_step(dev_w))  # compile outside the timed region

    def consume_cpu_busy(i, pending):
        del i, pending
        work @ work

    def consume_device_blocked(i, pending):
        pending.append(dev_step(dev_w))   # async dispatch, host returns
        if (i + 1) % cadence == 0:
            float(pending[-1])            # GIL-free park in PJRT
            pending.clear()

    # The native iterator is measured at TWO queue depths: the
    # PRODUCTION default (DataConfig.prefetch_batches = 2 — the depth
    # make_train_iterator's 1-core gate actually governs) and a deep
    # queue (=cadence). Round 4 benched only the deep queue and its
    # 1.07x contradicted the gate's depth-2 measurement; at matched
    # depth the gate and the bench agree (native ~0.90x on this
    # 1-core host — the gate correctly disables it).
    prod_depth = DataConfig().prefetch_batches
    variants = [("python", None), ("native", prod_depth),
                ("native_deep", cadence)]

    rates: dict = {}
    for shape, consume in (("cpu_busy", consume_cpu_busy),
                           ("device_blocked", consume_device_blocked)):
        for label, depth in variants:
            it = BatchIterator(ds.train, batch, seed=0)
            if depth is not None:
                try:
                    from distributedmnist_tpu.data.native_loader import (
                        NativePrefetcher)
                except ImportError as e:  # no C++ toolchain: still report
                    rates[f"{shape}_{label}"] = None
                    rates["native_error"] = f"{type(e).__name__}: {e}"
                    continue
                it = NativePrefetcher(it, depth=depth)
            next(it)  # spin-up cost out of the timed window
            pending: list = []
            t0 = time.perf_counter()
            for i in range(n_batches):
                next(it)
                consume(i, pending)
            rates[f"{shape}_{label}"] = n_batches / (time.perf_counter() - t0)
            if hasattr(it, "close"):
                it.close()

    def ratio(shape: str, label: str = "native"):
        n, p = rates.get(f"{shape}_{label}"), rates.get(f"{shape}_python")
        return round(n / p, 2) if n and p else rates.get("native_error")

    prod_ratio = ratio("device_blocked")
    native = rates.get("device_blocked_native")
    return ({"metric": "native_loader_overlapped_batches_per_sec",
           "value": round(native, 1) if native else None,
           "unit": "batches/sec",
           "detail": {"prefetch_depth_production": prod_depth,
                      "pipeline_speedup_vs_python": prod_ratio,
                      "pipeline_speedup_deep_queue": ratio(
                          "device_blocked", "native_deep"),
                      "cpu_busy_speedup_vs_python": ratio("cpu_busy"),
                      "gate_decision_matches_bench": (
                          None if not isinstance(prod_ratio, float)
                          else bool((prod_ratio < 1.0)
                                    == ((os.cpu_count() or 1) < 2))),
                      "rates_batches_per_sec": {
                          k: round(v, 1) for k, v in rates.items()
                          if isinstance(v, float)},
                      "batch": batch, "fetch_cadence": cadence,
                      "host_cpu_count": os.cpu_count(),
                      "backend": jax.default_backend(),
                      "idx_decode": decode,
                      "idx_decode_production_path": "python (default: parity "
                      "within noise, no native-build dependency; native "
                      "reader kept for C-ABI tests)"}})


def bench_weight_update_sharding() -> dict:
    """ZeRO-1 cross-replica sharded weight update (arXiv:2004.13336,
    `parallel.shard_weight_update`) vs the replicated update, on the
    flagship CNN with momentum: per-chip optimizer-state bytes (metered
    from the live state's shard shapes) and the weight-update wall time
    (the isolated aggregation+update program,
    parallel.api.build_weight_update_step — model compute would drown
    the signal). Gates: opt-state bytes ≤ (1/n_replica + ε) of
    replicated, and the sharded update's interleaved-repeat median no
    slower than replicated beyond 10%."""
    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.core.mesh import make_topology
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.parallel.api import (
        build_weight_update_step, init_train_state, state_partition_specs)
    from distributedmnist_tpu.train.lr_schedule import constant
    import jax.numpy as jnp

    topo = make_topology()
    n = topo.num_replicas
    if n <= 1:
        # zero1_plan_for no-ops on a 1-replica mesh: both arms would
        # run the identical replicated update and the gate would pass
        # VACUOUSLY — report skipped instead of a hollow green (CI runs
        # this case under a forced 8-device mesh, tier1.yml)
        return {"metric": "weight_update_sharding", "value": None,
                "unit": "per-chip opt-state bytes, sharded/replicated",
                "passes_gate": None,
                "skipped": ("single-replica mesh — the sharding claims "
                            "need n_replica > 1 (force a multi-device "
                            "mesh, e.g. XLA_FLAGS=--xla_force_host_"
                            "platform_device_count=8)"),
                "detail": _env_stamp()}

    def build(shard: bool):
        cfg = ExperimentConfig.from_dict({
            "optim": {"momentum": 0.9},
            "model": {"compute_dtype": "float32"},
            "parallel": {"shard_weight_update": shard},
        })
        model = get_model(cfg.model)
        state = topo.device_put_state(
            init_train_state(model, cfg, topo),
            state_partition_specs(model, cfg, topo))
        upd = build_weight_update_step(model, cfg, topo, constant(1e-3))
        grads = topo.device_put_replicated(
            jax.tree.map(lambda p: np.full(p.shape, 1e-4, np.float32)
                         if hasattr(p, "shape") else p,
                         jax.device_get(state.params)))

        def step_fn(st, g):
            new = upd(st, g)
            # the fetched scalar depends on the update so the timed
            # drain covers the whole program
            return new, {"loss": new.updates_applied.astype(jnp.float32)}
        return state, grads, step_fn

    def opt_state_bytes_per_chip(state) -> int:
        total = 0
        for leaf in jax.tree.leaves(state.momentum):
            shard = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard)) * leaf.dtype.itemsize
        return total

    chunk_len, n_chunks, n_repeats = 20, 2, 3
    timers, bytes_per_chip = {}, {}
    for name, shard in (("replicated", False), ("sharded", True)):
        state, grads, step_fn = build(shard)
        bytes_per_chip[name] = opt_state_bytes_per_chip(state)
        timers[name] = _ChunkTimer(step_fn, state, grads, chunk_len)

    rates: dict[str, list[float]] = {name: [] for name in timers}
    for _ in range(n_repeats):  # interleaved: drift lands on both arms
        for name, timer in timers.items():
            dt = sum(timer.measure(n_chunks))
            rates[name].append(chunk_len * n_chunks / dt)

    med = {name: statistics.median(r) for name, r in rates.items()}
    eps = 0.02  # covers flat-layout padding + any sub-floor fallback leaves
    bytes_ratio = bytes_per_chip["sharded"] / bytes_per_chip["replicated"]
    bytes_ok = bytes_ratio <= 1.0 / n + eps
    # updates/sec, higher better; sharded may be FASTER (1/n update
    # FLOPs) — the gate only forbids it being >10% slower
    time_ratio = med["replicated"] / med["sharded"]  # sharded_time/replicated
    time_ok = time_ratio <= 1.10
    return {
        "metric": "weight_update_sharding",
        "value": round(bytes_ratio, 4),
        "unit": "per-chip opt-state bytes, sharded/replicated",
        "passes_gate": bool(bytes_ok and time_ok),
        "detail": {
            "gate": (f"bytes ≤ 1/{n}+{eps} of replicated AND median "
                     f"update time within +10% over {n_repeats} "
                     "interleaved repeats"),
            "n_replicas": n,
            "opt_state_bytes_per_chip": bytes_per_chip,
            "bytes_gate_ok": bool(bytes_ok),
            "update_time_ratio_sharded_vs_replicated": round(time_ratio, 3),
            "time_gate_ok": bool(time_ok),
            "updates_per_sec_median": {k: round(v, 2)
                                       for k, v in med.items()},
            "updates_per_sec_by_repeat": {
                k: [round(r, 2) for r in v] for k, v in rates.items()},
            **_env_stamp()}}


def bench_zero1_overlap() -> dict:
    """Bucketed ZeRO-1 comm overlap (ISSUE 12, arXiv:1810.11112):
    monolithic (comm_buckets=1) vs bucketed (comm_buckets=4) FULL train
    step on the flagship CNN with momentum, interleaved-repeat medians.
    Reports ``overlap_ratio`` = bucketed/monolithic median step time
    (< 1.0 = the regrouped collectives overlapped compute). Gate,
    backend-dependent (the weak_scaling precedent):

      * accelerators — bucketed ≤ 1.0× monolithic: real overlap
        hardware must never lose to the monolithic discipline.
      * CPU mesh — bucketed ≤ 1.05×: the virtual devices' collectives
        serialize on the host, so the claim is PARITY within the
        measured interleaved-repeat noise (readings straddle 1.0 by
        ±2-3% run to run — the r05 cdf lesson; README documents
        "leave buckets at 1 on CPU meshes").

    The lowered StableHLO of both arms is hashed as structural
    evidence (the PR 10 cdf precedent, inverted): the programs
    genuinely differ — bucketed carries fewer, larger collectives —
    so the gate measures a real regrouping, and bitwise-equal
    numerics are pinned separately in tests/test_zero1.py."""
    from distributedmnist_tpu.data.datasets import make_synthetic

    n_dev = len(jax.devices())
    if n_dev <= 1:
        return {"metric": "zero1_overlap", "value": None,
                "unit": "x (bucketed/monolithic median step time)",
                "passes_gate": None,
                "skipped": ("single-replica mesh — comm bucketing needs "
                            "n_replica > 1 (force a multi-device mesh, "
                            "e.g. XLA_FLAGS=--xla_force_host_platform_"
                            "device_count=8)"),
                "detail": _env_stamp()}

    # CI-affordable sizes: the gate is a RATIO of comm disciplines on
    # the same step, not a throughput anchor
    batch = 128 * n_dev
    ds = make_synthetic(num_train=batch, num_test=64)
    host_batch = {"image": ds.train.images[:batch],
                  "label": ds.train.labels[:batch]}
    arms = {"monolithic": 1, "bucketed": 4}
    # back-to-back dispatched steps, NOT the _ChunkTimer scan: XLA's
    # while-loop + collective-scheduling passes make a scanned zero1
    # step pathologically slow to compile on this CPU mesh (measured
    # ~6 min for a 5-step scan vs ~4 s for the step itself). A python
    # dispatch loop drained once per chunk keeps the device queue
    # saturated, which is all a same-host ratio needs.
    chunk_len, n_pairs = 5, 6

    import hashlib

    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.core.mesh import make_topology
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.parallel.api import (
        build_train_step, init_train_state, state_partition_specs)
    from distributedmnist_tpu.train.lr_schedule import constant

    topo = make_topology()
    timers: dict = {}  # arm name -> measure(n_steps) -> wall seconds
    programs: dict[str, dict] = {}
    for name, buckets in arms.items():
        # init WITH the topology: the ZeRO-1 plan shapes the momentum
        # (and state specs) — bench._build's topo-less init would hand
        # the sharded step a replicated-layout state
        cfg = ExperimentConfig.from_dict({
            "data": {"dataset": "synthetic", "batch_size": batch},
            "model": {"compute_dtype": "float32"},
            "optim": {"momentum": 0.9},
            "parallel": {"shard_weight_update": True,
                         "comm_buckets": buckets},
            "sync": {"mode": "sync"},
        })
        model = get_model(cfg.model)
        state = topo.device_put_state(
            init_train_state(model, cfg, topo),
            state_partition_specs(model, cfg, topo))
        step_fn = build_train_step(model, cfg, topo, constant(8e-4))
        gbatch = topo.device_put_batch(host_batch)
        try:
            txt = step_fn.jitted.lower(
                state, gbatch, topo.zeros_measured(),
                step_fn.default_discipline()).as_text()
            programs[name] = {
                "stablehlo_lines": txt.count("\n"),
                "stablehlo_sha256": hashlib.sha256(
                    txt.encode()).hexdigest()[:16]}
        except Exception as e:
            programs[name] = {"error": f"{type(e).__name__}: {e}"}
        # compile + one warm step, then a dispatch-loop runner
        st, m = step_fn(state, gbatch)
        _drain(m)
        holder = {"state": st}

        def measure(n_steps, holder=holder, step_fn=step_fn,
                    gbatch=gbatch):
            st = holder["state"]
            t0 = time.perf_counter()
            for _ in range(n_steps):
                st, m = step_fn(st, gbatch)
            _drain(m)  # the queue ran the steps back-to-back
            holder["state"] = st
            return time.perf_counter() - t0

        timers[name] = measure

    # chunk-level interleave: each PAIR times the two arms back-to-back
    # (seconds apart, not a whole arm-sweep apart) and contributes one
    # bucketed/monolithic ratio — box-level drift over the run cancels
    # within pairs instead of landing on whichever arm ran later (the
    # failure mode arm-granularity interleaving measured here: ±5%
    # repeat drift flipping a ~1.0 ratio)
    rates: dict[str, list[float]] = {name: [] for name in arms}
    pair_ratios: list[float] = []
    for _ in range(n_pairs):
        dt_m = timers["monolithic"](chunk_len)
        dt_b = timers["bucketed"](chunk_len)
        rates["monolithic"].append(chunk_len / dt_m)
        rates["bucketed"].append(chunk_len / dt_b)
        pair_ratios.append(dt_b / dt_m)

    med = {name: statistics.median(r) for name, r in rates.items()}
    overlap_ratio = statistics.median(pair_ratios)  # step-time ratio
    cpu = jax.default_backend() == "cpu"
    bound = 1.05 if cpu else 1.0
    passes = overlap_ratio <= bound
    gate = (("cpu mesh: bucketed median step time ≤ 1.05× monolithic — "
             "host-serialized collectives make the honest claim parity "
             "within the measured ±2-3% repeat noise; accelerators gate "
             "≤ 1.0×") if cpu else
            "accelerator: bucketed median step time ≤ 1.0× monolithic")
    return {
        "metric": "zero1_overlap",
        "value": round(overlap_ratio, 3),
        "unit": "x (bucketed/monolithic median step time)",
        "passes_gate": bool(passes),
        "detail": {
            "gate": (f"{gate}; median of {n_pairs} back-to-back "
                     "chunk-pair ratios"),
            "n_replicas": n_dev, "batch": batch,
            "comm_buckets": arms["bucketed"],
            "ratio_by_pair": [round(r, 3) for r in pair_ratios],
            "steps_per_sec_median": {k: round(v, 3)
                                     for k, v in med.items()},
            "steps_per_sec_by_pair": {
                k: [round(r, 3) for r in v] for k, v in rates.items()},
            # structural evidence the regrouping is real: the two arms
            # lower to DIFFERENT programs (unlike the cdf case, where
            # hash identity proved the overhead was capture noise)
            "program": programs,
            "programs_differ": (
                "error" not in programs.get("monolithic", {"error": 1})
                and "error" not in programs.get("bucketed", {"error": 1})
                and programs["monolithic"] != programs["bucketed"]),
            **_env_stamp()},
    }


def bench_save_stall() -> dict:
    """Donation-safe async checkpoint snapshots (ISSUE 12): the step
    loop's per-save stall, sync host fetch (async_snapshot=false) vs
    async snapshot (true), measured from the journaled
    ``save_stall_ms`` of real Trainer runs over interleaved repeats.

    Gate, backend-dependent (the weak_scaling precedent — the claim is
    about OUR save path, not the host):

      * accelerators — async ≤ 0.5× the sync median: the sync fetch is
        a blocking D2H transfer of the whole state, exactly what the
        async device-side copy removes from the loop.
      * CPU client — ``device_get`` is ZERO-COPY host views here (PJRT
        copy-on-donate covers donation safety), so the sync fetch is
        already nearly free and residual step-drain noise (shared by
        both arms) swamps the 0.5× contrast (measured: medians within
        ~10% either direction). The gated claim is that the async
        machinery adds NO stall: async ≤ 1.0× sync + 1 ms.

    Artifacts stay bitwise identical either way (pinned in
    tests/test_async_checkpoint.py)."""
    import shutil
    import tempfile
    from pathlib import Path

    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.obsv.report import load_jsonl
    from distributedmnist_tpu.train.loop import Trainer

    workdir = Path(tempfile.mkdtemp(prefix="dmt_save_stall_"))
    n_repeats = 3
    stalls: dict[str, list[float]] = {"sync_fetch": [], "async_snapshot": []}
    try:
        def one_run(tag: str, async_snapshot: bool, rep: int) -> list[float]:
            d = workdir / f"{tag}_{rep}"
            cfg = ExperimentConfig.from_dict({
                "data": {"dataset": "synthetic", "batch_size": 64,
                         "use_native_pipeline": False},
                "model": {"compute_dtype": "float32"},
                "optim": {"momentum": 0.9},
                "parallel": {"shard_weight_update": True},
                # log cadence == save cadence: the flush preceding each
                # save drains the in-flight step, so the journaled stall
                # isolates the SAVE machinery (host fetch + canonical
                # conversion vs snapshot dispatch) from residual step
                # execution, which both arms share
                "train": {"max_steps": 8, "log_every_steps": 2,
                          "save_interval_steps": 2,
                          "save_results_period": 0,
                          "train_dir": str(d),
                          "async_checkpoint": True,
                          "async_snapshot": async_snapshot}})
            Trainer(cfg).run()
            return [r["save_stall_ms"]
                    for r in load_jsonl(d / "train_log.jsonl", "save")]

        for rep in range(n_repeats):  # interleaved
            stalls["sync_fetch"] += one_run("sync", False, rep)
            stalls["async_snapshot"] += one_run("async", True, rep)

        med = {k: statistics.median(v) for k, v in stalls.items()}
        ratio = med["async_snapshot"] / med["sync_fetch"]
        cpu = jax.default_backend() == "cpu"
        if cpu:
            passes = (med["async_snapshot"]
                      <= med["sync_fetch"] * 1.0 + 1.0)
            gate = ("cpu client: async-snapshot median save_stall_ms ≤ "
                    "1.0× sync-fetch + 1 ms (zero-copy device_get makes "
                    "the sync fetch ~free here; the gate holds the async "
                    "path to adding no stall — the 0.5× D2H claim gates "
                    "on accelerators)")
        else:
            passes = ratio <= 0.5
            gate = ("accelerator: async-snapshot median save_stall_ms ≤ "
                    "0.5× sync-fetch (the blocking D2H fetch leaves the "
                    "step loop)")
        return {
            "metric": "save_stall",
            "value": round(ratio, 3),
            "unit": "x (async-snapshot/sync-fetch median save stall)",
            "passes_gate": bool(passes),
            "detail": {
                "gate": (f"{gate}; {n_repeats} interleaved Trainer runs, "
                         "stalls read from the journaled save events"),
                "save_stall_ms_median": {k: round(v, 3)
                                         for k, v in med.items()},
                "save_stall_ms_all": {k: [round(x, 3) for x in v]
                                      for k, v in stalls.items()},
                "saves_per_arm": len(stalls["sync_fetch"]),
                **_env_stamp()},
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_checkpoint_durability() -> dict:
    """Storage-shim fsync tax (ISSUE 20): the full atomic-save
    protocol (tmp write → rename → digest sidecar → pointer) under
    ``train.durability=full`` (fsync data + sidecars + directory
    entries) vs ``none`` (the default: rename-atomic, no flush),
    identical state bytes, medians over INTERLEAVED repeats — one
    none/full rotation per repeat, so page-cache and disk drift land
    on both policies alike (the r05 cdf lesson).

    Gate, backend-dependent (the weak_scaling precedent — the claim
    is about OUR save machinery, not the runner's disk):

      * accelerators — full ≤ 3× none median: production NVMe fsyncs
        are sub-ms, so a larger multiple means the shim is flushing
        per-write instead of per-artifact.
      * CPU runners (CI) — full ≤ 10× none + 100 ms absolute: shared
        CI disks put 1-50 ms on every fsync and the none-arm median
        is small enough that the ratio alone is noise; the absolute
        term keeps a save well under any cadence budget while still
        catching per-byte-flush pathologies.

    Crash-consistency itself is not gated here — that is
    tests/test_crash_consistency.py's job; this case prices the knob
    so the README's policy table carries a measured number."""
    import shutil
    import tempfile
    from pathlib import Path

    from distributedmnist_tpu.train import checkpoint as ckpt
    from distributedmnist_tpu.train import storage

    rng = np.random.default_rng(0)
    # flagship-CNN-sized state: ~7 MB of params + momentum
    state = {"params": {f"layer{i}": rng.standard_normal(
                 (256, 256)).astype(np.float32) for i in range(12)},
             "momentum": {f"layer{i}": rng.standard_normal(
                 (256, 256)).astype(np.float32) for i in range(12)},
             "step": np.int32(0)}
    state_bytes = sum(a.nbytes for a in
                      [*state["params"].values(),
                       *state["momentum"].values()])
    workdir = Path(tempfile.mkdtemp(prefix="dmt_durability_"))
    n_repeats, saves_per_repeat = 5, 3
    wall_ms: dict[str, list[float]] = {"none": [], "full": []}
    try:
        step = 0
        for _ in range(n_repeats):  # interleaved: one rotation each
            for policy in ("none", "full"):
                d = workdir / policy
                d.mkdir(exist_ok=True)
                storage.set_durability(policy)
                for _ in range(saves_per_repeat):
                    step += 1
                    t0 = time.perf_counter()
                    ckpt.save_checkpoint(d, state, step)
                    wall_ms[policy].append(
                        (time.perf_counter() - t0) * 1e3)
    finally:
        storage.set_durability("none")
        shutil.rmtree(workdir, ignore_errors=True)

    med = {k: statistics.median(v) for k, v in wall_ms.items()}
    ratio = med["full"] / med["none"]
    extra_ms = med["full"] - med["none"]
    cpu = jax.default_backend() == "cpu"
    if cpu:
        passes = med["full"] <= med["none"] * 10.0 + 100.0
        gate = ("cpu runner: durability=full median save wall ≤ 10× "
                "none + 100 ms (shared CI disks make the bare ratio "
                "noise; the absolute term still catches per-byte "
                "flushing)")
    else:
        passes = ratio <= 3.0
        gate = ("accelerator host: durability=full median save wall "
                "≤ 3× none (NVMe fsyncs are sub-ms — a larger "
                "multiple means the shim flushes per-write, not "
                "per-artifact)")
    return {
        "metric": "checkpoint_durability_overhead",
        "value": round(ratio, 3),
        "unit": "x (durability=full/none median save wall)",
        "passes_gate": bool(passes),
        "detail": {
            "gate": (f"{gate}; medians over {n_repeats} interleaved "
                     f"repeats × {saves_per_repeat} saves"),
            "state_bytes": state_bytes,
            "save_wall_ms_median": {k: round(v, 3)
                                    for k, v in med.items()},
            "fsync_extra_ms_median": round(extra_ms, 3),
            "save_wall_ms_all": {k: [round(x, 2) for x in v]
                                 for k, v in wall_ms.items()},
            "fsync_scope": {"none": "rename-atomic only",
                            "full": "data + sidecar + pointer + "
                                    "directory entries"},
            **_env_stamp()},
    }


def bench_weak_scaling() -> dict:
    """Weak-scaling efficiency of the large-batch playbook (ROADMAP
    item 4, arXiv:1909.09756): images/sec at 1→2→4→8 devices with a
    CONSTANT per-device batch, flagship CNN under the full recipe —
    LAMB + linear-warmup/polynomial-decay schedule + bf16 compute with
    fp32 master weights. Each device count runs on a sub-mesh of the
    same visible devices (the forced mesh in CI), timed with the same
    on-device scan discipline as the headline.

    Gate (at 8 devices), backend-dependent because the claim is about
    OUR step program, not the host:

      * accelerators — the honest weak-scaling floor: img/s at n ≥
        0.6 × n × img/s at 1 (DP allreduce efficiency).
      * CPU backend — n virtual devices on a few cores SERIALIZE at
        every collective rendezvous (capacity ~min(n, cores) is still
        optimistic: measured 24 img/s at n=2 on a 2-core host vs 25 at
        n=1), so the gated claim is that multiplying virtual devices
        does not CRATER total throughput: img/s at 8 ≥ 0.5 × img/s at
        1 (measured 0.72× on this 2-core box). A step program whose
        per-device or collective cost grew superlinearly would fail
        it; a slow runner alone cannot.

    Per-device-count throughput and the raw efficiency curve land in
    the artifact either way."""
    import os

    from distributedmnist_tpu.core.config import MeshConfig
    from distributedmnist_tpu.core.mesh import make_topology
    from distributedmnist_tpu.data.datasets import make_synthetic

    devs = jax.devices()
    counts = [c for c in (1, 2, 4, 8) if c <= len(devs)]
    cpu = jax.default_backend() == "cpu"
    # CPU arms stay CI-affordable: the ratio gate needs matched
    # per-device work across device counts, not a big absolute batch
    per_dev = 64 if cpu else 2048
    chunk_len, n_chunks = (6, 2) if cpu else (50, 4)
    # bf16 is the MXU's native mode but SOFTWARE-emulated in CPU convs
    # (measured ~40× slower at this shape) — the CPU artifact measures
    # the scaling shape in f32 compute, accelerators run the full-bf16
    # recipe; the fp32-master machinery (bf16 param view, f32 update)
    # is exercised either way
    compute = "float32" if cpu else "bfloat16"
    recipe = {
        "optim": {"name": "lamb", "initial_learning_rate": 4e-3,
                  "schedule": "polynomial", "warmup_steps": 20,
                  "decay_total_steps": 2000, "weight_decay": 1e-4},
        "precision": {"param_dtype": "bfloat16", "master_weights": True,
                      "compute_dtype": compute},
    }

    ds = make_synthetic(num_train=per_dev * max(counts), num_test=64)
    rates: dict[int, float] = {}
    compile_s: dict[int, float] = {}
    for n in counts:
        topo = make_topology(MeshConfig(num_replicas=n), devices=devs[:n])
        batch = per_dev * n
        cfg, topo, model, state, step_fn = _build({
            "data": {"dataset": "synthetic", "batch_size": batch},
            "model": {"compute_dtype": compute},
            "sync": {"mode": "sync"},
            **recipe,
        }, topo)
        gbatch = topo.device_put_batch(
            {"image": ds.train.images[:batch],
             "label": ds.train.labels[:batch]})
        times, comp, _ = _scan_chunks(step_fn, state, gbatch,
                                      chunk_len, n_chunks)
        rates[n] = chunk_len * n_chunks * batch / sum(times)
        compile_s[n] = round(comp, 2)
        print(f"# weak_scaling n={n} batch={batch} "
              f"{rates[n]:.0f} img/s", file=sys.stderr)

    n_max = counts[-1]
    eff_curve = {n: round(rates[n] / (n * rates[1]), 3) for n in counts}
    cores = os.cpu_count() or 1
    if cpu:
        floor = 0.5
        gate_metric = rates[n_max] / rates[1]  # no-crater ratio
        gate_desc = (f"cpu backend: img/s at {n_max} virtual devices ≥ "
                     f"{floor}× img/s at 1 (collectives serialize on "
                     f"{cores} core(s); the gate catches superlinear "
                     "per-device/collective cost, not host speed)")
    else:
        floor = 0.6
        gate_metric = eff_curve[n_max]  # true weak-scaling efficiency
        gate_desc = (f"accelerator: img/s at {n_max} devices ≥ {floor}× "
                     f"{n_max}× img/s at 1 (DP allreduce efficiency)")
    gated = n_max >= 8
    passes = bool(gate_metric >= floor) if gated else None
    record = {
        "metric": "weak_scaling_efficiency",
        "value": round(eff_curve[n_max], 3),
        "unit": f"x (img/s at {n_max} dev ÷ {n_max}× img/s at 1 dev)",
        "passes_gate": passes,
        "detail": {
            "gate": gate_desc,
            "gate_metric": round(gate_metric, 3),
            "recipe": recipe,
            "per_device_batch": per_dev,
            "images_per_sec_by_devices": {str(n): round(r, 1)
                                          for n, r in rates.items()},
            "efficiency_by_devices": {str(n): e
                                      for n, e in eff_curve.items()},
            "throughput_ratio_nmax_vs_1": round(rates[n_max] / rates[1], 3),
            "host_cpu_count": cores,
            "compile_s_by_devices": {str(n): c
                                     for n, c in compile_s.items()},
            "compile_s": compile_s[n_max],
            **_env_stamp()},
    }
    if not gated:
        record["skipped_gate"] = (
            f"only {n_max} device(s) visible — the efficiency floor "
            "gates at 8 (force a mesh, e.g. XLA_FLAGS=--xla_force_"
            "host_platform_device_count=8)")
    return record


def bench_restart_latency() -> dict:
    """Restart-latency fast path (ROADMAP item 5), measured end-to-end
    on the local process cluster with REAL ``launch train`` worker
    processes. Three recovery disciplines, same payload (the chaos
    train payload's shape: 2-device simulated mesh, momentum + ZeRO-1):

      * **cold** — the payload turns the persistent compile cache off
        (``compile.persistent_cache=false``): process boot + full XLA
        compile + first step.
      * **warm** — spawn against the primed compile cache (the one
        directory core/compile_cache.py resolves): boot + cache
        deserialize instead of compile.
      * **standby** — promote a parked, precompiled spare: no boot, no
        compile, just adopt-logdir + resume.

    The measured quantity is spawn(or promotion)→first-moved-step — the
    exact recovery leg every supervisor restart and chaos trial pays.
    Gates (vs the cold median): warm ≤ 0.6×, standby ≤ 0.3×. The warm
    gate SKIPS honestly when the platform persisted no cache entries
    during the prime run (nothing to be warm from)."""
    import shutil
    import tempfile
    from pathlib import Path

    from distributedmnist_tpu.launch.cluster import (LocalClusterConfig,
                                                     LocalProcessCluster)
    from distributedmnist_tpu.launch.exec import CommandExecutor, RetryPolicy

    workdir = tempfile.mkdtemp(prefix="dmt_restart_bench_")
    payload = (
        "python -m distributedmnist_tpu.launch train "
        "train.train_dir=. data.dataset=synthetic data.batch_size=32 "
        "data.synthetic_train_size=256 data.synthetic_test_size=64 "
        "model.compute_dtype=float32 mesh.simulate_devices=2 "
        "optim.momentum=0.9 parallel.shard_weight_update=true "
        "train.max_steps=500 train.log_every_steps=1 "
        "train.save_interval_steps=5 train.async_checkpoint=false "
        "train.save_results_period=0")

    def first_step_after(cluster, anchor: float, timeout_s: float = 300.0,
                         keep_log: bool = False) -> float:
        """Seconds from ``anchor`` to the worker's first step record
        stamped at/after it (the artifact timestamps, not poll
        granularity)."""
        from distributedmnist_tpu.obsv.report import load_jsonl
        log = Path(cluster.cfg.worker_dir(0)) / "train_log.jsonl"
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            for rec in load_jsonl(log, "step"):
                if (isinstance(rec.get("time"), (int, float))
                        and rec["time"] >= anchor):
                    return rec["time"] - anchor
            time.sleep(0.25)
        raise RuntimeError(
            f"no step record within {timeout_s:.0f}s of the (re)spawn "
            f"({'existing' if keep_log else 'fresh'} log: {log})")

    def spawn_and_time(cluster) -> float:
        """One cold-ish sample: fresh worker dir, spawn, time to the
        first moved step, then stop the worker."""
        cluster.kill_all()
        wdir = Path(cluster.cfg.worker_dir(0))
        if wdir.exists():
            shutil.rmtree(wdir)
        wdir.mkdir(parents=True)
        cluster.run_train()
        anchor = cluster.status()["workers"][0]["spawned_at"]
        try:
            return first_step_after(cluster, anchor)
        finally:
            cluster.kill_all()

    clusters: list[LocalProcessCluster] = []

    def make_cluster(name: str, cache: bool) -> LocalProcessCluster:
        cfg = LocalClusterConfig(
            name=name, num_workers=1, workdir=workdir,
            train_command=(payload if cache else
                           payload + " compile.persistent_cache=false"))
        ex = CommandExecutor(journal=cfg.root / "command_journal.jsonl",
                             retry=RetryPolicy(max_attempts=1))
        c = LocalProcessCluster(cfg, ex)
        c.create()
        clusters.append(c)
        return c

    def compile_events(cluster) -> list[dict]:
        from distributedmnist_tpu.obsv.report import load_jsonl
        return load_jsonl(Path(cluster.cfg.worker_dir(0))
                          / "train_log.jsonl", "compile")

    detail: dict = {"payload": payload, **_env_stamp()}
    try:
        # --- cold arm: no cache at all --------------------------------
        cold_cluster = make_cluster("cold", cache=False)
        cold = [spawn_and_time(cold_cluster) for _ in range(3)]
        cold_cluster.delete()
        cold_median = statistics.median(cold)

        # --- warm arm: prime the shared cache, then measure -----------
        from distributedmnist_tpu.core.compile_cache import (
            cache_stats, resolve_cache_dir)
        warm_cluster = make_cluster("warm", cache=True)
        cache_dir = resolve_cache_dir()
        prime = spawn_and_time(warm_cluster)
        primed = cache_stats(cache_dir)
        warm: list[float] = []
        warm_skipped = None
        if primed["entries"] == 0:
            warm_skipped = ("platform persisted no compile-cache "
                            "entries — nothing to be warm from")
        else:
            warm = [spawn_and_time(warm_cluster) for _ in range(2)]
        # dir-level stats only: hit/miss counters are PER PROCESS (they
        # move in the workers, not in this bench process — reporting
        # ours here would upload meaningless zeros); the per-worker
        # hit evidence is worker_compile_events' persistent_cache
        # block (new_entries == 0 on a warm boot)
        cstats = cache_stats(cache_dir)
        detail["compile_cache"] = {
            "dir": cstats["dir"], "entries": cstats["entries"],
            "bytes": cstats["bytes"],
            "entries_after_prime": primed["entries"]}
        detail["worker_compile_events"] = compile_events(warm_cluster)[-1:]

        # --- standby arm: promote parked precompiled spares -----------
        standby: list[float] = []
        for _ in range(2):
            warm_cluster.ensure_standbys(1)
            deadline = time.time() + 300.0
            while time.time() < deadline:
                st = warm_cluster.status()
                if any(sb["ready"] for sb in st.get("standbys", [])):
                    break
                time.sleep(0.5)
            else:
                raise RuntimeError("standby never reached ready")
            warm_cluster.kill_all(worker="0")
            if not warm_cluster.promote_standby(0):
                raise RuntimeError("promote_standby found no ready spare")
            anchor = warm_cluster.status()["workers"][0]["spawned_at"]
            standby.append(first_step_after(warm_cluster, anchor,
                                            keep_log=True))
            warm_cluster.kill_all()
        warm_cluster.delete()

        warm_median = statistics.median(warm) if warm else None
        standby_median = statistics.median(standby)
        warm_ratio = (round(warm_median / cold_median, 3)
                      if warm_median is not None else None)
        standby_ratio = round(standby_median / cold_median, 3)
        warm_ok = None if warm_skipped else bool(warm_ratio <= 0.6)
        standby_ok = bool(standby_ratio <= 0.3)
        detail.update({
            "gate": "warm ≤ 0.6× cold median, standby ≤ 0.3× cold median",
            "cold_s": [round(t, 2) for t in cold],
            "cold_median_s": round(cold_median, 2),
            "prime_s": round(prime, 2),
            "warm_s": [round(t, 2) for t in warm],
            "warm_median_s": (round(warm_median, 2)
                              if warm_median is not None else None),
            "standby_s": [round(t, 2) for t in standby],
            "standby_median_s": round(standby_median, 2),
            "warm_ratio_vs_cold": warm_ratio,
            "standby_ratio_vs_cold": standby_ratio,
            "warm_gate_ok": warm_ok,
            "standby_gate_ok": standby_ok,
        })
        if warm_skipped:
            detail["warm_skipped"] = warm_skipped
        passes = standby_ok and (warm_ok is not False)
        return {"metric": "restart_latency",
                "value": warm_ratio if warm_ratio is not None
                else standby_ratio,
                "unit": "x (restart first-moved-step vs cold median)",
                "passes_gate": bool(passes),
                "detail": detail}
    finally:
        # an error mid-arm must not leak detached worker/standby
        # processes (start_new_session survives us; a parked standby
        # whose activation dir vanished would spin forever) — kill
        # every cluster this run created before removing its workdir
        for c in clusters:
            try:
                c.kill_all()
                c.exec.close()
            except Exception:
                pass
        shutil.rmtree(workdir, ignore_errors=True)


def bench_serving_latency() -> dict:
    """Online serving tier (ROADMAP item 3), gated end-to-end in one
    process: a real ServingReplica (socket, admission queue, bucketed
    batching) under a closed-loop load sweep at fixed offered load,
    with checkpoint publishes landing MID-SWEEP so the zero-drop
    hot-swap is measured, not assumed.

    Two sweeps, same replica, same offered load (closed loop,
    ``concurrency`` in-flight):

      * **steady** — no publishes: the p50/p99 baseline.
      * **swap** — a publisher thread pushes a fresh checkpoint every
        ~300 ms: every request still gets a terminal outcome
        (dropped == 0), at least one hot-swap actually happened
        (≥2 distinct model steps served), and p99 stays bounded
        relative to steady (≤ max(5×, +250 ms) — the swap may cost a
        batch boundary, never a stall).

    The reject rate at this load is reported (expected 0 under the
    default queue depth — admission control only sheds when the queue
    is actually full)."""
    import shutil
    import tempfile
    import threading
    from pathlib import Path

    from distributedmnist_tpu.core.config import ExperimentConfig, ServeConfig
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.servesvc.loadgen import make_input_fn, run_load
    from distributedmnist_tpu.servesvc.server import ServingReplica
    from distributedmnist_tpu.train.loop import Trainer

    workdir = Path(tempfile.mkdtemp(prefix="dmt_serving_bench_"))
    staging = workdir / "staging"
    publish = workdir / "publish"
    publish.mkdir()
    concurrency, n_requests = 4, 200

    def publish_step(step: int) -> None:
        """Atomically publish one staged checkpoint into the serve
        dir: artifact + digest sidecar first, pointer last (the same
        write order the trainer uses)."""
        name = f"ckpt-{step:08d}.msgpack"
        shutil.copy2(staging / name, publish / name)
        shutil.copy2(staging / (name + ".sha256"),
                     publish / (name + ".sha256"))
        tmp = publish / "checkpoint.json.tmp"
        tmp.write_text(json.dumps({"latest_step": step,
                                   "latest_path": name,
                                   "written_at": time.time()}))
        tmp.replace(publish / "checkpoint.json")

    replica = None
    try:
        # stage a stream of checkpoints (one short deterministic run)
        cfg = ExperimentConfig().override({
            "data.dataset": "synthetic", "data.batch_size": 32,
            "data.synthetic_train_size": 256,
            "data.synthetic_test_size": 64,
            "model.compute_dtype": "float32", "train.max_steps": 60,
            "train.train_dir": str(staging), "train.log_every_steps": 20,
            "train.save_interval_steps": 10,
            "train.async_checkpoint": False,
            "train.save_results_period": 0})
        Trainer(cfg).run()
        staged = sorted(int(p.name[5:13])
                        for p in staging.glob("ckpt-*.msgpack"))
        publish_step(staged[0])

        replica = ServingReplica(
            publish, serve_dir=workdir / "replica",
            scfg=ServeConfig(poll_secs=0.1), cfg=cfg)
        replica.start()
        client = ServeClient([("127.0.0.1", replica.bound_port)],
                             deadline_s=5.0)
        make_input = make_input_fn(
            list(replica.model.input_shape),
            str(np.dtype(replica.model.input_dtype)))

        # warm every bucket shape the sweep can hit (compile once):
        # sequential singles hit bucket 1, the concurrent burst hits
        # the 2/4 buckets the closed loop gathers
        run_load(client, 8, 1, make_input)
        run_load(client, 8 * concurrency, concurrency, make_input)

        steady = run_load(client, n_requests, concurrency, make_input,
                          journal_path=workdir / "loadgen_steady.jsonl")

        stop_pub = threading.Event()

        def publisher() -> None:
            for step in staged[1:]:
                if stop_pub.is_set():
                    return
                time.sleep(0.3)
                publish_step(step)

        pub_thread = threading.Thread(target=publisher, daemon=True)
        swaps_before = replica.swaps
        pub_thread.start()
        swap = run_load(client, n_requests, concurrency, make_input,
                        journal_path=workdir / "loadgen_swap.jsonl")
        stop_pub.set()
        pub_thread.join(timeout=10)
        swaps_during = replica.swaps - swaps_before

        p99_base = steady["latency_ms"]["p99"]
        p99_swap = swap["latency_ms"]["p99"]
        p99_bound = max(5.0 * p99_base, p99_base + 250.0)
        no_drop = (swap["dropped"] == 0 and swap["errors"] == 0
                   and steady["dropped"] == 0)
        swapped = (swaps_during >= 1
                   and len(swap["model_steps_served"]) >= 2)
        p99_ok = p99_swap <= p99_bound
        passes = bool(no_drop and swapped and p99_ok)
        return {
            "metric": "serving_latency",
            "value": p99_swap, "unit": "ms p99 across hot-swaps",
            "passes_gate": passes,
            "detail": {
                "gate": ("zero dropped/errored requests AND >=1 mid-"
                         "sweep hot-swap (>=2 model steps served) AND "
                         "p99_swap <= max(5x, +250ms) of steady p99"),
                "offered_load": {"concurrency": concurrency,
                                 "requests_per_sweep": n_requests},
                "steady": steady, "swap_sweep": swap,
                "swaps_during_sweep": swaps_during,
                "p99_steady_ms": p99_base, "p99_swap_ms": p99_swap,
                "p99_bound_ms": round(p99_bound, 3),
                "no_drop_ok": bool(no_drop),
                "swap_happened_ok": bool(swapped),
                "p99_gate_ok": bool(p99_ok),
                "reject_rate": swap["reject_rate"],
                **_env_stamp()}}
    finally:
        if replica is not None:
            try:
                replica.stop()
            except Exception:
                pass
        shutil.rmtree(workdir, ignore_errors=True)


def bench_degraded_network() -> dict:
    """Serving under transport faults (ISSUE 19): one real replica
    behind the netchaos proxy, gated on **exactly-once outcomes** —
    every request reaches one terminal, duplicates are answered from
    the dedup cache (never re-executed), and the tail stays bounded.

    Two arms, a FRESH replica each (loadgen request ids restart at 0
    per sweep — reusing a replica would let arm 1's dedup cache answer
    arm 2's requests and fake the clean baseline):

      * **clean** — direct connection: the p50/p99 baseline.
      * **degraded** — the same sweep through a ChaosProxy scripted
        with added latency+jitter and a one-shot connection reset that
        cuts the first response mid-wire.  The reset lands AFTER the
        replica computed and cached the outcome (the protocol caches
        before sending), so the client's retry must produce a dedup
        hit, not a second execution.

    Gates: zero drops and zero errors in both arms; the degraded sweep
    retried >= 1 request and the replica served >= 1 dedup hit; no
    request id has more than one ``respond`` execution record in the
    replica's journal (unlicensed duplicate = fail); degraded p99 <=
    max(5x, +500 ms) of clean p99 (retry backoff may cost a round
    trip, never a stall)."""
    import shutil
    import tempfile
    from pathlib import Path

    from distributedmnist_tpu.core.config import ExperimentConfig, ServeConfig
    from distributedmnist_tpu.launch.netchaos import ChaosProxy
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.servesvc.loadgen import make_input_fn, run_load
    from distributedmnist_tpu.servesvc.server import ServingReplica
    from distributedmnist_tpu.train.loop import Trainer

    workdir = Path(tempfile.mkdtemp(prefix="dmt_netchaos_bench_"))
    staging = workdir / "staging"
    publish = workdir / "publish"
    publish.mkdir()
    concurrency, n_requests = 4, 150

    cfg = ExperimentConfig().override({
        "data.dataset": "synthetic", "data.batch_size": 32,
        "data.synthetic_train_size": 256,
        "data.synthetic_test_size": 64,
        "model.compute_dtype": "float32", "train.max_steps": 20,
        "train.train_dir": str(staging), "train.log_every_steps": 20,
        "train.save_interval_steps": 10,
        "train.async_checkpoint": False,
        "train.save_results_period": 0})
    Trainer(cfg).run()
    name = sorted(staging.glob("ckpt-*.msgpack"))[-1].name
    for suffix in ("", ".sha256"):
        shutil.copy2(staging / (name + suffix), publish / (name + suffix))
    (publish / "checkpoint.json").write_text(json.dumps(
        {"latest_step": int(name[5:13]), "latest_path": name,
         "written_at": time.time()}))

    def run_arm(tag: str, proxy_scripts: list[dict] | None):
        """Boot a fresh replica, warm it DIRECT (string request ids —
        never colliding with the sweep's integer ids), then sweep
        through the proxy (or direct for the clean arm)."""
        replica = ServingReplica(
            publish, serve_dir=workdir / f"replica_{tag}",
            scfg=ServeConfig(poll_secs=0.1), cfg=cfg)
        proxy = None
        try:
            replica.start()
            direct = ("127.0.0.1", replica.bound_port)
            make_input = make_input_fn(
                list(replica.model.input_shape),
                str(np.dtype(replica.model.input_dtype)))
            warm = ServeClient([direct], deadline_s=5.0)
            for i in range(2 * concurrency):
                warm.request(make_input(i), request_id=f"warm-{tag}-{i}")
            ep = direct
            if proxy_scripts is not None:
                proxy = ChaosProxy(direct, proxy_scripts, worker=1,
                                   seed=0)
                ep = ("127.0.0.1", proxy.start())
            client = ServeClient([ep], deadline_s=5.0)
            sweep = run_load(
                client, n_requests, concurrency, make_input,
                journal_path=workdir / f"loadgen_{tag}.jsonl")
            sweep["dedup_hits"] = replica.dedup_hits
            # unlicensed duplicate = one id EXECUTED twice; a journal
            # with two respond records for one id means the dedup
            # cache failed and the model ran the request again
            per_id: dict = {}
            log = workdir / f"replica_{tag}" / "serve_log.jsonl"
            for line in log.read_text().splitlines():
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("action") == "respond":
                    rid = rec.get("id")
                    per_id[rid] = per_id.get(rid, 0) + 1
            sweep["double_executions"] = sum(
                n - 1 for n in per_id.values() if n > 1)
            return sweep
        finally:
            if proxy is not None:
                proxy.stop()
            try:
                replica.stop()
            except Exception:
                pass

    try:
        clean = run_arm("clean", None)
        degraded = run_arm("degraded", [
            {"kind": "latency", "delay_ms": 8.0, "jitter_ms": 4.0},
            # any classifier response is >100 bytes: the one-shot cut
            # always lands mid-response, after the outcome was cached
            {"kind": "reset", "after_bytes": 100}])

        p99_clean = clean["latency_ms"]["p99"]
        p99_deg = degraded["latency_ms"]["p99"]
        p99_bound = max(5.0 * p99_clean, p99_clean + 500.0)
        no_drop = (clean["dropped"] == 0 and clean["errors"] == 0
                   and degraded["dropped"] == 0
                   and degraded["errors"] == 0)
        dedup_ok = (degraded["retried"] >= 1
                    and degraded["dedup_hits"] >= 1)
        no_dupes = (clean["double_executions"] == 0
                    and degraded["double_executions"] == 0)
        p99_ok = p99_deg <= p99_bound
        passes = bool(no_drop and dedup_ok and no_dupes and p99_ok)
        return {
            "metric": "degraded_network",
            "value": p99_deg, "unit": "ms p99 behind chaos proxy",
            "passes_gate": passes,
            "detail": {
                "gate": ("zero dropped/errored requests in both arms "
                         "AND >=1 retry absorbed as a dedup hit AND "
                         "zero double executions AND p99_degraded <= "
                         "max(5x, +500ms) of clean p99"),
                "offered_load": {"concurrency": concurrency,
                                 "requests_per_sweep": n_requests},
                "clean": clean, "degraded": degraded,
                "p99_clean_ms": p99_clean, "p99_degraded_ms": p99_deg,
                "p99_bound_ms": round(p99_bound, 3),
                "no_drop_ok": bool(no_drop),
                "dedup_absorbed_retry_ok": bool(dedup_ok),
                "no_double_execution_ok": bool(no_dupes),
                "p99_gate_ok": bool(p99_ok),
                **_env_stamp()}}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def bench_quantized_serving() -> dict:
    """Quantized serving path (ROADMAP item 5): the int8 sidecar tier
    vs the fp32 path on real ServingReplicas under the closed-loop
    load sweep, PAIRED with the accuracy-parity oracle so speed can
    never silently buy wrongness.

    Three gated claims:

      * **parity (every backend)** — quantized top-1 on the full eval
        split within ``quant.parity_epsilon`` of full precision, and
        top-1 agreement ≥ 1 − epsilon. The oracle runs the same
        dequantize-in-graph predict the replica serves.
      * **resident weight bytes (every backend)** — the int8 tier's
        on-device weight bytes ≤ 0.35× fp32 (per-channel int8 + f32
        scales + f32 1-D leaves lands ~0.25×; the bound catches a
        quantizer that silently stopped quantizing).
      * **throughput/p99 (accelerators only)** — int8 throughput-per-
        replica ≥ fp32 and p99 ≤ fp32 over interleaved sweep pairs.
        On a CPU backend int8 matmuls are software-emulated (the
        dequant multiply is pure extra work with no int8 compute
        units behind it), so the perf half honest-skips — the
        weak_scaling CPU-arm precedent — and the sweeps are reported,
        not gated.
    """
    import shutil
    import tempfile
    from pathlib import Path

    from distributedmnist_tpu.core.config import ExperimentConfig, ServeConfig
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.servesvc.loadgen import make_input_fn, run_load
    from distributedmnist_tpu.servesvc.server import ServingReplica
    from distributedmnist_tpu.train import checkpoint as ckpt
    from distributedmnist_tpu.train.loop import Trainer

    workdir = Path(tempfile.mkdtemp(prefix="dmt_quant_bench_"))
    staging = workdir / "staging"
    publish = workdir / "publish"
    publish.mkdir()
    concurrency, n_requests, n_pairs = 4, 120, 2
    epsilon = 0.02

    def publish_step(step: int) -> None:
        names = [f"ckpt-{step:08d}.msgpack", f"ckpt-{step:08d}.quant.msgpack"]
        for name in names:
            for sfx in ("", ".sha256"):
                shutil.copy2(staging / (name + sfx), publish / (name + sfx))
        tmp = publish / "checkpoint.json.tmp"
        tmp.write_text(json.dumps({"latest_step": step,
                                   "latest_path": names[0],
                                   "written_at": time.time()}))
        tmp.replace(publish / "checkpoint.json")

    replicas = {}
    try:
        cfg = ExperimentConfig().override({
            "data.dataset": "synthetic", "data.batch_size": 64,
            "data.synthetic_train_size": 1024,
            "data.synthetic_test_size": 512,
            "data.use_native_pipeline": False,
            "model.compute_dtype": "float32", "train.max_steps": 30,
            "train.train_dir": str(staging), "train.log_every_steps": 10,
            "train.save_interval_steps": 10,
            "train.async_checkpoint": False,
            "train.save_results_period": 0,
            "quant.publish_tiers": "int8",
            "quant.parity_epsilon": epsilon})
        trainer = Trainer(cfg)
        trainer.run()
        step = max(int(p.name[5:13]) for p in staging.glob("ckpt-*.msgpack")
                   if not p.name.endswith(".quant.msgpack"))
        publish_step(step)
        meta_side = ckpt.read_quant_sidecar(staging, step)["meta"]

        for tier in ("fp32", "int8"):
            rep = ServingReplica(
                publish, serve_dir=workdir / f"replica_{tier}",
                scfg=ServeConfig(poll_secs=0.1, precision_tier=tier),
                cfg=cfg)
            rep.start()
            replicas[tier] = rep
        clients = {t: ServeClient([("127.0.0.1", r.bound_port)],
                                  deadline_s=5.0)
                   for t, r in replicas.items()}
        meta_probe = {t: {k: (c.meta() or {}).get(k)
                          for k in ("precision_tier", "active_tier",
                                    "tier_source_digest")}
                      for t, c in clients.items()}
        make_input = make_input_fn(
            list(replicas["fp32"].model.input_shape),
            str(np.dtype(replicas["fp32"].model.input_dtype)))

        # warm every bucket shape both arms can hit (compile once)
        for c in clients.values():
            run_load(c, 8, 1, make_input)
            run_load(c, 8 * concurrency, concurrency, make_input)

        # interleaved sweep pairs: box drift cancels within a pair
        sweeps: dict[str, list[dict]] = {"fp32": [], "int8": []}
        for _ in range(n_pairs):
            for tier in ("fp32", "int8"):
                sweeps[tier].append(run_load(
                    clients[tier], n_requests, concurrency, make_input))
        rps = {t: statistics.median(s["throughput_rps"] for s in v)
               for t, v in sweeps.items()}
        p99 = {t: statistics.median(s["latency_ms"]["p99"] for s in v)
               for t, v in sweeps.items()}
        dropped = sum(s["dropped"] + s["errors"]
                      for v in sweeps.values() for s in v)

        # -- the accuracy-parity oracle on the FULL eval split --------
        # the same installed weights + predict fns the replicas serve
        x_eval = trainer.datasets.test.images
        labels = trainer.datasets.test.labels
        probs = {}
        for tier, rep in replicas.items():
            probs[tier] = np.asarray(jax.device_get(
                rep._predict(rep._params, x_eval)))
        from distributedmnist_tpu.quant.ptq import parity_report
        parity = parity_report(probs["fp32"], probs["int8"], labels)
        parity_ok = (parity["top1_tier"] >= parity["top1_ref"] - epsilon
                     and parity["agreement"] >= 1.0 - epsilon)

        # -- resident weight bytes (the memory lever, every backend) --
        pbytes = meta_side["param_bytes"]
        bytes_ratio = pbytes["int8"] / pbytes["fp32"]
        bytes_ok = bytes_ratio <= 0.35

        cpu = jax.default_backend() == "cpu"
        tiers_measured = {t: sorted({tier for s in v
                                     for tier in s.get("tiers_served", [])})
                          for t, v in sweeps.items()}
        served_right_tier = tiers_measured["int8"] == ["int8"]
        if cpu:
            perf_ok = None  # honest skip: no int8 compute units to win on
            perf_note = ("cpu backend software-emulates int8 (the "
                         "dequant multiply is pure extra work) — "
                         "throughput/p99 reported, gated on "
                         "accelerators only; weak_scaling CPU-arm "
                         "precedent")
        else:
            perf_ok = bool(rps["int8"] >= rps["fp32"]
                           and p99["int8"] <= p99["fp32"])
            perf_note = ("accelerator: int8 throughput-per-replica ≥ "
                         "fp32 AND p99 ≤ fp32 (interleaved sweep "
                         "medians)")
        passes = bool(parity_ok and bytes_ok and served_right_tier
                      and dropped == 0 and perf_ok is not False)
        return {
            "metric": "quantized_serving",
            "value": round(rps["int8"] / rps["fp32"], 3),
            "unit": "x (int8/fp32 throughput-per-replica)",
            "passes_gate": passes,
            "detail": {
                "gate": ("parity: int8 top-1 within ±%.3f of fp32 on "
                         "the eval split AND agreement ≥ %.3f; bytes: "
                         "int8 resident weights ≤ 0.35× fp32; perf: %s"
                         % (epsilon, 1 - epsilon, perf_note)),
                "parity": parity, "parity_gate_ok": bool(parity_ok),
                "epsilon": epsilon,
                "param_bytes": pbytes,
                "int8_bytes_ratio": round(bytes_ratio, 4),
                "bytes_gate_ok": bool(bytes_ok),
                "throughput_rps_median": {k: round(v, 2)
                                          for k, v in rps.items()},
                "p99_ms_median": p99,
                "perf_gate_ok": perf_ok,
                "dropped_or_errored": dropped,
                "offered_load": {"concurrency": concurrency,
                                 "requests_per_sweep": n_requests,
                                 "pairs": n_pairs},
                # which tier each sweep ACTUALLY measured (the meta
                # probe + per-response tier records — satellite: a
                # loadgen artifact must say what it swept)
                "tiers_measured": tiers_measured,
                "meta_probe": meta_probe,
                "calibration": meta_side.get("calibration"),
                **_env_stamp()}}
    finally:
        for rep in replicas.values():
            try:
                rep.stop()
            except Exception:
                pass
        shutil.rmtree(workdir, ignore_errors=True)


def _paged_longcontext_arm() -> dict:
    """Paged vs dense decode_step at short and LONG max-context — the
    micro-arm behind the paged kernel's O(actual) vs O(max) claim.

    Both arms hold the ACTUAL context at ~64 tokens; what differs is
    the provisioned table width (4 blocks vs 68 blocks ≙ 1088-token
    max context).  The dense path gathers every table entry — its
    per-token traffic scales with the WIDTH — while the paged kernel
    masks dead entries to the null block and (compiled) skips their
    DMAs, so its cost tracks the live blocks only.

    Parity between the kernels gates on EVERY backend (the interpret-
    mode kernel runs the same index arithmetic as compiled TPU).  The
    speed gates (paged >= ~dense at width-4; paged >= 2x dense at
    width-68) only apply on accelerators: on CPU the Pallas kernel
    runs interpreted — honestly reported as skipped, never faked by
    timing the interpreter.
    """
    import functools

    import jax.numpy as jnp

    from distributedmnist_tpu.core.config import ModelConfig
    from distributedmnist_tpu.models.registry import get_model
    from distributedmnist_tpu.servesvc.kv_cache import PagedKVCache

    cpu = jax.default_backend() == "cpu"
    heads, hd, layers, slots, vocab = 4, 16, 2, 4, 32
    model = get_model(ModelConfig(
        name="transformer", seq_len=1152, model_dim=heads * hd,
        num_heads=heads, num_layers=layers, vocab_size=vocab,
        compute_dtype="float32", attention_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    iters = 2 if cpu else 20
    arms: dict = {}
    parity_ok = True
    speed: dict = {}
    for arm, width in (("short_ctx_64", 4), ("long_ctx_1088", 68)):
        bs, length = 16, 63
        cache = PagedKVCache(
            num_layers=layers, num_blocks=slots * width + 2,
            block_size=bs, num_heads=heads, head_dim=hd,
            max_blocks_per_seq=width)
        tables = np.zeros((slots, width), np.int32)
        for s in range(slots):
            t = cache.alloc_sequence(length + 1)
            tables[s] = t
            toks = jnp.asarray(rng.integers(0, vocab, size=(1, length)),
                               jnp.int32)
            _, ks, vs = model.decode_prefill(params, toks)
            cache.write_prompt(t, ks[:, 0], vs[:, 0], length)
        tables_dev = jnp.asarray(tables)
        tokens = jnp.asarray(rng.integers(0, vocab, size=(slots,)),
                             jnp.int32)
        positions = jnp.full((slots,), length, jnp.int32)
        lengths = jnp.full((slots,), length + 1, jnp.int32)
        out = {}
        ms = {}
        for kern in ("paged", "dense"):
            step = jax.jit(functools.partial(
                model.decode_step, block_size=bs, attention_kernel=kern))
            logits, _, _ = step(params, tokens, positions, cache.k,
                                cache.v, tables_dev, lengths)
            jax.block_until_ready(logits)   # compile outside the clock
            t0 = time.perf_counter()
            for _ in range(iters):
                logits, _, _ = step(params, tokens, positions, cache.k,
                                    cache.v, tables_dev, lengths)
            jax.block_until_ready(logits)
            ms[kern] = (time.perf_counter() - t0) * 1e3 / iters
            out[kern] = np.asarray(logits)
        diff = float(np.max(np.abs(out["paged"] - out["dense"])))
        arm_parity = diff <= 1e-4
        parity_ok = parity_ok and arm_parity
        arms[arm] = {"table_width_blocks": width,
                     "actual_context_tokens": length + 1,
                     "paged_ms_per_step": round(ms["paged"], 3),
                     "dense_ms_per_step": round(ms["dense"], 3),
                     "dense_over_paged": round(ms["dense"] / ms["paged"],
                                               3),
                     "parity_max_abs_diff": diff,
                     "parity_ok": arm_parity}
        speed[arm] = ms
    if cpu:
        speed_gate_ok = None
        speed_note = ("skipped (cpu backend: the pallas kernel runs "
                      "in interpret mode — timing the interpreter "
                      "would fake the claim either way)")
    else:
        short_ok = (speed["short_ctx_64"]["paged"]
                    <= 1.06 * speed["short_ctx_64"]["dense"])
        long_ok = (speed["long_ctx_1088"]["dense"]
                   >= 2.0 * speed["long_ctx_1088"]["paged"])
        speed_gate_ok = bool(short_ok and long_ok)
        speed_note = ("paged >= ~dense at width 4, paged >= 2x dense "
                      "at width 68")
    return {"arms": arms, "parity_ok": bool(parity_ok),
            "speed_gate_ok": speed_gate_ok, "speed_gate": speed_note,
            "iters_per_arm": iters}


def bench_decode_throughput() -> dict:
    """Continuous-batching decode service, gated end-to-end in one
    process: a real DecodeReplica (socket, bounded admission, paged KV
    cache, streaming) under the closed-loop generate loadgen, with
    checkpoint publishes landing MID-SWEEP so the swap-during-
    generation policy is measured, not assumed.

    Two sweeps, same replica, same offered load:

      * **steady** — no publishes: the tokens/s + TTFT baseline.
      * **swap** — a publisher thread pushes fresh checkpoints every
        ~300 ms mid-generation.

    Gated claims (platform-independent — about OUR decode path):

      * zero dropped/errored requests across both sweeps, every
        response actually streamed tokens;
      * continuous batching really refilled: sequences finished >
        decode_slots (slots turned over instead of running one padded
        round);
      * ≥1 hot-swap landed mid-sweep AND the pin policy held — zero
        ``decode_swap`` violations replayed from the replica's own
        journal (no sequence finished on weights it didn't start on);
      * p99 time-to-first-token under swaps bounded relative to steady
        (≤ max(5×, +250 ms) — a swap costs a loop boundary, never a
        stall).

    Absolute tokens/s is REPORTED (the artifact's trajectory metric);
    it gates nowhere on CPU — the decode matmuls here are host-
    serialized, the honest weak_scaling/quantized_serving precedent.

    Two riders ship in the detail: the **long_context** micro-arm
    (paged vs dense decode_step at 4-block and 68-block table widths —
    kernel parity gates on every backend, the speed claims only on
    accelerators where the kernel compiles), and **table_prep** (the
    block-table upload cache's hit accounting vs the measured cost of
    the naive per-step rebuild it replaced).
    """
    import shutil
    import tempfile
    import threading
    from pathlib import Path

    from distributedmnist_tpu.core.config import (DecodeConfig,
                                                  ExperimentConfig,
                                                  ServeConfig)
    from distributedmnist_tpu.obsv.invariants import check_serving
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.servesvc.decode import DecodeReplica
    from distributedmnist_tpu.servesvc.loadgen import (make_prompt_fn,
                                                       run_load)
    from distributedmnist_tpu.train.loop import Trainer

    workdir = Path(tempfile.mkdtemp(prefix="dmt_decode_bench_"))
    staging = workdir / "staging"
    publish = workdir / "publish"
    publish.mkdir()
    concurrency, n_requests = 4, 60

    def publish_step(step: int) -> None:
        name = f"ckpt-{step:08d}.msgpack"
        shutil.copy2(staging / name, publish / name)
        shutil.copy2(staging / (name + ".sha256"),
                     publish / (name + ".sha256"))
        tmp = publish / "checkpoint.json.tmp"
        tmp.write_text(json.dumps({"latest_step": step,
                                   "latest_path": name,
                                   "written_at": time.time()}))
        tmp.replace(publish / "checkpoint.json")

    replica = None
    try:
        cfg = ExperimentConfig().override({
            "data.dataset": "synthetic_lm", "data.batch_size": 32,
            "data.synthetic_train_size": 256,
            "data.synthetic_test_size": 64,
            "data.use_native_pipeline": False,
            "model.name": "transformer", "model.seq_len": 64,
            "model.model_dim": 64, "model.num_heads": 4,
            "model.num_layers": 2, "model.vocab_size": 32,
            "model.compute_dtype": "float32",
            "model.attention_impl": "dense",
            "train.max_steps": 60, "train.train_dir": str(staging),
            "train.log_every_steps": 20,
            "train.save_interval_steps": 10,
            "train.async_checkpoint": False,
            "train.save_results_period": 0})
        Trainer(cfg).run()
        staged = sorted(int(p.name[5:13])
                        for p in staging.glob("ckpt-*.msgpack"))
        publish_step(staged[0])

        dcfg = DecodeConfig(decode_slots=4, block_size=8, num_blocks=64,
                            max_prompt_len=16, max_new_tokens=16)
        replica = DecodeReplica(
            publish, serve_dir=workdir / "replica",
            scfg=ServeConfig(poll_secs=0.1), dcfg=dcfg, cfg=cfg)
        replica.start()
        client = ServeClient([("127.0.0.1", replica.bound_port)],
                             deadline_s=20.0)
        make_prompt = make_prompt_fn(cfg.model.vocab_size,
                                     dcfg.max_prompt_len)

        # warm the compiled shapes before anything is timed: one
        # request per prompt bucket (every pow-2 up to max_prompt_len
        # — prefill compiles per bucket) plus a concurrent burst for
        # the decode step itself
        bucket = 1
        while bucket <= dcfg.max_prompt_len:
            out = client.generate([1] * bucket, max_tokens=2)
            assert out.get("status") == "ok", out
            bucket *= 2
        run_load(client, 2 * concurrency, concurrency, make_prompt,
                 decode=True)

        steady = run_load(client, n_requests, concurrency, make_prompt,
                          journal_path=workdir / "loadgen_steady.jsonl",
                          decode=True)

        stop_pub = threading.Event()

        def publisher() -> None:
            for step in staged[1:]:
                if stop_pub.is_set():
                    return
                time.sleep(0.3)
                publish_step(step)

        pub_thread = threading.Thread(target=publisher, daemon=True)
        swaps_before = replica.swaps
        finished_before = replica.sequences_finished
        pub_thread.start()
        swap = run_load(client, n_requests, concurrency, make_prompt,
                        journal_path=workdir / "loadgen_swap.jsonl",
                        decode=True)
        stop_pub.set()
        pub_thread.join(timeout=10)
        swaps_during = replica.swaps - swaps_before
        finished_during = replica.sequences_finished - finished_before

        # block-table prep accounting (the per-iteration host rebuild
        # used to be paid on EVERY decode step; now it is cached per
        # (version, epoch) and only re-uploaded when composition
        # changes) — counters from the replica that just served, plus
        # a micro-measure of what ONE naive rebuild costs
        table_uploads = replica.table_uploads
        table_reuses = replica.table_upload_reuses
        width = dcfg.max_blocks_per_seq()
        t0 = time.perf_counter()
        reb_iters = 200
        for _ in range(reb_iters):
            t_np = np.zeros((dcfg.decode_slots, width), np.int32)
            jax.block_until_ready(jax.numpy.asarray(t_np))
        naive_rebuild_ms = ((time.perf_counter() - t0) * 1e3
                            / reb_iters)

        # stop BEFORE replaying the journal (flushes + closes it);
        # the shared finally below is a no-op for a stopped replica
        replica.stop()

        # replay the swap-during-generation invariant over the
        # replica's own journal — the policy gate is the checker, not
        # a bespoke assertion
        trial = workdir / "trial"
        (trial / "worker1").mkdir(parents=True)
        shutil.copy2(workdir / "replica" / "serve_log.jsonl",
                     trial / "worker1" / "serve_log.jsonl")
        violations, _, _, decode_applicable = check_serving(
            trial, {"serve_workers": [1]}, [])
        policy_violations = [v.to_dict() for v in violations
                             if v.invariant == "decode_swap"]

        # paged-vs-dense long-context micro-arm (parity gates
        # everywhere; speed gates on accelerators only)
        long_context = _paged_longcontext_arm()

        ttft_base = steady["ttft_ms"]["p99"]
        ttft_swap = swap["ttft_ms"]["p99"]
        ttft_bound = max(5.0 * ttft_base, ttft_base + 250.0)
        no_drop = all(s["dropped"] == 0 and s["errors"] == 0
                      for s in (steady, swap))
        all_streamed = (steady.get("tokens_streamed", 0) > 0
                        and swap.get("tokens_streamed", 0) > 0
                        and steady["responses"] == n_requests
                        and swap["responses"] == n_requests)
        refilled = finished_during > dcfg.decode_slots
        swapped = swaps_during >= 1
        policy_ok = decode_applicable and not policy_violations
        ttft_ok = ttft_swap <= ttft_bound
        paged_ok = (long_context["parity_ok"]
                    and long_context["speed_gate_ok"] is not False)
        passes = bool(no_drop and all_streamed and refilled and swapped
                      and policy_ok and ttft_ok and paged_ok)
        cpu = jax.default_backend() == "cpu"
        return {
            "metric": "decode_throughput",
            "value": swap.get("tokens_per_sec"),
            "unit": "tokens/sec under hot-swaps",
            "passes_gate": passes,
            "detail": {
                "gate": ("zero dropped/errored, every response "
                         "streamed, continuous refill (> slots "
                         "sequences finished mid-sweep), >=1 mid-"
                         "sweep swap with zero decode_swap "
                         "violations, ttft_p99_swap <= max(5x, "
                         "+250ms) steady; absolute tokens/s "
                         + ("reported only (cpu backend: host-"
                            "serialized decode matmuls)" if cpu
                            else "reported (no accelerator anchor "
                                 "yet)")),
                "offered_load": {"concurrency": concurrency,
                                 "requests_per_sweep": n_requests},
                "decode": {"slots": dcfg.decode_slots,
                           "block_size": dcfg.block_size,
                           "num_blocks": dcfg.num_blocks,
                           "max_new_tokens": dcfg.max_new_tokens,
                           "swap_policy": dcfg.swap_policy},
                "steady": steady, "swap_sweep": swap,
                "swaps_during_sweep": swaps_during,
                "sequences_finished_during_sweep": finished_during,
                "ttft_p99_steady_ms": ttft_base,
                "ttft_p99_swap_ms": ttft_swap,
                "ttft_bound_ms": round(ttft_bound, 3),
                "no_drop_ok": bool(no_drop),
                "all_streamed_ok": bool(all_streamed),
                "refill_ok": bool(refilled),
                "swap_happened_ok": bool(swapped),
                "policy_ok": bool(policy_ok),
                "decode_swap_violations": policy_violations,
                "ttft_gate_ok": bool(ttft_ok),
                "paged_kernel_ok": bool(paged_ok),
                "long_context": long_context,
                "table_prep": {
                    "uploads": table_uploads,
                    "reuses": table_reuses,
                    "reuse_ratio": round(
                        table_reuses / max(1, table_uploads
                                           + table_reuses), 4),
                    "naive_rebuild_ms_per_step": round(
                        naive_rebuild_ms, 4)},
                **_env_stamp()}}
    finally:
        # one cleanup path for every exit (training/boot/sweep
        # failures included) — the quantized_serving pattern
        if replica is not None:
            try:
                replica.stop()
            except Exception:
                pass
        shutil.rmtree(workdir, ignore_errors=True)


def bench_tp_serving() -> dict:
    """Tensor-parallel serving groups under fire: two 2-rank TP decode
    replicas (real ``launch serve --tp-ranks 2`` process groups behind
    the unchanged socket contract), a failover client across both, a
    checkpoint publisher pushing hot-swaps mid-sweep, and a SIGKILL of
    one rank of group 1 mid-generation.

    Gated claims:

      * zero dropped/errored requests across both sweeps — the rank
        kill takes its whole group down (die-as-a-unit) and the CLIENT
        still reaches a terminal outcome for every request via
        failover to the surviving group;
      * the killed group's journal chain replays clean through the
        ``serve_group`` invariant (rank_exit → group_down →
        group_restart → group_start) and the restarted group actually
        serves again;
      * ≥1 hot-swap landed on the surviving group mid-sweep, with the
        serving invariants (outcomes/digest/monotone/decode_swap)
        green on replay;
      * follower ranks journaled ``shard_verify`` — the shard-wise
        digest evidence that hot-swap staging under TP verified the
        bytes each rank holds.

    Tokens/s is reported, never gated: on CPU the "TP" mesh is
    virtual devices and collectives are host-serialized.
    """
    import os
    import shutil
    import signal as _signal
    import subprocess
    import tempfile
    import threading
    from pathlib import Path

    from distributedmnist_tpu.core.config import ExperimentConfig
    from distributedmnist_tpu.obsv.invariants import (check_serve_group,
                                                      check_serving)
    from distributedmnist_tpu.servesvc.client import (ServeClient,
                                                      discover_endpoints)
    from distributedmnist_tpu.servesvc.loadgen import (make_prompt_fn,
                                                       run_load)
    from distributedmnist_tpu.train.loop import Trainer

    workdir = Path(tempfile.mkdtemp(prefix="dmt_tp_bench_"))
    staging = workdir / "staging"
    publish = workdir / "publish"
    publish.mkdir()
    trial = workdir / "trial"
    supervisors: list[subprocess.Popen] = []
    concurrency, n_requests = 3, 24

    def publish_step(step: int) -> None:
        name = f"ckpt-{step:08d}.msgpack"
        shutil.copy2(staging / name, publish / name)
        shutil.copy2(staging / (name + ".sha256"),
                     publish / (name + ".sha256"))
        tmp = publish / "checkpoint.json.tmp"
        tmp.write_text(json.dumps({"latest_step": step,
                                   "latest_path": name,
                                   "written_at": time.time()}))
        tmp.replace(publish / "checkpoint.json")

    def wait_for(pred, timeout_s: float, what: str) -> None:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if pred():
                return
            time.sleep(0.25)
        raise RuntimeError(f"timed out after {timeout_s:.0f}s "
                           f"waiting for {what}")

    def group_actions(k: int) -> list:
        p = trial / f"worker{k}" / "group_log.jsonl"
        if not p.exists():
            return []
        return [json.loads(l).get("action")
                for l in p.read_text().splitlines() if l.strip()]

    try:
        cfg = ExperimentConfig().override({
            "data.dataset": "synthetic_lm", "data.batch_size": 32,
            "data.synthetic_train_size": 256,
            "data.synthetic_test_size": 64,
            "data.use_native_pipeline": False,
            "model.name": "transformer", "model.seq_len": 64,
            "model.model_dim": 64, "model.num_heads": 4,
            "model.num_layers": 2, "model.vocab_size": 32,
            "model.compute_dtype": "float32",
            "model.attention_impl": "dense",
            "train.max_steps": 40, "train.train_dir": str(staging),
            "train.log_every_steps": 20,
            "train.save_interval_steps": 10,
            "train.async_checkpoint": False,
            "train.save_results_period": 0})
        Trainer(cfg).run()
        staged = sorted(int(p.name[5:13])
                        for p in staging.glob("ckpt-*.msgpack"))
        publish_step(staged[0])

        for k in (1, 2):
            serve_dir = trial / f"worker{k}"
            serve_dir.mkdir(parents=True, exist_ok=True)
            supervisors.append(subprocess.Popen(
                [sys.executable, "-m", "distributedmnist_tpu.launch",
                 "serve", "--train_dir", str(publish),
                 "--serve-dir", str(serve_dir), "--port", "0",
                 "--poll-secs", "0.2", "--queue-depth", "16",
                 "--decode", "--decode-slots", "4",
                 "--max-new-tokens", "8", "--max-prompt-len", "16",
                 "--tp-ranks", "2"],
                # this process has initialised the ambient backend (the
                # Trainer above) and a chip belongs to one process: the
                # groups run on the CPU platform's virtual devices,
                # which is what this case gates — the die-as-a-unit
                # lifecycle, not a device rate
                env={**os.environ, "JAX_PLATFORMS": "cpu"}))
        wait_for(lambda: len(discover_endpoints(trial)) == 2, 600,
                 "both TP groups' serve.json")

        client = ServeClient(lambda: discover_endpoints(trial),
                             deadline_s=120.0, max_attempts=8)
        make_prompt = make_prompt_fn(cfg.model.vocab_size, 16)
        # warm every prompt bucket on BOTH replicas (round-robin:
        # two requests per bucket) before anything is timed or killed
        bucket = 1
        while bucket <= 16:
            for _ in range(2):
                out = client.generate([1] * bucket, max_tokens=2)
                assert out.get("status") == "ok", out
            bucket *= 2

        steady = run_load(client, n_requests, concurrency, make_prompt,
                          journal_path=workdir / "loadgen_steady.jsonl",
                          decode=True)

        # sweep B: publisher pushes swaps while one rank of group 1 is
        # murdered mid-generation
        stop_pub = threading.Event()

        def publisher() -> None:
            for step in staged[1:]:
                if stop_pub.is_set():
                    return
                time.sleep(0.4)
                publish_step(step)

        kill_info: dict = {}

        def killer() -> None:
            time.sleep(1.0)
            roster = json.loads(
                (trial / "worker1" / "group.json").read_text())
            pid = int(roster["pids"]["1"])     # a non-zero rank
            try:
                os.kill(pid, _signal.SIGKILL)
                kill_info["killed_pid"] = pid
            except OSError as e:
                kill_info["error"] = str(e)

        pub_t = threading.Thread(target=publisher, daemon=True)
        kill_t = threading.Thread(target=killer, daemon=True)
        pub_t.start()
        kill_t.start()
        swap = run_load(client, n_requests, concurrency, make_prompt,
                        journal_path=workdir / "loadgen_swap.jsonl",
                        decode=True)
        stop_pub.set()
        pub_t.join(timeout=10)
        kill_t.join(timeout=10)

        # the murdered group must come back as a UNIT and serve again
        wait_for(lambda: "group_restart" in group_actions(1), 120,
                 "group 1's unit restart in its journal")
        wait_for(lambda: (trial / "worker1" / "serve.json").exists(),
                 600, "restarted group 1 republishing its endpoint")
        ep = json.loads((trial / "worker1" / "serve.json").read_text())
        confirm = ServeClient([(ep["host"], int(ep["port"]))],
                              deadline_s=240.0, max_attempts=2)
        out = confirm.generate([1, 2, 3], max_tokens=2)
        restarted_serves = out.get("status") == "ok"

        # graceful teardown BEFORE replay so every journal is flushed
        for p in supervisors:
            if p.poll() is None:
                p.send_signal(_signal.SIGTERM)
        for p in supervisors:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()

        # ---- replay ----------------------------------------------------
        # the rank kill is a journaled fault: worker 1's server-side
        # admit/terminal mismatch is exempt (its in-flight admissions
        # died with the group); the CLIENT-side zero-drop gate is what
        # proves failover covered them
        fault_records = [{"event": "fault", "action": "kill_worker",
                          "worker": 1, "ts": time.time()}]
        violations, applicable, _, decode_applicable = check_serving(
            trial, {"serve_workers": [1, 2]}, fault_records)
        group_violations, group_applicable = check_serve_group(trial)

        acts = group_actions(1)
        i_exit = acts.index("rank_exit") if "rank_exit" in acts else -1
        chain_ok = (i_exit >= 0
                    and "group_down" in acts[i_exit:]
                    and "group_restart" in acts[i_exit:]
                    and acts.count("group_start") >= 2)
        shard_verified = 0
        for k in (1, 2):
            rlog = trial / f"worker{k}" / "rank1" / "serve_log.jsonl"
            if rlog.exists():
                shard_verified += sum(
                    1 for l in rlog.read_text().splitlines() if l.strip()
                    and json.loads(l).get("action") == "shard_verify")
        swaps = 0
        for k in (1, 2):
            slog = trial / f"worker{k}" / "serve_log.jsonl"
            swaps += sum(
                1 for l in slog.read_text().splitlines() if l.strip()
                and json.loads(l).get("action") == "weight_swap"
                and not json.loads(l).get("initial"))

        no_drop = all(s["dropped"] == 0 and s["errors"] == 0
                      for s in (steady, swap))
        all_responded = (steady["responses"] == n_requests
                         and swap["responses"] == n_requests)
        invariants_ok = (applicable and decode_applicable
                         and group_applicable and not violations
                         and not group_violations)
        passes = bool(no_drop and all_responded and chain_ok
                      and restarted_serves and swaps >= 1
                      and shard_verified >= 1 and invariants_ok
                      and "killed_pid" in kill_info)
        return {
            "metric": "tp_serving",
            "value": swap.get("tokens_per_sec"),
            "unit": "tokens/sec through a rank kill + hot-swaps",
            "passes_gate": passes,
            "detail": {
                "gate": ("zero dropped/errored requests through a "
                         "mid-sweep SIGKILL of one TP rank (group died "
                         "as a unit, client failed over, group "
                         "restarted and served) + >=1 hot-swap with "
                         "serving/serve_group invariants green on "
                         "replay + follower shard_verify digests "
                         "journaled; tokens/s reported only (cpu: "
                         "virtual-device mesh)"),
                "tp_ranks": 2, "groups": 2,
                "offered_load": {"concurrency": concurrency,
                                 "requests_per_sweep": n_requests},
                "steady": steady, "swap_sweep": swap,
                "kill": kill_info,
                "group1_actions": acts,
                "no_drop_ok": bool(no_drop),
                "all_responded_ok": bool(all_responded),
                "die_as_unit_chain_ok": bool(chain_ok),
                "restarted_group_serves_ok": bool(restarted_serves),
                "hot_swaps_observed": swaps,
                "shard_verify_records": shard_verified,
                "serving_violations": [v.to_dict() for v in violations],
                "serve_group_violations": [v.to_dict()
                                           for v in group_violations],
                **_env_stamp()}}
    finally:
        for p in supervisors:
            try:
                if p.poll() is None:
                    p.kill()
            except Exception:
                pass
        shutil.rmtree(workdir, ignore_errors=True)


def bench_input_pipeline_overlap() -> dict:
    """Dispatch-ahead input pipeline: a deliberately slow host loader
    feeding the flagship CNN step, sync-feed (next → device_put →
    dispatch → drain, serial) vs prefetch-feed (DevicePrefetcher at the
    production depth). The loader's per-batch cost is calibrated to the
    measured step wall, so a working overlap reads ~2× and the gate is
    ≥ 1.5× batches/sec. The consumer drains every step — the shape
    where the host's serial feed is fully exposed (and what a
    metrics-hungry policy loop looks like); the interleaved-repeat
    median gates it, as in bench_mode_overhead."""
    from distributedmnist_tpu.core.config import DataConfig
    from distributedmnist_tpu.data.datasets import make_synthetic
    from distributedmnist_tpu.data.device_prefetch import DevicePrefetcher

    n_dev = len(jax.devices())
    # the gate is a RATIO of feed disciplines, not a throughput anchor:
    # keep the step light on CPU meshes (8 virtual devices over a
    # couple of real cores turn a big conv step into multi-second
    # rendezvous), full-size on a real accelerator
    per_dev = 64 if jax.default_backend() == "cpu" else 2048
    batch = per_dev * max(1, n_dev)
    cfg, topo, model, state, step_fn = _build({
        "data": {"dataset": "synthetic", "batch_size": batch},
        "model": {"compute_dtype": "bfloat16"},
        "sync": {"mode": "sync"},
    })
    ds = make_synthetic(num_train=batch, num_test=64)
    host_batch = {"image": ds.train.images[:batch],
                  "label": ds.train.labels[:batch]}

    # compile + warm, then calibrate the per-step wall (dispatch +
    # drain) the slow loader is matched against
    state, m = step_fn(state, topo.device_put_batch(host_batch))
    _drain(m)
    calib = []
    for _ in range(5):
        t0 = time.perf_counter()
        state, m = step_fn(state, topo.device_put_batch(host_batch))
        float(m["loss"])
        calib.append(time.perf_counter() - t0)
    exec_s = statistics.median(calib)
    # loader cost ≈ step cost maximizes the visible overlap (expected
    # ~2×); the floor keeps sleep() resolution out of the measurement
    sleep_s = max(exec_s, 0.002)

    class SlowLoader:
        """Stand-in for an expensive host stage (decode / augment /
        assembly): sleep-dominated, so the cost is overlappable
        wherever a producer thread can run — exactly what the
        prefetcher must exploit."""

        def __iter__(self):
            return self

        def __next__(self):
            time.sleep(sleep_s)
            return host_batch

    depth = DataConfig().device_prefetch_depth
    n_batches, n_repeats = 12, 3

    def run_arm(prefetched: bool, st):
        loader = SlowLoader()
        feed = (DevicePrefetcher(loader, put=topo.device_put_batch,
                                 depth=depth) if prefetched else None)
        try:
            t0 = time.perf_counter()
            for _ in range(n_batches):
                g = next(feed) if prefetched else topo.device_put_batch(
                    next(loader))
                st, m = step_fn(st, g)
                float(m["loss"])  # drain: expose the feed fully
            dt = time.perf_counter() - t0
        finally:
            if feed is not None:
                feed.close()
        return n_batches / dt, st

    rates: dict[str, list[float]] = {"sync": [], "prefetch": []}
    for _ in range(n_repeats):  # interleaved: drift lands on both arms
        for name, pf in (("sync", False), ("prefetch", True)):
            rate, state = run_arm(pf, state)
            rates[name].append(rate)

    med = {k: statistics.median(v) for k, v in rates.items()}
    speedup = med["prefetch"] / med["sync"]
    return {
        "metric": "input_pipeline_overlap_speedup",
        "value": round(speedup, 2), "unit": "x (prefetch/sync batches/sec)",
        "meets_1p5x_gate": bool(speedup >= 1.5),
        "detail": {
            "gate": f"median of {n_repeats} interleaved repeats ≥ 1.5x",
            "step_wall_ms": round(exec_s * 1e3, 2),
            "loader_ms_per_batch": round(sleep_s * 1e3, 2),
            "prefetch_depth": depth, "batch": batch,
            "batches_per_sec": {k: [round(r, 2) for r in v]
                                for k, v in rates.items()},
            "expected_upper_bound_x": round(
                (sleep_s + exec_s) / max(sleep_s, exec_s), 2),
            **_env_stamp()}}


def bench_autoscale_response() -> dict:
    """Resource broker (ISSUE 16), gated in one process: a BROKERED
    roster beats a STATIC allocation of the same device budget under
    the same burst, and the detect→capacity-live reaction time is
    measured, not assumed.

    The budget is three slots. The static arm pins one serving replica
    and leaves two with the (notional) trainer for the whole burst —
    sixteen closed-loop clients against a queue_depth-4 admission
    bound. The replica sheds overload as typed ``overloaded`` rejects,
    and the client's failover shim retries those; with max_attempts=1
    the retry budget is spent immediately and every shed lands as a
    terminal ``error:unavailable`` outcome — the typed refusal the
    gate counts. Pressure therefore surfaces to the broker as queue-
    wait latency (the window's p99), which is exactly what the p99
    threshold marks exist for. The brokered arm starts identically,
    but the real decision core (:func:`launch.broker.decide`) watches
    the loadgen's journaled rolling window; the first p99 crossing
    trades a trainer slot for a second live ServingReplica (capacity
    live = it answers meta), and the remaining burst spreads across
    both. Gate: the scale-up actually fired, zero SILENT drops in
    either arm (typed refusals are admission control, not drops), and
    the brokered arm refuses measurably less (rejected+errors <= 0.8x
    static; if the static arm never shed at all, brokered p99 must
    not be worse than 1.1x static — the budget trade can't have
    hurt)."""
    import shutil
    import tempfile
    import threading
    from pathlib import Path

    from distributedmnist_tpu.core.config import (BrokerConfig,
                                                  ExperimentConfig,
                                                  ServeConfig)
    from distributedmnist_tpu.launch.broker import (SCALE_UP,
                                                    collect_signals,
                                                    decide)
    from distributedmnist_tpu.servesvc.client import ServeClient
    from distributedmnist_tpu.servesvc.loadgen import (make_input_fn,
                                                       read_latest_window,
                                                       run_load)
    from distributedmnist_tpu.servesvc.server import ServingReplica
    from distributedmnist_tpu.train.loop import Trainer

    workdir = Path(tempfile.mkdtemp(prefix="dmt_autoscale_bench_"))
    publish = workdir / "publish"
    concurrency, n_requests = 16, 2000
    scfg = ServeConfig(poll_secs=0.5, queue_depth=4, max_batch=8,
                       default_deadline_ms=10_000.0)
    # p99 marks are the live trigger: one pressured replica queues
    # requests to ~200ms p99 (measured: conc 16 vs queue_depth 4),
    # calm sits well under 120. The reject marks stay as a secondary
    # trip-wire but can't fire here — the client retries typed
    # ``overloaded`` rejects, so the window's reject_rate (terminal
    # status=="rejected" only) stays 0 under pure overload.
    bcfg = BrokerConfig(window_s=2.0, cooldown_s=5.0,
                        reject_high=0.05, reject_low=0.005,
                        p99_high_ms=120.0, p99_low_ms=40.0,
                        max_serve_replicas=2, max_train_workers=2,
                        settle_timeout_s=30.0)
    replicas: list = []

    def spawn(name: str) -> "ServingReplica":
        r = ServingReplica(publish, serve_dir=workdir / name, scfg=scfg,
                           cfg=cfg)
        r.start()
        replicas.append(r)
        return r

    try:
        # stage one published checkpoint (a short deterministic run)
        cfg = ExperimentConfig().override({
            "data.dataset": "synthetic", "data.batch_size": 32,
            "data.synthetic_train_size": 256,
            "data.synthetic_test_size": 64,
            "model.compute_dtype": "float32", "train.max_steps": 10,
            "train.train_dir": str(publish),
            "train.log_every_steps": 10,
            "train.save_interval_steps": 10,
            "train.async_checkpoint": False,
            "train.save_results_period": 0})
        Trainer(cfg).run()

        r1 = spawn("replica1")
        endpoints = [("127.0.0.1", r1.bound_port)]
        # max_attempts=1: the failover shim always retries typed
        # ``overloaded`` rejects, so a shed can never come back as
        # terminal status=="rejected" — with one attempt the budget
        # exhausts on the spot and the shed lands as a countable
        # terminal ``error:unavailable`` instead of being smeared
        # into retry latency
        client = ServeClient(lambda: list(endpoints), deadline_s=10.0,
                             max_attempts=1)
        make_input = make_input_fn(list(r1.model.input_shape),
                                   str(np.dtype(r1.model.input_dtype)))
        # warm the bucket shapes once so neither arm pays r1's compile
        run_load(client, 4, 1, make_input)
        run_load(client, 4 * concurrency, concurrency, make_input)

        # -- static arm: 1 replica holds the whole burst ----------------
        static = run_load(client, n_requests, concurrency, make_input,
                          journal_path=workdir / "loadgen_static.jsonl")

        # -- brokered arm: decide() on the live window ------------------
        journal = workdir / "loadgen_brokered.jsonl"
        reaction: dict = {}
        stop_mon = threading.Event()

        def monitor() -> None:
            # the broker loop, minus the process tree: 1 serving slot
            # + 2 train slots; the first crossing trades train->serve
            while not stop_mon.is_set():
                now = time.time()
                sig = collect_signals(read_latest_window(journal), [],
                                      now=now, window_s=bcfg.window_s)
                d = decide(bcfg, 1, 2, sig, None, now)
                if d is not None and d.decision == SCALE_UP:
                    reaction["t_detect"] = now
                    reaction["trigger"] = d.trigger
                    reaction["value"] = d.value
                    r2 = spawn("replica2")
                    probe = ServeClient([("127.0.0.1", r2.bound_port)],
                                        deadline_s=1.0)
                    while probe.meta(deadline_s=1.0) is None \
                            and not stop_mon.is_set():
                        time.sleep(0.05)
                    reaction["t_live"] = time.time()
                    reaction["reaction_s"] = round(
                        reaction["t_live"] - reaction["t_detect"], 3)
                    endpoints.append(("127.0.0.1", r2.bound_port))
                    return
                time.sleep(0.1)

        mon = threading.Thread(target=monitor, daemon=True)
        mon.start()
        brokered = run_load(client, n_requests, concurrency, make_input,
                            journal_path=journal, window_s=bcfg.window_s,
                            snapshot_every_s=0.5)
        stop_mon.set()
        mon.join(timeout=10)

        fired = "reaction_s" in reaction
        # dropped = issued but never resolved (a silent loss); typed
        # refusals (rejected / error:unavailable) are admission
        # control doing its job and are judged by the shed gate below
        no_drop = (static["dropped"] == 0 and brokered["dropped"] == 0)
        static_shed = static["rejected"] + static["errors"]
        brokered_shed = brokered["rejected"] + brokered["errors"]
        if static_shed > 0:
            shed_ok = brokered_shed <= 0.8 * static_shed
            gate_how = ("brokered typed refusals (rejected+errors) "
                        "<= 0.8x static")
        else:
            shed_ok = (brokered["latency_ms"]["p99"]
                       <= 1.1 * static["latency_ms"]["p99"])
            gate_how = ("static never shed: brokered p99 <= 1.1x "
                        "static p99")
        passes = bool(fired and no_drop and shed_ok)
        return {
            "metric": "autoscale_response",
            "value": reaction.get("reaction_s"),
            "unit": "s detect->capacity-live",
            "passes_gate": passes,
            "detail": {
                "gate": ("scale-up fired AND zero silent drops in "
                         "both arms AND " + gate_how),
                "budget": {"slots": 3, "static": "1 serve + 2 train",
                           "brokered": "1->2 serve"},
                "offered_load": {"concurrency": concurrency,
                                 "requests_per_arm": n_requests},
                "static": static, "brokered": brokered,
                "reaction": reaction,
                "fired_ok": bool(fired), "no_drop_ok": bool(no_drop),
                "shed_ok": bool(shed_ok),
                "shed_static": static_shed,
                "shed_brokered": brokered_shed,
                **_env_stamp()}}
    finally:
        for r in replicas:
            try:
                r.stop()
            except Exception:
                pass
        shutil.rmtree(workdir, ignore_errors=True)


def bench_straggler_adaptation() -> dict:
    """Online straggler-discipline controller (ISSUE 18), gated: under
    a phased straggler schedule the ADAPTIVE quorum discipline reaches
    the target step count in less modeled wall time than the best
    STATIC discipline an operator could have tuned a priori — with the
    per-window discipline trace journaled and zero flaps.

    The schedule is seeded and phased: calm (all four replicas near
    50 ms) → two-of-four stragglers at 8× → a uniform 3× slowdown
    (every replica healthy but slow — the phase that breaks any fixed
    deadline). The adaptive arm runs the REAL jitted quorum step with
    the schedule injected through the traced ``measured_ms`` input and
    the live ``[k, timeout_ms, interval_ms]`` discipline vector — the
    tentpole claim measured, not assumed: the controller's swaps change
    which replicas the emitted flags mask with ONE compiled executable
    (cache size asserted). Per-step barrier cost is the slowest
    CONTRIBUTING replica's time, read from the emitted flags.

    Static arms (modeled on the same schedule): sync (wait for all),
    quorum k=n-1 (the paper's backup-worker recipe, arXiv:1604.00981),
    and a timeout tuned the only way a static deadline honestly can be
    — generous against the tail observed BEFORE deployment (1.5x the
    calm phase's p99). That deadline masks the 8x stragglers nicely,
    then masks EVERY replica in the uniform-slowdown phase: zero
    contributors, zero progress — the failure mode that motivates
    retargeting the deadline from the live p50 instead of a frozen one.
    An arm that never applies its target number of updates does not
    complete, and is excluded from (but reported next to) the margin.

    Gate: adaptive completes, beats the best completing static by
    >= 10% on modeled time-to-target, adapted in BOTH directions
    (>= 1 tighten and >= 1 relax journaled + licensed), with zero
    flaps. Honest skip (< 4 devices realizable): the pure decision
    core replays the same schedule's CDFs — the decision trace is
    still asserted both directions, but no timing gate is claimed."""
    from distributedmnist_tpu.core.config import MeshConfig
    from distributedmnist_tpu.core.mesh import make_topology
    from distributedmnist_tpu.train.discipline import (
        DisciplineController, WindowStats, discipline_trace)

    n = 4
    base, spike, slow = 50.0, 8.0, 3.0
    phases = (("calm", 25, np.ones(n)),
              ("stragglers_2of4", 30,
               np.array([1.0, 1.0, spike, spike])),
              ("uniform_slow", 25, np.full(n, slow)))
    rng = np.random.default_rng(0)
    rows, phase_of = [], []
    for name, steps, mult in phases:
        for _ in range(steps):
            rows.append(base * mult + rng.uniform(0.0, 1.5, n))
            phase_of.append(name)
    times = np.stack(rows)          # [steps, n] the ground-truth CDF
    total_steps = times.shape[0]
    window, cooldown = 6, 6

    sync_cfg = {"mode": "quorum", "adaptive": True,
                "adaptive_window_steps": window,
                "adaptive_cooldown_steps": cooldown}

    def static_cost(t_row: np.ndarray, kind: str, k: int = n,
                    deadline: float = 0.0) -> tuple[float, int]:
        """(modeled barrier seconds-equivalent ms, contributors)."""
        s = np.sort(t_row)
        if kind == "quorum":
            return float(s[k - 1]), k
        mask = t_row <= deadline
        if not mask.any():
            return deadline, 0     # waited the deadline out for nothing
        return (float(t_row.max()) if mask.all()
                else deadline), int(mask.sum())

    def run_static(kind: str, k: int = n, deadline: float = 0.0) -> dict:
        cost = applied = 0.0
        for i in range(total_steps):
            c, m = static_cost(times[i], kind, k, deadline)
            cost += c
            applied += 1 if m > 0 else 0
        return {"time_ms": round(cost, 1), "applied": int(applied),
                "completed": applied == total_steps}

    calm = times[:phases[0][1]]
    static_deadline = round(1.5 * float(np.percentile(calm, 99)), 1)
    statics = {
        "sync": run_static("quorum", k=n),
        "quorum_k3": run_static("quorum", k=n - 1),
        f"timeout_{static_deadline}ms": run_static(
            "timeout", deadline=static_deadline)}

    journal: list[dict] = []
    from distributedmnist_tpu.core.config import ExperimentConfig
    scfg = ExperimentConfig.from_dict({"sync": sync_cfg}).sync

    def window_stats(history: list[np.ndarray]) -> WindowStats | None:
        if len(history) < window:
            return None
        tail = np.stack(history[-window:])
        p50, p90, p99 = np.percentile(tail, (50.0, 90.0, 99.0))
        fast = float(np.median(tail, axis=0).min())
        return WindowStats(p50_ms=float(p50), p90_ms=float(p90),
                           p99_ms=float(p99), n_samples=window,
                           fast_p50_ms=fast)

    cache_size = None
    try:
        # must land BEFORE the first backend touch — this case runs in
        # its own CI step (DMT_BENCH_CASES=straggler_adaptation) so it
        # owns the process's jax init
        from distributedmnist_tpu.core.mesh import simulate_devices
        simulate_devices(n)
        topo = make_topology(MeshConfig(simulate_devices=n))
        realizable = topo.num_replicas >= n
    except Exception as e:  # backend already pinned to fewer devices
        realizable, topo = False, None
        print(f"# straggler_adaptation: no {n}-device mesh: {e}",
              file=sys.stderr)

    if realizable:
        from distributedmnist_tpu.parallel.api import make_discipline_vector
        cfg, topo, model, state, step_fn = _build({
            "data": {"dataset": "synthetic", "batch_size": 32},
            "model": {"compute_dtype": "float32"},
            "sync": sync_cfg,
        }, topo)
        from distributedmnist_tpu.data.datasets import make_synthetic
        ds = make_synthetic(num_train=32, num_test=16)
        gbatch = topo.device_put_batch({"image": ds.train.images[:32],
                                        "label": ds.train.labels[:32]})
        ctrl = DisciplineController(scfg, n, journal.append,
                                    make_discipline_vector)
        cost = 0.0
        history: list[np.ndarray] = []
        for i in range(total_steps):
            measured = topo.device_put_measured(times[i])
            state, metrics = step_fn(state, gbatch, measured,
                                     ctrl.vector)
            t = np.asarray(metrics["step_times_ms"], dtype=np.float64)
            flags = np.asarray(metrics["flags"])
            cost += float(t[flags > 0].max())
            history.append(t)
            ctrl.maybe_adapt(i + 1, window_stats(history))
        adaptive = {"time_ms": round(cost, 1), "applied": total_steps,
                    "completed": True}
        try:
            cache_size = int(step_fn.jitted._cache_size())
        except Exception:
            cache_size = None
    else:
        # honest skip: the pure decision core over the same schedule —
        # asserts the controller's trace, claims nothing about timing
        ctrl = DisciplineController(
            scfg, n, journal.append,
            lambda k, t_ms, i_ms: (k, t_ms, i_ms))
        cost = 0.0
        history = []
        for i in range(total_steps):
            k = int(ctrl.current.k)
            c, _ = static_cost(times[i], "quorum", k)
            cost += c
            history.append(times[i])
            ctrl.maybe_adapt(i + 1, window_stats(history))
        adaptive = {"time_ms": round(cost, 1), "applied": total_steps,
                    "completed": True, "modeled_only": True}

    summary = ctrl.summary()
    trace = discipline_trace(journal)
    decisions = [r.get("decision") for r in journal
                 if r.get("action") == "begin"]
    tightens = sum(1 for d in decisions if str(d).startswith("tighten"))
    relaxes = len(decisions) - tightens
    from distributedmnist_tpu.obsv.journal import summarize_discipline
    disc = summarize_discipline(journal)
    completing = {k: v for k, v in statics.items() if v["completed"]}
    best_name = min(completing, key=lambda k: completing[k]["time_ms"])
    best = completing[best_name]["time_ms"]
    margin = round(1.0 - adaptive["time_ms"] / best, 3) if best else None
    both_ways = tightens >= 1 and relaxes >= 1
    if realizable:
        passes = bool(adaptive["completed"] and margin is not None
                      and margin >= 0.10 and both_ways
                      and disc["flaps"] == 0
                      and (cache_size is None or cache_size == 1))
        skipped = None
    else:
        passes = None
        skipped = (f"fewer than {n} devices realizable: the traced "
                   "timing signal cannot run; decision trace asserted "
                   "on the modeled CDF instead "
                   f"(both_ways={both_ways}, flaps={disc['flaps']})")
        if not (both_ways and disc["flaps"] == 0):
            passes = False  # even the modeled trace misbehaved
    print(f"# straggler_adaptation: adaptive={adaptive['time_ms']}ms "
          f"best_static={best_name}:{best}ms margin={margin} "
          f"changes={summary['changes']} trace={trace} "
          f"jit_cache={cache_size}", file=sys.stderr)
    return {
        "metric": "straggler_adaptation_margin",
        "value": margin,
        "unit": "fraction vs best completing static",
        "passes_gate": passes,
        "detail": {
            "gate": ("adaptive completes AND beats best completing "
                     "static by >= 10% modeled time-to-target AND "
                     ">=1 tighten AND >=1 relax AND zero flaps AND "
                     "one compiled executable across swaps"),
            "schedule": [{"phase": p[0], "steps": p[1],
                          "multipliers": list(map(float, p[2]))}
                         for p in phases],
            "static_deadline_ms": static_deadline,
            "adaptive": adaptive, "statics": statics,
            "best_static": best_name,
            "discipline": {"changes": summary["changes"],
                           "tightens": tightens, "relaxes": relaxes,
                           "flaps": disc["flaps"], "trace": trace},
            "jit_cache_size": cache_size,
            **({"skipped": skipped} if skipped else {}),
            **_env_stamp()}}


def main() -> None:
    """Run every case, then print the ONE self-contained artifact line
    on stdout, LAST — the driver keeps the tail of the output, so
    last-wins is what makes the artifact survive capture (VERDICT weak
    #2: headline-first + cases-on-stderr lost the cnn headline).

    ``DMT_BENCH_CASES`` (comma-separated substrings of case-function
    names) selects a subset — what lets CI afford an artifact on CPU
    runners, where the full flash/pallas cases are minutes-scale. The
    artifact notes the filter so a subset can never pass for a full run.
    """
    import os

    only = {s.strip() for s in os.environ.get("DMT_BENCH_CASES",
                                              "").split(",") if s.strip()}

    def want(fn) -> bool:
        return not only or any(k in fn.__name__ for k in only)

    if want(bench_cnn_sync):
        headline = bench_cnn_sync()
        _case(headline)  # stderr progress; stdout reserved for the end
    else:
        headline = {"metric": "bench_subset", "value": None, "unit": None,
                    "vs_baseline": None,
                    "subset": sorted(only)}
    cases: list[dict] = []
    for case in (bench_transformer_flash, bench_flash_long_context,
                 bench_mode_overhead, bench_native_loader,
                 bench_input_pipeline_overlap, bench_weight_update_sharding,
                 bench_zero1_overlap, bench_save_stall,
                 bench_checkpoint_durability,
                 bench_weak_scaling, bench_restart_latency,
                 bench_serving_latency, bench_degraded_network,
                 bench_quantized_serving,
                 bench_decode_throughput, bench_tp_serving,
                 bench_autoscale_response, bench_straggler_adaptation):
        if not want(case):
            continue
        try:
            got = case()
        except Exception as e:  # keep the other cases' numbers; main()
            # still exits non-zero below
            got = {"metric": case.__name__,
                   "error": f"{type(e).__name__}: {e}"}
        for record in got if isinstance(got, list) else [got]:
            _case(record)
            cases.append(record)
    # regression guard: the headline ratchets (every case carrying a
    # vs_baseline anchor: CNN, transformer flash, long-context flash)
    # must not move down while the overlap case moves up (ISSUE 2
    # acceptance) — surfaced as one field instead of leaving the
    # reader to scan cases. `ok` is vs the PUBLISHED round-1 anchor
    # (the repo's ratchet mechanism); round-over-round trajectory
    # lives in the BENCH_r* history, not here.
    anchored_fns = ("bench_transformer_flash", "bench_flash_long_context")
    guarded = [headline] + [
        c for c in cases
        # a CRASHED anchor case records {"metric": fn_name, "error":..}
        # with no vs_baseline — it must appear here as not-ok, not
        # silently vanish from the guard
        if "vs_baseline" in c or c.get("metric") in anchored_fns]
    guard = {
        "threshold": "vs_baseline >= 0.9 of the published anchor",
        "cases": [{"metric": c.get("metric"),
                   "vs_baseline": c.get("vs_baseline"),
                   "ok": (False if "error" in c
                          else None if c.get("vs_baseline") is None
                          else bool(c["vs_baseline"] >= 0.9))}
                  for c in guarded]}
    # compile time as a first-class artifact metric (ROADMAP item 5):
    # every case already measures its compile_s — surface them in one
    # place, headline_regression_guard-style, so a compile-cache or
    # lowering regression shows up in the bench JSON trajectory
    # instead of hiding inside per-case detail
    compile_seconds = {
        "note": ("per-case XLA compile wall seconds; compare across "
                 "BENCH_r* rounds — a jump here is a compile/lowering "
                 "or persistent-cache regression even when throughput "
                 "holds"),
        "by_case": {c.get("metric"): c["detail"]["compile_s"]
                    for c in [headline] + cases
                    if isinstance(c.get("detail"), dict)
                    and c["detail"].get("compile_s") is not None}}
    print(json.dumps({**headline, "cases": cases,
                      "headline_regression_guard": guard,
                      "compile_seconds": compile_seconds},
                     separators=(",", ":")))
    # the artifact keeps a crashed case as an "error" field (the other
    # cases' numbers survive), but the run itself failed
    errored = [c.get("metric") for c in cases if "error" in c]
    if errored:
        print(f"# bench cases errored: {errored}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
