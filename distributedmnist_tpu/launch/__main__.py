"""Framework CLI.

≙ the reference's launch surface: ``tools/tf_ec2.py``'s subcommand
dispatch (:828-867) and the templated per-role SSH commands it
generated (:109-146). On TPU there are no roles to template — every
host runs the same program — so the CLI reduces to:

  python -m distributedmnist_tpu.launch train --config cfg.json [k=v ...]
  python -m distributedmnist_tpu.launch eval  --train_dir DIR
  python -m distributedmnist_tpu.launch sweep --configs DIR --results DIR
  python -m distributedmnist_tpu.launch cluster run --until-step N [--backend local]
  python -m distributedmnist_tpu.launch report --train_dir DIR --out DIR
  python -m distributedmnist_tpu.launch devices

Dotted overrides (``sync.mode=quorum``) take the place of the ~25
tf.app.flags (src/distributed_train.py:36-99).
"""

from __future__ import annotations

import argparse
import json
import sys


def _load_cfg_and_bringup(args):
    """Parse config BEFORE touching any jax API, then bring the
    platform up: a simulated N-device CPU mesh when the config asks for
    one, multi-host discovery otherwise."""
    from ..core.config import ExperimentConfig, parse_cli_overrides
    from ..core.mesh import initialize_distributed, simulate_devices

    cfg = (ExperimentConfig.from_file(args.config) if args.config
           else ExperimentConfig())
    cfg = cfg.override(parse_cli_overrides(getattr(args, "overrides", [])))
    if cfg.mesh.simulate_devices > 0:
        simulate_devices(cfg.mesh.simulate_devices)
    else:
        initialize_distributed()  # multi-host bring-up before backend init
    # persistent compile cache (core/compile_cache.py's one rule): a
    # restarted worker reuses its predecessor's compiles instead of
    # paying the full XLA compile again on every recovery
    from ..core.compile_cache import enable_persistent_cache
    enable_persistent_cache(cfg.compile)
    return cfg


def _park_standby(trainer, activation: str) -> None:
    """The warm-standby protocol (ROADMAP item 5): precompile, signal
    readiness by touching ``<activation>.ready``, then PARK until the
    supervisor's promotion writes the activation file (atomic rename —
    never read torn) naming the dead worker's train_dir, and adopt it.
    The parked process has already paid import, mesh bring-up and the
    train-step compile, so promotion→first-moved-step is data-path
    time only."""
    import json as _json
    import os as _os
    import time as _time
    from pathlib import Path

    try:
        trainer.precompile()
    except Exception as e:  # park anyway: a warm PROCESS still beats a
        # cold boot even if the compile must happen at first step
        print(f"standby precompile failed ({type(e).__name__}: {e}); "
              "parking warm-process only", file=sys.stderr)
    act = Path(activation)
    act.parent.mkdir(parents=True, exist_ok=True)
    ready = act.with_name(act.name + ".ready")
    ready.write_text(_json.dumps({"pid": _os.getpid(),
                                  "ready_at": _time.time()}))
    while not act.exists():
        _time.sleep(0.1)
    assignment = _json.loads(act.read_text())
    trainer.adopt_train_dir(assignment["train_dir"])


def _park_serve_standby(activation: str) -> str:
    """The serving-payload half of the warm-standby protocol: the
    parked spare has already paid process boot, jax import and the
    publish-dir config wait (everything before this call in
    ``_serve``); it signals ready, parks, and on promotion returns the
    assigned worker logdir to use as serve_dir — the replica then
    binds there and writes its endpoint card where
    ``discover_endpoints`` looks. The assignment's ``train_dir`` key
    names the ADOPTED logdir (the protocol's field name, shared with
    the trainer's parking path)."""
    import json as _json
    import os as _os
    import time as _time
    from pathlib import Path

    act = Path(activation)
    act.parent.mkdir(parents=True, exist_ok=True)
    ready = act.with_name(act.name + ".ready")
    ready.write_text(_json.dumps({"pid": _os.getpid(),
                                  "ready_at": _time.time()}))
    while not act.exists():
        _time.sleep(0.1)
    assignment = _json.loads(act.read_text())
    return assignment["train_dir"]


def _train(args) -> None:
    import os

    cfg = _load_cfg_and_bringup(args)
    from ..train.loop import Trainer

    trainer = Trainer(cfg)
    activation = os.environ.get("DMT_STANDBY_ACTIVATION")
    if activation:
        _park_standby(trainer, activation)
    summary = trainer.run()
    if summary.get("preempted"):
        # a flushed, resumable stop (SIGTERM/SIGINT mid-run): exit with
        # the distinct resumable code so a supervisor restarts us
        # instead of treating this as a crash; skip eval — the process
        # was asked to leave
        print(json.dumps({"summary": {k: v for k, v in summary.items()
                                      if k != "timing"}}, default=str))
        sys.exit(cfg.train.resumable_exit_code)
    result = trainer.evaluate("test")
    print(json.dumps({"summary": {k: v for k, v in summary.items() if k != "timing"},
                      "test": result}, default=str))


def _eval(args) -> None:
    from ..core.config import EvalConfig
    from .. import evalsvc

    ecfg = EvalConfig(eval_interval_secs=args.eval_interval_secs,
                      eval_dir=args.eval_dir, run_once=args.run_once,
                      max_evals=args.max_evals)
    evalsvc.Evaluator(args.train_dir, ecfg,
                      single_device=args.single_device).run()


def _serve(args) -> None:
    """The serving-replica payload (`launch serve`): hot-follow the
    publish dir's checkpoints and serve inference over a local socket
    — the process the cluster's serving payload verb spawns. Runs on
    ONE ambient device (no simulated mesh, no collectives), adopting
    the model/config from the checkpoint itself like the evaluator.
    ``--decode`` swaps the workload inside the replica contract from
    one-shot classification to continuous-batching autoregressive
    decode (streaming tokens, paged KV cache).

    Honors ``DMT_STANDBY_ACTIVATION`` like ``launch train``: a serving
    spare pays the import + config wait up front, parks ready, and on
    promotion adopts the ASSIGNED worker logdir as its serve_dir — the
    warm pool the resource broker promotes scale-up replicas from."""
    import dataclasses
    import os

    from ..servesvc.server import ServingReplica, wait_for_run_config

    cfg = wait_for_run_config(args.train_dir)
    # same cache rule as `launch train`: a restarted replica reads its
    # predecessor's prefill-bucket and decode-step compiles
    from ..core.compile_cache import enable_persistent_cache
    enable_persistent_cache(cfg.compile)
    activation = os.environ.get("DMT_STANDBY_ACTIVATION")
    if activation:
        args.serve_dir = _park_serve_standby(activation)
    tp_ranks = (args.tp_ranks if args.tp_ranks is not None
                else cfg.serve.tp_ranks)
    if tp_ranks > 1 and args.tp_rank is None:
        # TP group supervisor: re-invoke this very command once per
        # rank (rank 0 = the real replica owning the socket, ranks>0 =
        # shard-verifying followers) and babysit them die-as-a-unit
        import sys

        from ..servesvc.tp_group import ServeGroup, default_spawn_fn
        spawn = default_spawn_fn(sys.argv[1:], args.serve_dir, tp_ranks)
        ServeGroup(args.serve_dir, tp_ranks, spawn,
                   max_restarts=cfg.serve.tp_group_max_restarts,
                   poll_secs=cfg.serve.tp_group_poll_secs).run_forever()
        return
    if args.tp_rank is not None and args.tp_rank > 0:
        from ..servesvc.tp_group import run_rank_follower
        run_rank_follower(args.train_dir, args.serve_dir, args.tp_rank,
                          tp_ranks,
                          poll_secs=cfg.serve.tp_group_poll_secs)
        return
    overrides = {k: getattr(args, k) for k in
                 ("host", "port", "max_batch", "queue_depth",
                  "batch_window_ms", "poll_secs", "default_deadline_ms",
                  "precision_tier", "compute_dtype", "tp_ranks")
                 if getattr(args, k) is not None}
    scfg = dataclasses.replace(cfg.serve, **overrides)
    if args.decode:
        from ..servesvc.decode import DecodeReplica
        d_over = {k: getattr(args, k) for k in
                  ("decode_slots", "max_new_tokens", "max_prompt_len",
                   "swap_policy", "attention_kernel")
                  if getattr(args, k) is not None}
        dcfg = dataclasses.replace(cfg.decode, **d_over)
        DecodeReplica(args.train_dir, serve_dir=args.serve_dir,
                      scfg=scfg, dcfg=dcfg, cfg=cfg).serve_forever()
        return
    ServingReplica(args.train_dir, serve_dir=args.serve_dir,
                   scfg=scfg, cfg=cfg).serve_forever()


def _serve_load(args) -> None:
    """Closed-loop load generator (`launch serve-load`): drive a
    serving cluster through the round-robin failover shim, journal
    every request's terminal outcome, print the latency summary."""
    import time as _time

    from ..servesvc.client import ServeClient, discover_endpoints
    from ..servesvc.loadgen import make_input_fn, run_load

    if args.endpoints:
        eps = [tuple(e.rsplit(":", 1)) for e in args.endpoints.split(",")]
        eps = [(h, int(p)) for h, p in eps]
        endpoints_fn = lambda: eps  # noqa: E731
    elif args.cluster_root:
        root = args.cluster_root
        endpoints_fn = lambda: discover_endpoints(root)  # noqa: E731
    else:
        raise SystemExit("serve-load needs --endpoints or --cluster-root")
    client = ServeClient(endpoints_fn, deadline_s=args.deadline_s,
                         max_attempts=args.max_attempts)
    deadline = _time.time() + args.ready_timeout_s
    meta = None
    while meta is None and _time.time() < deadline:
        meta = client.meta(deadline_s=2.0)
        if meta is None:
            _time.sleep(0.5)
    if meta is None:
        raise SystemExit(f"no serving replica became ready within "
                         f"{args.ready_timeout_s:.0f}s")
    make_input = make_input_fn(meta["input_shape"], meta["input_dtype"])
    summary = run_load(client, args.requests, args.concurrency,
                       make_input, journal_path=args.out)
    print(json.dumps(summary))


def _sweep(args) -> None:
    from ..core.mesh import initialize_distributed
    initialize_distributed()
    from .sweep import load_sweep_configs, run_sweep

    cfgs = load_sweep_configs(args.configs)
    if args.only:
        cfgs = [c for c in cfgs if c.name in set(args.only.split(","))]
    records = run_sweep(cfgs, args.results)
    print(json.dumps([{k: r[k] for k in ("name", "test_accuracy",
                                         "examples_per_sec")}
                      for r in records]))


def _report(args) -> None:
    from ..obsv.report import generate_report

    stats = generate_report(args.train_dir, args.eval_dir, args.out,
                            name=args.name)
    print(json.dumps(stats, indent=2))


def _fetch(args) -> None:
    """Download + verify the REAL archives (≙ maybe_download,
    src/mnist_data.py:176-187, plus the digest pinning the reference
    never had). The one-command path from a fixture cache to verified
    real data: the day this box has egress,
    ``launch fetch --verify`` upgrades the cache and rewrites
    PROVENANCE.md to say so — the 99%-on-real-MNIST oracle is then
    ``launch train --config configs/repro/mnist_99.json`` away."""
    import hashlib
    import time
    from pathlib import Path

    from ..data import datasets as DS

    root = Path(args.data_dir)
    dataset = args.dataset
    pins = DS._PINNED_SHA256.get(dataset, {})

    def list_stranded():
        """*.quarantine files left by an interrupted earlier run
        (killed between quarantine and restore)."""
        return (sorted(p.name for p in root.glob("*.quarantine"))
                if root.is_dir() else [])

    def recover(stranded):
        """Put a stranded file back when its slot is still empty,
        discard it when the slot was re-filled — either way no
        *.quarantine survives into this run's bookkeeping. Must run
        under the fetch lock: a LIVE peer's quarantine files are
        indistinguishable from stranded ones."""
        for name in stranded:
            aside = root / name
            orig = aside.with_name(name[: -len(".quarantine")])
            if orig.exists():
                aside.unlink()
            else:
                aside.rename(orig)

    def build_plan(recovered):
        plan = []
        for key, names in DS._IDX_FILES.items():
            gz = names[0] + ".gz"
            cached = DS._find_idx(root, names)
            if cached is None and any(n in recovered
                                      or n + ".gz" in recovered
                                      for n in names):
                # dry-run only: a real fetch recovers the stranded file
                # first, so "missing" would misstate what it will do
                plan.append({"file": gz, "cached": None,
                             "status": "stranded quarantine (a "
                                       "non-dry-run fetch recovers it "
                                       "before planning)",
                             "pinned_sha256": pins.get(gz),
                             "mirrors": [b + gz
                                         for b in DS._IDX_MIRRORS[dataset]]})
                continue
            status = "missing"
            if cached is not None:
                if cached.name in pins:
                    got = hashlib.sha256(cached.read_bytes()).hexdigest()
                    status = ("verified" if got == pins[cached.name]
                              else "DIGEST MISMATCH")
                else:
                    status = ("cached, not digest-verifiable "
                              "(fixture or raw idx)")
            plan.append({"file": gz,
                         "cached": str(cached) if cached else None,
                         "status": status, "pinned_sha256": pins.get(gz),
                         "mirrors": [b + gz
                                     for b in DS._IDX_MIRRORS[dataset]]})
        return plan

    if args.dry_run:
        # zero mutation (and no lock): stranded files are reported and
        # annotated in the plan, not recovered
        stranded = list_stranded()
        plan = build_plan({x[: -len(".quarantine")] for x in stranded})
        print(json.dumps({"dataset": dataset, "data_dir": str(root),
                          "plan": plan,
                          "stranded_quarantine": stranded}, indent=2))
        return

    # Everything that mutates the cache — stranded recovery, planning
    # against the recovered state, quarantine, download, and the
    # commit/rollback — runs under an exclusive per-data-dir flock.
    # The rollback deletes every known-name file that postdates this
    # run's snapshot, which would destroy archives a concurrent peer
    # installed, and an unlocked recovery would un-quarantine a live
    # peer's files mid-fetch. The lock lives under the system temp dir
    # (keyed on the resolved cache path) so the cache itself stays
    # byte-identical across a failed fetch; it therefore serializes
    # same-HOST fetches only — distinct hosts sharing one NFS dir fall
    # back to maybe_download's atomic per-file installs, as before
    # (flock over NFS is not dependable anyway).
    import fcntl
    import tempfile
    root.mkdir(parents=True, exist_ok=True)
    lock_name = ("dmt_fetch_"
                 + hashlib.sha256(str(root.resolve()).encode())
                 .hexdigest()[:16] + ".lock")
    import os as _os
    # O_CREAT|O_RDWR with 0o666 (not open(..., "w")): on a shared
    # machine a second user must be able to open the SAME lock file —
    # "w" would both truncate and fail on the other user's 0644 file
    lock_fd = _os.open(Path(tempfile.gettempdir()) / lock_name,
                       _os.O_CREAT | _os.O_RDWR, 0o666)
    lock_f = _os.fdopen(lock_fd, "r+")
    fcntl.flock(lock_f, fcntl.LOCK_EX)
    try:
        recover(list_stranded())
        plan = build_plan(set())

        quarantined: list[tuple] = []
        if args.verify:
            # anything cached that cannot be digest-verified (the synthetic
            # fixture, an unpinned raw idx, a mismatch) steps ASIDE so the
            # download below replaces it with the verifiable archive — but
            # only a successful download deletes it: without egress the
            # fixture cache must survive intact
            for entry, (key, names) in zip(plan, DS._IDX_FILES.items()):
                if entry["cached"] and entry["status"] != "verified":
                    for name in names:
                        for cand in (root / name, root / (name + ".gz")):
                            if cand.exists():
                                aside = cand.with_name(cand.name + ".quarantine")
                                cand.rename(aside)
                                quarantined.append((aside, cand))

        # Snapshot AFTER quarantining: at rollback, every known-name file
        # not in this set was installed by THIS run and must go — including
        # downloads into slots that were empty to begin with (which have no
        # quarantine entry to displace).
        all_names = [n for names in DS._IDX_FILES.values()
                     for name in names for n in (name, name + ".gz")]
        pre_existing = {n for n in all_names if (root / n).exists()}

        ok = DS.maybe_download(root, dataset)
        verified = {}
        unverifiable = []
        for key, names in DS._IDX_FILES.items():
            cached = DS._find_idx(root, names)
            if cached is None:
                ok = False
                continue
            if cached.name in pins:
                got = hashlib.sha256(cached.read_bytes()).hexdigest()
                if got != pins[cached.name]:
                    ok = False
                    continue
                verified[cached.name] = got
            else:
                # a legitimate cache of uncompressed idx files (or an
                # unpinned dataset): structurally validated on install,
                # just not digest-pinnable — present counts as healthy
                unverifiable.append(cached.name)

        downloaded = sorted(n for n in all_names
                            if n not in pre_existing and (root / n).exists())
        if ok:
            for aside, _orig in quarantined:
                aside.unlink(missing_ok=True)
        else:
            # transactional rollback: drop EVERY file this run installed
            # (quarantine-displacing replacements AND downloads into
            # previously-empty slots), then put every quarantined file
            # back — the cache ends exactly as it started
            for n in downloaded:
                (root / n).unlink(missing_ok=True)
            for aside, orig in quarantined:
                orig.unlink(missing_ok=True)
                aside.rename(orig)

        # PROVENANCE.md is only rewritten when this run actually
        # established real data: it downloaded archives, or it
        # digest-verified every slot. A cache this run neither fetched nor
        # verified (unpinnable idx files, --verify not passed) keeps
        # whatever provenance it had — fetch must never relabel a fixture
        # as real.
        establishes_real = bool(downloaded) or (
            bool(pins) and len(verified) == len(DS._IDX_FILES))
        if ok and establishes_real:
            (root / "PROVENANCE.md").write_text(
                f"# Real dataset ({dataset})\n\n"
                f"Downloaded and installed by `launch fetch` at "
                f"{time.strftime('%Y-%m-%d %H:%M:%S UTC', time.gmtime())}.\n"
                + ("Archives verified against the pinned sha256 digests "
                   "(distributedmnist_tpu/data/datasets.py:_PINNED_SHA256):\n\n"
                   + "".join(f"- `{k}`: `{v}`\n" for k, v in sorted(verified.items()))
                   if verified else
                   "No digest-pinnable archives (structural idx validation "
                   "applied on install).\n")
                + ("".join(f"- `{n}`: present, structurally valid, no digest "
                           "pin applicable\n" for n in sorted(unverifiable))
                   if unverifiable else ""))
        if ok:
            print(json.dumps({"ok": True, "dataset": dataset,
                              "data_dir": str(root),
                              "downloaded": downloaded,
                              "verified": sorted(verified),
                              "unverifiable": sorted(unverifiable),
                              "provenance_updated": establishes_real}))
        else:
            print(json.dumps({"ok": False, "dataset": dataset,
                              "data_dir": str(root),
                              "hint": "no egress or mirror/digest failure; "
                                      "the cache was left as-is (fixture runs "
                                      "keep working)"}))
            sys.exit(1)
    finally:
        fcntl.flock(lock_f, fcntl.LOCK_UN)
        lock_f.close()


def _devices(_args) -> None:
    """≙ list_running_instances (tools/tf_ec2.py:371-402) — but the
    'cluster' is whatever mesh JAX sees."""
    import jax
    info = {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "devices": [{"id": d.id, "platform": d.platform,
                     "kind": getattr(d, "device_kind", "?")}
                    for d in jax.devices()],
    }
    print(json.dumps(info, indent=2))


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "campaign":
        # pre-dispatch: the campaign owns its own flags (REMAINDER
        # cannot capture leading options), and its 8-device mesh must
        # be forced before any backend init
        from ..core.mesh import simulate_devices
        simulate_devices(8)
        from .campaign import main as campaign_main
        return campaign_main(argv[1:])
    p = argparse.ArgumentParser(prog="distributedmnist_tpu.launch")
    sub = p.add_subparsers(dest="cmd", required=True)

    pt = sub.add_parser("train", help="run a training experiment")
    pt.add_argument("--config", default=None)
    pt.add_argument("overrides", nargs="*", help="dotted overrides k=v")
    pt.set_defaults(fn=_train)

    pe = sub.add_parser("eval", help="continuous evaluator")
    pe.add_argument("--train_dir", required=True)
    pe.add_argument("--eval_dir", default="/tmp/dmt_eval")
    pe.add_argument("--eval_interval_secs", type=float, default=1.0)
    pe.add_argument("--run_once", action="store_true")
    pe.add_argument("--max_evals", type=int, default=0)
    pe.add_argument("--single_device", action="store_true",
                    help="evaluate on ONE ambient device regardless of the "
                         "training mesh (DP checkpoints only; the lean "
                         "co-located mode)")
    pe.set_defaults(fn=_eval)

    pv = sub.add_parser(
        "serve", help="serving replica: hot-follow a train_dir's "
                      "published checkpoints (digest-verified, torn "
                      "publishes skipped) and serve inference over a "
                      "local socket with admission control and "
                      "zero-drop weight hot-swap")
    pv.add_argument("--train_dir", required=True,
                    help="the publish dir to follow")
    pv.add_argument("--serve-dir", default=".",
                    help="where serve.json / serve_log.jsonl / "
                         "heartbeats land (the worker's own logdir "
                         "under a cluster)")
    pv.add_argument("--host", default=None)
    pv.add_argument("--port", type=int, default=None,
                    help="0 = ephemeral (the bound port is published "
                         "in serve.json)")
    pv.add_argument("--max-batch", type=int, default=None, dest="max_batch")
    pv.add_argument("--queue-depth", type=int, default=None,
                    dest="queue_depth",
                    help="admission bound; a full queue load-sheds "
                         "with a typed reject")
    pv.add_argument("--batch-window-ms", type=float, default=None,
                    dest="batch_window_ms")
    pv.add_argument("--poll-secs", type=float, default=None,
                    dest="poll_secs", help="checkpoint-follow cadence")
    pv.add_argument("--default-deadline-ms", type=float, default=None,
                    dest="default_deadline_ms")
    pv.add_argument("--precision-tier", default=None,
                    dest="precision_tier",
                    help="fp32 | bf16 | int8 — prefer the named "
                         "quantized sidecar tier (quant.publish_tiers) "
                         "over the full-precision artifact; absent/"
                         "torn sidecars fall back to fp32, journaled")
    pv.add_argument("--compute-dtype", default=None, dest="compute_dtype",
                    help="serving-side activations/matmul dtype "
                         "override (serve.compute_dtype)")
    pv.add_argument("--decode", action="store_true",
                    help="serve continuous-batching autoregressive "
                         "decode (streaming tokens over a paged KV "
                         "cache) instead of one-shot classification; "
                         "the followed checkpoint must be a causal LM "
                         "with a decode export (the plain block, or a "
                         "latent one with dense, gated or per-token "
                         "routed feed-forwards)")
    pv.add_argument("--decode-slots", type=int, default=None,
                    dest="decode_slots",
                    help="concurrently-generating sequences per "
                         "replica (decode.decode_slots)")
    pv.add_argument("--max-new-tokens", type=int, default=None,
                    dest="max_new_tokens",
                    help="per-request generation ceiling "
                         "(decode.max_new_tokens)")
    pv.add_argument("--max-prompt-len", type=int, default=None,
                    dest="max_prompt_len",
                    help="longest admissible prompt "
                         "(decode.max_prompt_len)")
    pv.add_argument("--swap-policy", default=None, dest="swap_policy",
                    help="pin | restart — what a weight hot-swap does "
                         "to sequences mid-generation "
                         "(decode.swap_policy)")
    pv.add_argument("--attention-kernel", default=None,
                    dest="attention_kernel",
                    help="auto | dense | paged — how the decode step "
                         "reads the cache (decode.attention_kernel); "
                         "auto: on a TPU the kernel that walks each "
                         "slot's block table over the rows as stored, "
                         "O(live context) per token, elsewhere the "
                         "gather; dense and paged name an arm")
    pv.add_argument("--tp-ranks", type=int, default=None,
                    dest="tp_ranks",
                    help="boot the replica as an N-rank tensor-"
                         "parallel process group (serve.tp_ranks): "
                         "rank 0 owns the socket and the sharded "
                         "serving mesh, other ranks shard-verify "
                         "every publish; any rank dying takes the "
                         "whole group down for a unit restart")
    pv.add_argument("--tp-rank", type=int, default=None, dest="tp_rank",
                    help=argparse.SUPPRESS)  # internal: set by the
    # group supervisor when re-invoking serve per rank
    pv.set_defaults(fn=_serve)

    pl = sub.add_parser(
        "serve-load", help="closed-loop load generator over a serving "
                           "cluster (round-robin failover shim, "
                           "per-request journal, p50/p99 summary)")
    pl.add_argument("--cluster-root", default=None,
                    help="LocalProcessCluster root to discover "
                         "worker*/serve.json endpoints from")
    pl.add_argument("--endpoints", default=None,
                    help="comma-separated host:port list (overrides "
                         "--cluster-root)")
    pl.add_argument("--requests", type=int, default=200)
    pl.add_argument("--concurrency", type=int, default=2)
    pl.add_argument("--deadline-s", type=float, default=5.0,
                    dest="deadline_s")
    pl.add_argument("--max-attempts", type=int, default=6,
                    dest="max_attempts")
    pl.add_argument("--ready-timeout-s", type=float, default=120.0,
                    dest="ready_timeout_s")
    pl.add_argument("--out", default="loadgen.jsonl",
                    help="per-request journal path")
    pl.set_defaults(fn=_serve_load)

    ps = sub.add_parser("sweep", help="run a directory of experiment configs")
    ps.add_argument("--configs", required=True)
    ps.add_argument("--results", required=True)
    ps.add_argument("--only", default=None, help="comma-separated names")
    ps.set_defaults(fn=_sweep)

    pr = sub.add_parser("report", help="figures + stats from run logs")
    pr.add_argument("--train_dir", required=True)
    pr.add_argument("--eval_dir", default=None)
    pr.add_argument("--out", required=True)
    pr.add_argument("--name", default="experiment")
    pr.set_defaults(fn=_report)

    pd = sub.add_parser("devices", help="show mesh topology")
    pd.set_defaults(fn=_devices)

    pf = sub.add_parser(
        "fetch", help="download + digest-verify the real dataset archives "
                      "(one command from fixture cache to verified real "
                      "data, ≙ src/mnist_data.py:39,179)")
    pf.add_argument("--dataset", default="mnist",
                    choices=["mnist", "fashion_mnist"])
    pf.add_argument("--data-dir", default="data_cache/mnist")
    pf.add_argument("--verify", action="store_true",
                    help="re-verify cached archives against the pinned "
                         "sha256 digests; non-verifiable cached files "
                         "(e.g. the synthetic fixture) are replaced")
    pf.add_argument("--dry-run", action="store_true",
                    help="print the fetch/verify plan without touching "
                         "the network or the cache")
    pf.set_defaults(fn=_fetch)

    def _pod(args) -> None:
        from .pod import main as pod_main
        pod_main(args.rest)

    pp = sub.add_parser("pod", help="TPU pod-slice lifecycle (gcloud)",
                        add_help=False)
    pp.add_argument("rest", nargs=argparse.REMAINDER)
    pp.set_defaults(fn=_pod)

    def _cluster(args) -> None:
        from .cluster import main as cluster_main
        cluster_main(args.rest)

    pc = sub.add_parser(
        "cluster", help="backend-pluggable cluster lifecycle "
                        "(local process-cluster or gcloud TPU-VM; "
                        "fault plans, command journal, supervised "
                        "self-healing runs, seeded chaos campaigns "
                        "with invariant checking — `cluster chaos "
                        "--trials N --seed S --until-step M`)",
        add_help=False)
    pc.add_argument("rest", nargs=argparse.REMAINDER)
    pc.set_defaults(fn=_cluster)

    sub.add_parser("campaign",
                   help="run the full experiment campaign grid "
                        "(options: see `campaign --help`)")

    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
