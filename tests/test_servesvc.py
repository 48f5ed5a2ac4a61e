"""Serving-tier tests: the replica's robustness contract (admission,
deadlines, digest-verified hot-swap, graceful drain), the failover
client shim, the serving chaos schedule grammar, and the three serving
replay invariants over handcrafted artifacts."""

import json
import shutil
import socket
import threading
import time
from pathlib import Path

import pytest

from conftest import base_config


# ---------------------------------------------------------------------------
# shared publisher: ONE short deterministic training run per module
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def published(tmp_path_factory, synthetic_datasets):
    """A staging dir holding a stream of real checkpoints (steps
    10/20/30) plus the run's config — each test publishes them into
    its own serve dir at its own cadence."""
    staging = tmp_path_factory.mktemp("staging")
    cfg = base_config(train={"train_dir": str(staging), "max_steps": 30,
                             "log_every_steps": 10,
                             "save_interval_steps": 10})
    from distributedmnist_tpu.train.loop import Trainer
    Trainer(cfg, datasets=synthetic_datasets).run()
    steps = sorted(int(p.name[5:13]) for p in staging.glob("ckpt-*.msgpack"))
    assert steps == [10, 20, 30]
    return {"staging": staging, "cfg": cfg, "steps": steps}


def publish_step(staging: Path, serve_dir: Path, step: int,
                 truncate: bool = False) -> None:
    """Copy one staged checkpoint (artifact + digest sidecar) into the
    serve dir and point ``checkpoint.json`` at it. ``truncate`` tears
    the artifact AFTER the copy (sidecar kept intact) — the corrupt-
    publish scenario digest verification must refuse."""
    name = f"ckpt-{step:08d}.msgpack"
    serve_dir.mkdir(parents=True, exist_ok=True)
    shutil.copy2(staging / name, serve_dir / name)
    shutil.copy2(staging / (name + ".sha256"),
                 serve_dir / (name + ".sha256"))
    if truncate:
        data = (serve_dir / name).read_bytes()
        (serve_dir / name).write_bytes(data[:max(1, len(data) // 2)])
    tmp = serve_dir / "checkpoint.json.tmp"
    tmp.write_text(json.dumps({"latest_step": step, "latest_path": name,
                               "written_at": time.time()}))
    tmp.replace(serve_dir / "checkpoint.json")


def make_replica(published, tmp_path, first_step=10, **serve_kw):
    from distributedmnist_tpu.core.config import ServeConfig
    from distributedmnist_tpu.servesvc.server import ServingReplica
    serve_src = tmp_path / "publish"
    publish_step(published["staging"], serve_src, first_step)
    scfg = ServeConfig(poll_secs=0.05, **serve_kw)
    rep = ServingReplica(serve_src, serve_dir=tmp_path / "replica",
                         scfg=scfg, cfg=published["cfg"])
    return rep, serve_src


def raw_request(port: int, payload: dict, timeout=10.0) -> dict:
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=timeout) as conn:
        conn.settimeout(timeout)
        conn.sendall((json.dumps(payload) + "\n").encode())
        buf = b""
        while b"\n" not in buf:
            chunk = conn.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf.decode())


def serve_records(rep) -> list[dict]:
    return [json.loads(l) for l in
            (rep.serve_dir / "serve_log.jsonl").read_text().splitlines()
            if l.strip()]


def sample_input(published):
    from distributedmnist_tpu.servesvc.loadgen import make_input_fn
    shape = (published["cfg"].model.image_size,) * 2 + (1,)
    return make_input_fn(shape, "float32")


# ---------------------------------------------------------------------------
# the replica end-to-end
# ---------------------------------------------------------------------------

def test_serve_responds_and_hot_swaps(published, tmp_path):
    """Requests answer from the digest-verified newest step; a fresh
    publish mid-traffic hot-swaps without dropping anything; swap
    journal is monotone with digests."""
    rep, serve_src = make_replica(published, tmp_path)
    rep.start()
    try:
        make_input = sample_input(published)
        out = raw_request(rep.bound_port, {"id": 1,
                                           "inputs": make_input(1)})
        assert out["status"] == "ok" and out["model_step"] == 10
        assert len(out["probs"]) == 10
        # publish step 20 mid-traffic; keep requesting until the swap
        publish_step(published["staging"], serve_src, 20)
        deadline = time.time() + 30
        got_step = 10
        i = 2
        while got_step < 20 and time.time() < deadline:
            out = raw_request(rep.bound_port, {"id": i,
                                               "inputs": make_input(i)})
            assert out["status"] == "ok"  # zero drops across the swap
            got_step = out["model_step"]
            i += 1
        assert got_step == 20
        recs = serve_records(rep)
        swaps = [r for r in recs if r.get("action") == "weight_swap"]
        assert [s["step"] for s in swaps] == [10, 20]
        assert all(s.get("digest") for s in swaps)
        assert all(isinstance(s.get("swap_ms"), float) for s in swaps)
    finally:
        rep.stop()
    # server-side exactly-one-terminal bookkeeping
    recs = serve_records(rep)
    admits = sum(1 for r in recs if r.get("action") == "admit")
    responds = sum(1 for r in recs if r.get("action") == "respond")
    rejects = sum(1 for r in recs if r.get("action") == "reject"
                  and r.get("admitted"))
    assert admits == responds + rejects and admits >= 2


def test_serve_skips_corrupt_publish(published, tmp_path):
    """A torn publish (bytes disagree with the digest sidecar) is
    SKIPPED — the replica keeps serving the previous weights, journals
    the fallback, and the next good publish swaps past it. Invariant:
    no response is ever computed from a failed-digest checkpoint."""
    rep, serve_src = make_replica(published, tmp_path)
    rep.start()
    try:
        make_input = sample_input(published)
        publish_step(published["staging"], serve_src, 20, truncate=True)
        # give the follower several polls at the torn artifact
        time.sleep(0.5)
        out = raw_request(rep.bound_port, {"id": 1,
                                           "inputs": make_input(1)})
        assert out["status"] == "ok"
        assert out["model_step"] == 10  # still the last GOOD step
        publish_step(published["staging"], serve_src, 30)
        deadline = time.time() + 30
        while rep.model_step < 30 and time.time() < deadline:
            time.sleep(0.05)
        assert rep.model_step == 30  # skipped 20 entirely
        recs = serve_records(rep)
        assert [r["step"] for r in recs
                if r.get("action") == "weight_swap"] == [10, 30]
        assert any(r.get("action") == "follow_corrupt_checkpoint_fallback"
                   for r in recs), recs
    finally:
        rep.stop()


def test_serve_admission_and_deadline(published, tmp_path):
    """A full queue sheds with a typed ``overloaded`` reject; an
    expired request gets a typed ``deadline_exceeded`` — bounded queue
    and bounded latency, never silence."""
    rep, _ = make_replica(published, tmp_path, queue_depth=1, max_batch=1)
    slow = threading.Event()
    real_predict = rep._predict

    def slow_predict(params, x):
        if slow.is_set():
            time.sleep(0.4)
        return real_predict(params, x)

    rep._predict = slow_predict
    rep.start()
    try:
        make_input = sample_input(published)
        inputs = make_input(0)
        # warm the bucket so the stall below is the sleep, not compile
        assert raw_request(rep.bound_port,
                           {"id": 0, "inputs": inputs})["status"] == "ok"
        slow.set()
        results: list[dict] = []

        def fire(i):
            results.append(raw_request(rep.bound_port,
                                       {"id": i, "inputs": inputs}))

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(1, 9)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        statuses = {}
        for r in results:
            key = (r["status"], r.get("reason"))
            statuses[key] = statuses.get(key, 0) + 1
        assert statuses.get(("rejected", "overloaded"), 0) >= 1, statuses
        assert statuses.get(("ok", None), 0) >= 1, statuses
        assert len(results) == 8  # every request got SOME terminal answer
        # expired-in-queue: occupy the batcher with a slow in-flight
        # batch, then queue a request whose deadline is shorter than
        # that batch — it must come back as a TYPED deadline reject
        occupier = threading.Thread(target=fire, args=(98,))
        occupier.start()
        time.sleep(0.1)  # the occupier is now inside the slow predict
        out = raw_request(rep.bound_port, {"id": 99, "inputs": inputs,
                                           "deadline_ms": 1})
        occupier.join(timeout=30)
        assert out == {"id": 99, "status": "rejected",
                       "reason": "deadline_exceeded",
                       "model_step": out["model_step"]}
    finally:
        rep.stop()


def test_serve_graceful_stop_sheds_typed(published, tmp_path):
    """Stopping a replica drains its queue with ``shutting_down``
    rejects — the zero-drop contract holds through teardown."""
    rep, _ = make_replica(published, tmp_path, max_batch=1)
    hold = threading.Event()
    real_predict = rep._predict

    def gated(params, x):
        hold.wait(timeout=5)
        return real_predict(params, x)

    rep._predict = gated
    rep.start()
    try:
        make_input = sample_input(published)
        inputs = make_input(0)
        results: list[dict] = []
        threads = [threading.Thread(
            target=lambda i=i: results.append(
                raw_request(rep.bound_port, {"id": i, "inputs": inputs})))
            for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # let them admit while the batcher is gated
        rep.request_stop()
        hold.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        rep.stop()
    assert len(results) == 4
    assert all(r["status"] in ("ok", "rejected") for r in results)
    rejected = [r for r in results if r["status"] == "rejected"]
    assert all(r["reason"] == "shutting_down" for r in rejected)
    recs = serve_records(rep)
    admits = sum(1 for r in recs if r.get("action") == "admit")
    terminals = sum(1 for r in recs if r.get("action") == "respond"
                    or (r.get("action") == "reject" and r.get("admitted")))
    assert admits == terminals


def test_client_fails_over_and_deadline(published, tmp_path):
    """The round-robin shim retries a dead endpoint onto a live one;
    with nothing alive it returns a typed terminal error instead of
    hanging."""
    from distributedmnist_tpu.servesvc.client import ServeClient
    rep, _ = make_replica(published, tmp_path)
    rep.start()
    try:
        make_input = sample_input(published)
        # endpoint 0 is a dead port (bound then closed), endpoint 1 live
        dead = socket.socket()
        dead.bind(("127.0.0.1", 0))
        dead_port = dead.getsockname()[1]
        dead.close()
        client = ServeClient([("127.0.0.1", dead_port),
                              ("127.0.0.1", rep.bound_port)],
                             deadline_s=10.0, max_attempts=4)
        outs = [client.request(make_input(i), request_id=i)
                for i in range(3)]
        assert all(o["status"] == "ok" for o in outs), outs
        nothing = ServeClient([("127.0.0.1", dead_port)],
                              deadline_s=1.0, max_attempts=3)
        out = nothing.request(make_input(0), request_id=0)
        assert out["status"] == "error"
        assert out["reason"] in ("unavailable", "deadline_exceeded")
    finally:
        rep.stop()


# ---------------------------------------------------------------------------
# protocol hardening (ISSUE 19): dedup cache, deadlines, quarantine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("replayed_by", ["the_same_bytes_sent_again",
                                         "the_clients_retry_after_a_reset"])
def test_dedup_replay_answers_from_cache(published, tmp_path, replayed_by):
    """A replayed request id (the retry after a reset ate the
    response) is answered from the idempotency cache — journaled as a
    ``dedup_hit`` AFTER the one respond, never a second execution.
    Once by hand, and once as it happens: behind a proxy that cuts the
    first response on the wire, after the replica cached it, the
    client's own retry is the replay."""
    rep, _ = make_replica(published, tmp_path)
    rep.start()
    proxy = None
    try:
        make_input = sample_input(published)
        if replayed_by == "the_same_bytes_sent_again":
            payload = {"id": "r-7", "inputs": make_input(7)}
            first = raw_request(rep.bound_port, payload)
            replay = raw_request(rep.bound_port, payload)
            assert first["status"] == "ok"
            # byte-identical outcome: same step, same probs, same id
            assert replay == first
        else:
            from distributedmnist_tpu.launch.netchaos import ChaosProxy
            from distributedmnist_tpu.servesvc.client import ServeClient
            # any classifier response is longer than 100 bytes: the
            # one-shot cut lands inside it
            proxy = ChaosProxy(("127.0.0.1", rep.bound_port),
                               [{"kind": "reset", "after_bytes": 100}],
                               worker=1, seed=0)
            client = ServeClient([("127.0.0.1", proxy.start())],
                                 deadline_s=10.0)
            out = client.request(make_input(7), request_id="r-7")
            assert out["status"] == "ok" and out["retried"] is True
            assert len(out["probs"]) == 10
        assert rep.dedup_hits == 1
        recs = serve_records(rep)
        acts = [(r["action"], r.get("id")) for r in recs
                if r.get("id") == "r-7"]
        assert acts.count(("respond", "r-7")) == 1
        assert acts.count(("admit", "r-7")) == 1
        i_resp = acts.index(("respond", "r-7"))
        assert ("dedup_hit", "r-7") in acts[i_resp:]
    finally:
        if proxy is not None:
            proxy.stop()
        rep.stop()


def test_dedup_cache_bound_evicts_oldest(published, tmp_path):
    """The cache is bounded LRU: past ``dedup_cache_size`` distinct
    ids, the oldest entry is gone and its replay re-executes (a second
    admit+respond, not a hit) — memory stays bounded under churn."""
    rep, _ = make_replica(published, tmp_path, dedup_cache_size=2)
    rep.start()
    try:
        make_input = sample_input(published)
        for i in range(3):  # id 0 evicted when id 2 lands
            raw_request(rep.bound_port,
                        {"id": i, "inputs": make_input(i)})
        out = raw_request(rep.bound_port,
                          {"id": 0, "inputs": make_input(0)})
        assert out["status"] == "ok"
        assert rep.dedup_hits == 0
        recs = serve_records(rep)
        assert sum(1 for r in recs if r.get("action") == "respond"
                   and r.get("id") == 0) == 2
    finally:
        rep.stop()


def test_slowloris_aborted_while_siblings_served(published, tmp_path):
    """A peer trickling a half request (and one sending nothing: the
    half-open case) costs ONE bounded stall of conn_read_timeout_s on
    its own connection thread — journaled ``conn_abort``, no terminal
    owed, and concurrent well-formed requests keep flowing."""
    rep, _ = make_replica(published, tmp_path, conn_read_timeout_s=0.5)
    rep.start()
    try:
        make_input = sample_input(published)
        slow = socket.create_connection(("127.0.0.1", rep.bound_port),
                                        timeout=10.0)
        slow.sendall(b'{"id": 99, "inp')   # never finishes the line
        half_open = socket.create_connection(
            ("127.0.0.1", rep.bound_port), timeout=10.0)
        # while both stalls are pending, the replica still serves
        out = raw_request(rep.bound_port,
                          {"id": 1, "inputs": make_input(1)})
        assert out["status"] == "ok"
        deadline = time.time() + 10.0
        reasons: set = set()
        while len(reasons) < 2 and time.time() < deadline:
            reasons = {r.get("reason") for r in serve_records(rep)
                       if r.get("action") == "conn_abort"}
            time.sleep(0.05)
        assert reasons == {"read_deadline", "half_open"}
        # the aborted sockets are really closed, not leaked
        slow.settimeout(2.0)
        assert slow.recv(4096) == b""
        slow.close()
        half_open.close()
        # no terminal was owed: admit/terminal books still balance
        recs = serve_records(rep)
        admits = sum(1 for r in recs if r.get("action") == "admit")
        responds = sum(1 for r in recs if r.get("action") == "respond")
        assert admits == responds
    finally:
        rep.stop()


def test_client_quarantines_dead_endpoint(published, tmp_path):
    """After a failed attempt the client benches that endpoint with
    seeded jittered backoff — follow-up requests go straight to the
    live sibling (attempts == 1) instead of re-dialing the corpse —
    and the outcome records carry the attempt books."""
    from distributedmnist_tpu.servesvc.client import ServeClient
    rep, _ = make_replica(published, tmp_path)
    rep.start()
    try:
        make_input = sample_input(published)
        dead = socket.socket()
        dead.bind(("127.0.0.1", 0))
        dead_port = dead.getsockname()[1]
        dead.close()
        client = ServeClient([("127.0.0.1", dead_port),
                              ("127.0.0.1", rep.bound_port)],
                             deadline_s=10.0, max_attempts=4,
                             quarantine_s=30.0, seed=3)
        out = client.request(make_input(0), request_id=0)
        assert out["status"] == "ok"
        if out["attempts"] > 1:     # the dead endpoint was tried first
            assert out["retried"] is True
        assert client.quarantined() == [("127.0.0.1", dead_port)]
        # benched: the next requests never pay the dead dial again
        for i in range(1, 4):
            out = client.request(make_input(i), request_id=i)
            assert out["status"] == "ok" and out["attempts"] == 1
            assert out["retried"] is False
    finally:
        rep.stop()


# ---------------------------------------------------------------------------
# quantized precision tiers (serve.precision_tier + the quant sidecar)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def quant_published(tmp_path_factory, synthetic_datasets):
    """Like ``published`` but the trainer also writes int8 sidecars —
    the tier-preference scenarios publish from here."""
    staging = tmp_path_factory.mktemp("qstaging")
    cfg = base_config(train={"train_dir": str(staging), "max_steps": 30,
                             "log_every_steps": 10,
                             "save_interval_steps": 10},
                      quant={"publish_tiers": "int8",
                             "calibration_examples": 32})
    from distributedmnist_tpu.train.loop import Trainer
    Trainer(cfg, datasets=synthetic_datasets).run()
    return {"staging": staging, "cfg": cfg}


def publish_quant_step(staging: Path, serve_dir: Path, step: int,
                       with_sidecar: bool = True,
                       tear_sidecar: bool = False) -> None:
    """publish_step plus the quant sidecar family; ``tear_sidecar``
    truncates the sidecar AFTER the copy (its digest stays intact) —
    the torn-sidecar scenario digest verification must refuse.

    The sidecar lands BEFORE the pointer flip (publish_step): a
    fast-polling follower that reads the pointer the instant it moves
    must find the sidecar already there, or this test races — a
    replica that consumes the step through the absent-sidecar fallback
    never re-reads it (by design; journaled), so the expected tier
    would be timing-dependent."""
    if with_sidecar:
        qname = f"ckpt-{step:08d}.quant.msgpack"
        serve_dir.mkdir(parents=True, exist_ok=True)
        shutil.copy2(staging / qname, serve_dir / qname)
        shutil.copy2(staging / (qname + ".sha256"),
                     serve_dir / (qname + ".sha256"))
        if tear_sidecar:
            data = (serve_dir / qname).read_bytes()
            (serve_dir / qname).write_bytes(
                data[:max(1, len(data) // 2)])
    publish_step(staging, serve_dir, step)


def test_int8_tier_preferred_and_meta_reports_it(quant_published,
                                                 tmp_path):
    """A replica on precision_tier=int8 installs the sidecar tier, the
    weight_swap journals tier + source identity, responses carry the
    tier, and the meta probe reports active tier + source digest (what
    loadgen artifacts record a sweep actually measured)."""
    from distributedmnist_tpu.core.config import ServeConfig
    from distributedmnist_tpu.servesvc.server import ServingReplica
    from distributedmnist_tpu.train import checkpoint as ckpt
    serve_src = tmp_path / "publish"
    publish_quant_step(quant_published["staging"], serve_src, 10)
    rep = ServingReplica(serve_src, serve_dir=tmp_path / "replica",
                         scfg=ServeConfig(poll_secs=0.05,
                                          precision_tier="int8"),
                         cfg=quant_published["cfg"])
    rep.start()
    try:
        make_input = sample_input(quant_published)
        out = raw_request(rep.bound_port, {"id": 1,
                                           "inputs": make_input(1)})
        assert out["status"] == "ok" and out["model_step"] == 10
        assert out["tier"] == "int8"
        meta = raw_request(rep.bound_port, {"meta": True})
        assert meta["precision_tier"] == "int8"
        assert meta["active_tier"] == "int8"
        src = ckpt.read_quant_sidecar(serve_src, 10)["meta"][
            "source_params_digest"]
        assert meta["tier_source_digest"] == src
        assert meta["model_digest"] == ckpt.quant_sidecar_digest(
            serve_src, 10)
    finally:
        rep.stop()
    swaps = [r for r in serve_records(rep)
             if r.get("action") == "weight_swap"]
    assert [(s["step"], s["tier"], s["source_artifact"])
            for s in swaps] == [(10, "int8",
                                 "ckpt-00000010.quant.msgpack")]
    assert swaps[0]["source_digest"] == src


def test_torn_sidecar_falls_back_to_fp32_without_wedge(quant_published,
                                                       tmp_path):
    """Satellite: a TORN sidecar journals
    ``follow_quant_sidecar_fallback`` and that publish serves full
    precision — the follower cursor advances (no skip-loop re-read
    wedge), and the NEXT good publish upgrades back to int8."""
    from distributedmnist_tpu.core.config import ServeConfig
    from distributedmnist_tpu.servesvc.server import ServingReplica
    serve_src = tmp_path / "publish"
    publish_quant_step(quant_published["staging"], serve_src, 10,
                       tear_sidecar=True)
    rep = ServingReplica(serve_src, serve_dir=tmp_path / "replica",
                         scfg=ServeConfig(poll_secs=0.05,
                                          precision_tier="int8"),
                         cfg=quant_published["cfg"])
    rep.start()
    try:
        make_input = sample_input(quant_published)
        out = raw_request(rep.bound_port, {"id": 1,
                                           "inputs": make_input(1)})
        assert out["status"] == "ok" and out["model_step"] == 10
        assert out["tier"] == "fp32"  # the fallback, never torn bytes
        # the cursor CONSUMED step 10 through the fp32 path — several
        # polls later there is still exactly ONE fallback journaled
        time.sleep(0.4)
        recs = serve_records(rep)
        fallbacks = [r for r in recs
                     if r.get("action") == "follow_quant_sidecar_fallback"]
        assert len(fallbacks) == 1, fallbacks
        assert fallbacks[0]["step"] == 10
        assert "CheckpointCorruptError" in fallbacks[0]["reason"]
        # a sidecar-less publish falls back too (journaled as absent)…
        publish_quant_step(quant_published["staging"], serve_src, 20,
                           with_sidecar=False)
        deadline = time.time() + 30
        while rep.model_step < 20 and time.time() < deadline:
            time.sleep(0.05)
        assert rep.model_step == 20 and rep.model_tier == "fp32"
        # …and the next GOOD sidecar restores the quantized tier
        publish_quant_step(quant_published["staging"], serve_src, 30)
        while rep.model_step < 30 and time.time() < deadline:
            time.sleep(0.05)
        assert rep.model_step == 30 and rep.model_tier == "int8"
    finally:
        rep.stop()
    recs = serve_records(rep)
    swaps = [(r["step"], r["tier"]) for r in recs
             if r.get("action") == "weight_swap"]
    assert swaps == [(10, "fp32"), (20, "fp32"), (30, "int8")]
    reasons = [r["reason"].split(":")[0] for r in recs
               if r.get("action") == "follow_quant_sidecar_fallback"]
    assert reasons == ["CheckpointCorruptError", "sidecar_absent"]


# ---------------------------------------------------------------------------
# serving chaos schedule grammar
# ---------------------------------------------------------------------------

@pytest.mark.tier1
def test_chaos_serving_tier_payload_wiring():
    """serve_precision_tiers pins replica tiers AND arms the publisher
    with the matching quant.publish_tiers; tier-less configs keep the
    byte-identical historical payloads."""
    from distributedmnist_tpu.launch.chaos import ChaosConfig
    cfg = ChaosConfig(payload="serving", serve_replicas=2,
                      serve_precision_tiers=("int8",))
    cmds = cfg.resolved_worker_commands()
    assert "--precision-tier int8" in cmds["1"]
    assert "--precision-tier" not in cmds["2"]
    assert "quant.publish_tiers=int8" in cfg.resolved_train_command()
    plain = ChaosConfig(payload="serving", serve_replicas=2)
    assert "--precision-tier" not in plain.resolved_worker_commands()["1"]
    assert "quant.publish_tiers" not in plain.resolved_train_command()
    # a typo'd tier fails typed at config build, naming the valid set —
    # not as a replica crash-looping against its restart budget
    from distributedmnist_tpu.launch.cluster import ClusterError
    with pytest.raises(ClusterError, match="in8.*valid tiers"):
        ChaosConfig(payload="serving", serve_precision_tiers=("in8",))


@pytest.mark.tier1
def test_serving_schedule_grammar_and_determinism():
    from distributedmnist_tpu.launch.chaos import generate_serving_schedule
    a = generate_serving_schedule(7, 3, [1, 2], (5, 40), (6, 20))
    b = generate_serving_schedule(7, 3, [1, 2], (5, 40), (6, 20))
    assert a == b  # deterministic in (seed, trial)
    kinds = [(f.kind, f.worker) for f in a.faults]
    # always ≥1 serve-replica kill and EXACTLY one publisher corrupt
    assert any(k == "kill" and w in (1, 2) for k, w in kinds)
    assert kinds.count(("corrupt", 0)) == 1
    # the corrupt is UNPAIRED (no publisher kill in serving mode)
    assert ("kill", 0) not in kinds
    for f in a.faults:
        if f.kind in ("kill", "hang", "stall"):
            assert f.worker in (1, 2)
            assert 5 <= f.step <= 40
        if f.kind == "corrupt":
            assert 6 <= f.step <= 20
    c = generate_serving_schedule(8, 3, [1, 2], (5, 40), (6, 20))
    assert c != a  # seed actually varies the draw


# ---------------------------------------------------------------------------
# the three serving replay invariants over handcrafted artifacts
# ---------------------------------------------------------------------------

def _write_jsonl(path: Path, records: list[dict]) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text("".join(json.dumps(r) + "\n" for r in records))


def _serving_trial(tmp_path, *, drop_request=False, vanish_admit=False,
                   faulted=False, swap_after_tear=False,
                   backwards_swap=False) -> Path:
    trial = tmp_path / "trial"
    issues = [{"event": "load", "action": "issue", "id": i, "time": 1.0 + i}
              for i in range(3)]
    outcomes = [{"event": "load", "action": "outcome", "id": i,
                 "status": "ok", "latency_ms": 5.0, "time": 2.0 + i}
                for i in range(3)]
    if drop_request:
        outcomes = outcomes[:-1]
    _write_jsonl(trial / "loadgen.jsonl", issues + outcomes)
    journal = [{"event": "fault", "action": "corrupt_latest_checkpoint",
                "worker": 0, "target": "ckpt-00000020.msgpack",
                "ts": 100.0}]
    if faulted:
        journal.append({"event": "fault", "action": "kill_worker",
                        "worker": 1, "ts": 50.0})
    _write_jsonl(trial / "command_journal.jsonl", journal)
    serve = [{"event": "serve", "action": "weight_swap", "step": 10,
              "digest": "d", "time": 90.0},
             {"event": "serve", "action": "admit", "id": 0, "time": 91.0},
             {"event": "serve", "action": "respond", "id": 0,
              "model_step": 10, "time": 91.5}]
    if vanish_admit:
        serve.append({"event": "serve", "action": "admit", "id": 1,
                      "time": 92.0})  # no terminal for it
    if swap_after_tear:
        serve.append({"event": "serve", "action": "weight_swap",
                      "step": 20, "digest": "d2", "time": 101.0})
    if backwards_swap:
        serve.append({"event": "serve", "action": "weight_swap",
                      "step": 5, "digest": "d0", "time": 102.0})
    _write_jsonl(trial / "worker1" / "serve_log.jsonl", serve)
    (trial / "worker1" / "train_log.jsonl").write_text("")
    return trial


def _check(trial) -> dict:
    from distributedmnist_tpu.obsv.invariants import check_serving
    from distributedmnist_tpu.obsv.report import load_jsonl
    journal = load_jsonl(trial / "command_journal.jsonl")
    violations, applicable, workers, decode_applicable = check_serving(
        trial, {"serve_workers": [1]}, journal)
    return {"violations": violations, "applicable": applicable,
            "workers": workers, "decode_applicable": decode_applicable,
            "by_inv": {v.invariant for v in violations}}


@pytest.mark.tier1
def test_serving_invariants_clean_pass(tmp_path):
    got = _check(_serving_trial(tmp_path))
    assert got["applicable"] and got["workers"] == {1}
    assert got["violations"] == []


@pytest.mark.tier1
def test_serving_invariant_catches_dropped_request(tmp_path):
    got = _check(_serving_trial(tmp_path, drop_request=True))
    assert "serve_outcomes" in got["by_inv"]


@pytest.mark.tier1
def test_serving_invariant_vanished_admit_needs_fault_exemption(tmp_path):
    # an admitted request with no terminal outcome on an UNFAULTED
    # replica is a violation ...
    got = _check(_serving_trial(tmp_path, vanish_admit=True))
    assert "serve_outcomes" in got["by_inv"]
    # ... but on a replica the run killed, the in-flight loss is the
    # fault working (the CLIENT side still reached its outcome)
    got = _check(_serving_trial(tmp_path, vanish_admit=True, faulted=True))
    assert "serve_outcomes" not in got["by_inv"]


@pytest.mark.tier1
def test_serving_invariant_swap_after_tear_fails(tmp_path):
    got = _check(_serving_trial(tmp_path, swap_after_tear=True))
    assert "serve_digest" in got["by_inv"]


@pytest.mark.tier1
def test_serving_invariant_monotone(tmp_path):
    got = _check(_serving_trial(tmp_path, backwards_swap=True))
    assert "serve_monotone" in got["by_inv"]


@pytest.mark.tier1
def test_serving_invariants_skip_for_train_trials(tmp_path):
    from distributedmnist_tpu.obsv.invariants import check_serving
    (tmp_path / "t").mkdir()
    violations, applicable, workers, decode_applicable = check_serving(
        tmp_path / "t", {}, [])
    assert not applicable and not violations and not workers
    assert not decode_applicable


# ---------------------------------------------------------------------------
# mixed-payload cluster + target_worker supervision
# ---------------------------------------------------------------------------

def test_worker_commands_and_target_worker(tmp_path):
    """A mixed roster runs per-worker payloads, and supervision counts
    target progress from the named worker only — worker 1 races far
    past the target while slow worker 0 is what the run waits for."""
    from distributedmnist_tpu.launch.cluster import (LocalClusterConfig,
                                                     LocalProcessCluster)
    from distributedmnist_tpu.launch.exec import CommandExecutor, RetryPolicy
    from distributedmnist_tpu.launch.supervisor import (ClusterSupervisor,
                                                        SupervisorConfig)
    loop = ('i=0; while [ $i -lt {n} ]; do i=$((i+1)); '
            'echo "{{\\"step\\": $i}}" >> train_log.jsonl; '
            'sleep {dt}; done; sleep 60')
    cfg = LocalClusterConfig(
        name="mixed", num_workers=2, workdir=str(tmp_path),
        train_command=loop.format(n=12, dt="0.25"),
        worker_commands={"1": loop.format(n=500, dt="0.01")})
    cluster = LocalProcessCluster(cfg, CommandExecutor(
        journal=cfg.root / "command_journal.jsonl",
        retry=RetryPolicy(max_attempts=1)))
    cluster.create()
    try:
        cluster.run_train()
        sup = ClusterSupervisor(cluster, SupervisorConfig(quorum=1))
        t0 = time.monotonic()
        got = sup.supervise_until_step(10, poll_secs=0.2,
                                       timeout_secs=60.0,
                                       target_worker=0)
        elapsed = time.monotonic() - t0
        # worker 1 blew past 10 almost immediately; the run returned
        # only once WORKER 0 (0.25 s/step) actually got there
        assert got["step"] >= 10
        assert elapsed >= 1.5, elapsed
        prog = cluster.worker_progress()
        # the fast payload really ran ITS OWN command, well past the
        # target worker 0 was held to (loose bound: 1-core box)
        assert prog[1] > 3 * got["step"], prog
    finally:
        cluster.kill_all()
        cluster.exec.close()


# ---------------------------------------------------------------------------
# the acceptance scenario: a seeded serving-mode chaos trial
# ---------------------------------------------------------------------------

@pytest.mark.slow  # boots a publisher + 2 serving replicas + reference (~3 min)
def test_serving_chaos_trial_end_to_end(tmp_path):
    """Replica kill + corrupt published checkpoint under live load:
    the trial completes with all three serving invariants passing and
    the load generator reporting zero dropped requests."""
    from distributedmnist_tpu.launch.chaos import ChaosConfig, run_campaign
    cfg = ChaosConfig(name="servetrial", workdir=str(tmp_path),
                      payload="serving", trials=1, seed=0,
                      until_step=60, save_interval_steps=10,
                      serve_replicas=2, shrink=False,
                      trial_timeout_s=420.0)
    summary = run_campaign(cfg)
    assert summary["trials"] == 1
    assert summary["all_green"], summary
    inv = summary["invariants"]
    for name in ("serve_outcomes", "serve_digest", "serve_monotone"):
        assert inv[name]["pass"] == 1, (name, inv)
    sv = summary["serving"]
    assert sv["issued"] > 0 and sv["dropped"] == 0, sv
    assert summary["faults"]["fired"] >= 1, summary["faults"]


@pytest.mark.slow  # boots a publisher + 2 decode replicas + proxies (~4 min)
def test_network_chaos_trial_end_to_end(tmp_path):
    """ISSUE 19 acceptance: transport faults (chaos proxies) under
    live decode load — every scheduled net fault fires, the mandatory
    reset cuts a token stream MID-generation, the partition opens under
    live traffic, zero requests are dropped, and invariant 13 holds
    the exactly-once books."""
    import json as _json
    from distributedmnist_tpu.launch.chaos import ChaosConfig, run_campaign
    cfg = ChaosConfig(name="nettrial", workdir=str(tmp_path),
                      payload="serving", trials=1, seed=0,
                      until_step=60, save_interval_steps=10,
                      serve_replicas=2, serve_decode=True, network=True,
                      shrink=False, trial_timeout_s=420.0)
    summary = run_campaign(cfg)
    assert summary["trials"] == 1
    assert summary["all_green"], summary
    inv = summary["invariants"]
    assert inv["net_faults"]["pass"] == 1, inv
    for name in ("serve_outcomes", "serve_digest", "serve_monotone",
                 "decode_swap"):
        assert inv[name]["pass"] == 1, (name, inv)
    sv = summary["serving"]
    assert sv["issued"] > 0 and sv["dropped"] == 0, sv
    assert summary["faults"]["never_fired"] == 0, summary["faults"]
    net = summary["net"]
    assert net["fired"] >= 2, net
    assert net["faults_by_kind"].get("net_reset") == 1, net
    assert net["faults_by_kind"].get("net_partition") == 1, net
    # the reset's journal record proves the cut was MID-stream (bytes
    # had already flowed) and the partition cut LIVE connections
    recs = [_json.loads(l) for l in
            (tmp_path / "nettrial" / "trial000"
             / "command_journal.jsonl").read_text().splitlines()]
    rst = [r for r in recs if r.get("action") == "net_reset"]
    assert rst and rst[0]["mid_stream"] and rst[0]["bytes_passed"] > 0
