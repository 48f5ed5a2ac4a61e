"""The load generator against a stand-in replica that speaks the decode
wire protocol: it runs as a process of its own, never imports jax, sends
an open loop on its schedule and a closed loop one request per client."""

import json
import socket
import subprocess
import sys
import threading
import time

import pytest

from benchmark.lib import cell as cell_lib, serve_metrics

LOADGEN = cell_lib.BENCH_DIR / "lib" / "loadgen.py"


class FakeReplica:
    """One connection per request; a token line every ``gap_s``; then
    the terminal line; then the connection is closed."""

    def __init__(self, gap_s=0.01, reject=()):
        self.gap_s, self.reject = gap_s, set(reject)
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(64)
        self.port = self.sock.getsockname()[1]
        self.in_flight, self.max_in_flight = 0, 0
        self.lock = threading.Lock()
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _done(self):
        with self.lock:
            self.in_flight -= 1

    def _serve(self, conn):
        with self.lock:
            self.in_flight += 1
            self.max_in_flight = max(self.max_in_flight, self.in_flight)
        try:
            req = json.loads(conn.makefile().readline())
            if req["id"] in self.reject:
                self._done()
                conn.sendall((json.dumps(
                    {"id": req["id"], "status": "rejected",
                     "reason": "overloaded"}) + "\n").encode())
                return
            toks = []
            for i in range(req["max_tokens"]):
                time.sleep(self.gap_s)
                toks.append(i % 7)
                conn.sendall((json.dumps(
                    {"id": req["id"], "stream": "token", "token": i % 7,
                     "index": i, "model_step": 0}) + "\n").encode())
            self._done()   # before the line that lets the client go on
            conn.sendall((json.dumps(
                {"id": req["id"], "status": "ok", "tokens": toks,
                 "finish_reason": "max_tokens", "model_step": 0,
                 "started_step": 0}) + "\n").encode())
        except OSError:
            self._done()              # the generator cut the request off
        finally:
            conn.close()

    def close(self):
        self.sock.close()


def _req(rid, max_tokens, **kw):
    return {"id": rid, "prompt": [1, 2, 3], "max_tokens": max_tokens,
            "temperature": 0.0, "deadline_ms": 5000.0, **kw}


def _run(tmp_path, plan):
    plan_path, out_path = tmp_path / "plan.json", tmp_path / "out.json"
    plan_path.write_text(json.dumps(plan))
    got = subprocess.run([sys.executable, str(LOADGEN), str(plan_path),
                          str(out_path)], capture_output=True, text=True,
                         timeout=60)
    started = json.loads(got.stdout.splitlines()[0]) if got.stdout else {}
    load = json.loads(out_path.read_text()) if out_path.exists() else None
    return got, started, load


def test_the_generator_is_a_process_that_never_imports_jax():
    src = LOADGEN.read_text()
    assert "jax" not in [w for line in src.splitlines()
                         if line.startswith(("import ", "from "))
                         for w in line.replace(",", " ").split()]
    probe = ("import runpy, sys; sys.argv = ['x']\n"
             "try:\n    runpy.run_path(%r, run_name='not_main')\n"
             "finally:\n    print('jax' in sys.modules, "
             "'numpy' in sys.modules)" % str(LOADGEN))
    out = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                         text=True, timeout=60).stdout.split()
    assert out == ["False", "False"]


def test_open_loop_sends_on_schedule_and_times_from_due(tmp_path):
    replica = FakeReplica(gap_s=0.02)
    try:
        reqs = [_req("o0", 3, due_s=-0.2), _req("o1", 3, due_s=0.0),
                _req("o2", 40, due_s=0.1), _req("o3", 3, due_s=0.45)]
        got, started, load = _run(tmp_path, {
            "host": "127.0.0.1", "port": replica.port, "mode": "open",
            "warmup": [_req("w0", 2)], "warmup_timeout_s": 10,
            "warmup_s": 0.3, "seconds": 0.5, "grace_s": 2.0,
            "requests": reqs})
    finally:
        replica.close()
    assert got.returncode == 0, got.stderr
    assert started["event"] == "load_start"
    assert started["window_end"] - started["window_start"] == pytest.approx(
        0.5)
    by_id = {r["id"]: r for r in load["records"]}
    assert by_id["w0"]["warmup"] and by_id["w0"]["terminal"]["status"] == "ok"
    ws = load["window_start"]
    for r in reqs:
        rec = by_id[r["id"]]
        assert rec["due"] == pytest.approx(ws + r["due_s"], abs=1e-6)
        assert 0 <= rec["sent"] - rec["due"] < 0.2   # sent when due
    # o2 was still streaming when the run ended: cut off, and healthy
    assert by_id["o2"]["aborted"] and by_id["o2"]["terminal"] is None
    assert 0 < len(by_id["o2"]["stream"]) < 40
    # o3 was due inside the window and got its first token in the grace
    assert by_id["o3"]["stream"]
    s = serve_metrics.summarize(load, vocab=7)
    assert s["failed"] == 0 and s["attempted"] == 4
    assert s["ttft_samples"] == 3 and s["ttft_missing"] == 0
    assert 15 < s["ttft_ms_p50"] < 150 and 10 < s["itl_ms_p50"] < 100


def test_closed_loop_keeps_one_request_per_client_in_flight(tmp_path):
    replica = FakeReplica(gap_s=0.005, reject={"c1-1"})
    try:
        queues = [[_req(f"c{c}-{j}", 4) for j in range(50)]
                  for c in range(3)]
        got, _, load = _run(tmp_path, {
            "host": "127.0.0.1", "port": replica.port, "mode": "closed",
            "warmup": [], "warmup_timeout_s": 10, "warmup_s": 0.1,
            "seconds": 0.4, "grace_s": 0.0, "queues": queues})
    finally:
        replica.close()
    assert got.returncode == 0, got.stderr
    assert replica.max_in_flight == 3
    recs = load["records"]
    for c in range(3):
        mine = [r for r in recs if r["client"] == c]
        assert len(mine) > 3
        # the next request goes out when the last one ended, not before
        for prev, nxt in zip(mine, mine[1:]):
            assert nxt["due"] >= prev["ended"] - 1e-3
    s = serve_metrics.summarize(load, vocab=7)
    assert s["failed"] == 1 and "overloaded" in s["failures"]["c1-1"]
    assert s["cut_off_at_end"] <= 3 and s["tokens_in_window"] > 0


def test_a_failed_warmup_stops_the_run(tmp_path):
    replica = FakeReplica(reject={"w0"})
    try:
        got, started, load = _run(tmp_path, {
            "host": "127.0.0.1", "port": replica.port, "mode": "closed",
            "warmup": [_req("w0", 2)], "warmup_timeout_s": 5,
            "warmup_s": 0.1, "seconds": 0.1, "grace_s": 0, "queues": [[]]})
    finally:
        replica.close()
    assert got.returncode == 1 and load is None
    assert started["event"] == "warmup_failed"
