"""The load generator: a process of its own that never imports jax (the
parent holds the chip, and real clients are other processes: client
threads must not share the decode loop's interpreter lock). One thread,
one ``selectors`` loop over every open connection, standard library
only. It speaks the decode replica's documented wire protocol itself
(``servesvc/decode.py``: one connection per request, one JSON line per
streamed token, one terminal line) and stamps every line with the wall
clock as it is read.

    python benchmark/lib/loadgen.py PLAN.json OUT.json

The plan (written by the serve drivers from ``lib/traffic.py``):
``host``, ``port``, ``mode`` (``open`` | ``closed``), ``warmup`` (requests
sent one after another before the load, to compile every shape),
``requests`` with ``due_s`` relative to the window's start (open) or
``queues`` of requests per client (closed), ``warmup_s`` (load offered
before the window opens), ``seconds`` (the window) and ``grace_s`` (how
long after the window a request due inside it may still wait for its
first token). It prints one JSON line when the load starts, giving the
window's wall-clock bounds, and writes raw per-request records to
``OUT.json``; every metric is computed from those by
``lib/serve_metrics.py`` in the parent.

An open loop sends each request when it is DUE, whatever the replica is
doing, and records due and send times both: latencies run from the due
time, and send minus due is how late this generator ran. A closed loop
(copied in behaviour from ``servesvc/loadgen.py``'s ``run_load``) keeps
one request in flight per client and issues the next when the last one
reached its terminal line."""

from __future__ import annotations

import errno
import json
import selectors
import socket
import sys
import time


class _Conn:
    """One request on its own connection."""

    __slots__ = ("req", "client", "due", "sock", "out", "buf", "rec")

    def __init__(self, req: dict, client: int | None, due: float):
        self.req, self.client, self.due = req, client, due
        self.sock: socket.socket | None = None
        self.out = (json.dumps({k: v for k, v in req.items()
                                if k != "due_s"}) + "\n").encode()
        self.buf = b""
        self.rec = {"id": req["id"], "client": client, "due": due,
                    "prompt_len": len(req["prompt"]),
                    "max_tokens": req["max_tokens"], "sent": None,
                    "stream": [], "terminal": None, "error": None,
                    "ended": None, "aborted": False}


class LoadGenerator:
    def __init__(self, host: str, port: int):
        self.addr = (host, port)
        self.sel = selectors.DefaultSelector()
        self.live: set[_Conn] = set()
        self.records: list[dict] = []

    # -- one connection's life -------------------------------------------

    def start(self, conn: _Conn) -> None:
        self.records.append(conn.rec)
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setblocking(False)
        conn.sock = s
        rc = s.connect_ex(self.addr)
        if rc not in (0, errno.EINPROGRESS):
            self._end(conn, error=f"connect: {errno.errorcode.get(rc, rc)}")
            return
        self.live.add(conn)
        self.sel.register(s, selectors.EVENT_WRITE, conn)

    def _end(self, conn: _Conn, error: str | None = None,
             aborted: bool = False) -> None:
        conn.rec["ended"] = time.time()
        conn.rec["error"] = error
        conn.rec["aborted"] = aborted
        if conn.sock is not None:
            try:
                self.sel.unregister(conn.sock)
            except (KeyError, ValueError):
                pass
            conn.sock.close()
            conn.sock = None
        self.live.discard(conn)

    def _on_writable(self, conn: _Conn) -> None:
        err = conn.sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
        if err:
            self._end(conn, error=f"connect: {errno.errorcode.get(err, err)}")
            return
        try:
            sent = conn.sock.send(conn.out)
        except BlockingIOError:
            return
        except OSError as e:
            self._end(conn, error=f"send: {e}")
            return
        conn.out = conn.out[sent:]
        if not conn.out:
            conn.rec["sent"] = time.time()
            self.sel.modify(conn.sock, selectors.EVENT_READ, conn)

    def _on_readable(self, conn: _Conn) -> bool:
        """Read what is there. True when the request reached its end."""
        try:
            chunk = conn.sock.recv(1 << 16)
        except BlockingIOError:
            return False
        except OSError as e:
            self._end(conn, error=f"recv: {e}")
            return True
        now = time.time()
        if not chunk:
            self._end(conn, error=None if conn.rec["terminal"] is not None
                      else "closed before a terminal line")
            return True
        conn.buf += chunk
        *lines, conn.buf = conn.buf.split(b"\n")
        for line in lines:
            if not line:
                continue
            try:
                msg = json.loads(line)
            except ValueError:
                self._end(conn, error="unparsable line")
                return True
            if msg.get("stream") == "token":
                conn.rec["stream"].append(
                    [now, msg.get("index"), msg.get("token")])
            elif "stream" in msg:
                conn.rec["stream"].append([now, msg["stream"], None])
            else:
                toks = msg.get("tokens")
                conn.rec["terminal"] = {
                    "t": now, "status": msg.get("status"),
                    "reason": msg.get("reason"),
                    "finish_reason": msg.get("finish_reason"),
                    "tokens": toks if isinstance(toks, list) else None}
                self._end(conn)
                return True
        return False

    def poll(self, timeout: float) -> list[_Conn]:
        """Serve ready connections; returns those that just ended."""
        ended = []
        for key, mask in self.sel.select(max(0.0, timeout)):
            conn = key.data
            if conn.sock is None:
                continue
            if mask & selectors.EVENT_WRITE:
                self._on_writable(conn)
            elif self._on_readable(conn):
                ended.append(conn)
        return ended

    def abort_all(self) -> None:
        for conn in list(self.live):
            self._end(conn, aborted=True)

    # -- phases -----------------------------------------------------------

    def run_warmup(self, requests: list[dict], timeout_s: float) -> None:
        """One request after another, each to its end: the first use of
        a shape compiles, which must not happen under load."""
        for req in requests:
            conn = _Conn(req, None, time.time())
            conn.rec["warmup"] = True
            self.start(conn)
            limit = time.time() + timeout_s
            while conn in self.live and time.time() < limit:
                self.poll(0.5)
            if conn in self.live:
                self._end(conn, error="warm-up request timed out")

    def run_open(self, requests: list[dict], t_window: float,
                 seconds: float, grace_s: float) -> None:
        pending = sorted(requests, key=lambda r: r["due_s"], reverse=True)
        t_end = t_window + seconds
        in_window = []
        while pending or time.time() < t_end:
            now = time.time()
            while pending and t_window + pending[-1]["due_s"] <= now:
                req = pending.pop()
                conn = _Conn(req, None, t_window + req["due_s"])
                if req["due_s"] >= 0:
                    in_window.append(conn)
                self.start(conn)
            horizon = (t_window + pending[-1]["due_s"] if pending else t_end)
            self.poll(min(0.05, max(0.0, horizon - time.time())))
        # a request due inside the window keeps its claim on a first
        # token for grace_s more; nothing new is sent
        limit = t_end + grace_s
        while time.time() < limit and any(
                c in self.live and not c.rec["stream"] for c in in_window):
            self.poll(0.05)
        self.abort_all()

    def run_closed(self, queues: list[list[dict]], t_end: float) -> None:
        cursors = [0] * len(queues)

        def issue(client: int) -> None:
            i = cursors[client]
            if i >= len(queues[client]):
                self.records.append({"id": f"c{client}-exhausted",
                                     "client": client, "exhausted": True})
                return
            cursors[client] += 1
            self.start(_Conn(queues[client][i], client, time.time()))

        for c in range(len(queues)):
            issue(c)
        while time.time() < t_end:
            for conn in self.poll(min(0.05, t_end - time.time())):
                if time.time() < t_end:
                    issue(conn.client)
        self.abort_all()


def main(argv: list[str]) -> int:
    plan_path, out_path = argv
    with open(plan_path, encoding="utf-8") as f:
        plan = json.load(f)
    gen = LoadGenerator(plan["host"], int(plan["port"]))
    gen.run_warmup(plan.get("warmup", []), float(plan["warmup_timeout_s"]))
    bad = [r for r in gen.records if r["error"] or not r["terminal"]
           or r["terminal"]["status"] != "ok"]
    if bad:
        print(json.dumps({"event": "warmup_failed", "records": bad[:3]}),
              flush=True)
        return 1
    t0 = time.time() + 0.25
    t_window = t0 + float(plan["warmup_s"])
    seconds = float(plan["seconds"])
    print(json.dumps({"event": "load_start", "t0": t0,
                      "window_start": t_window,
                      "window_end": t_window + seconds}), flush=True)
    time.sleep(max(0.0, t0 - time.time()))
    if plan["mode"] == "open":
        gen.run_open(plan["requests"], t_window, seconds,
                     float(plan["grace_s"]))
    elif plan["mode"] == "closed":
        gen.run_closed(plan["queues"], t_window + seconds)
    else:
        raise ValueError(f"unknown mode {plan['mode']!r}")
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump({"mode": plan["mode"], "window_start": t_window,
                   "window_end": t_window + seconds,
                   "ended": time.time(), "records": gen.records}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
