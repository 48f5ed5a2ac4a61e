"""From the load generator's raw records to the serving numbers. All
times are the client's clock; a latency runs from when the request was
DUE, so a stall counts against every request it delayed."""

from __future__ import annotations

import math

from .stats import percentile


def _token_times(rec: dict) -> list[float]:
    return [t for t, index, _ in rec["stream"] if isinstance(index, int)]


def check_request(rec: dict, vocab: int) -> str | None:
    """Why this request counts as failed, or None. A request the
    generator itself cut off at the end of the run is judged on what it
    had streamed until then."""
    if rec.get("exhausted"):
        return "client ran out of requests"
    if rec["error"]:
        return rec["error"]
    if any(not isinstance(index, int) for _, index, _ in rec["stream"]):
        return "restart marker in the stream"
    if [index for _, index, _ in rec["stream"]] != list(
            range(len(rec["stream"]))):
        return "token indices are not 0, 1, 2, ..."
    streamed = [tok for _, _, tok in rec["stream"]]
    if any(not isinstance(t, int) or not 0 <= t < vocab for t in streamed):
        return "token outside the vocabulary"
    term = rec["terminal"]
    if term is None:
        return None if rec["aborted"] else "no terminal line"
    if term["status"] != "ok":
        return f"{term['status']}: {term['reason']}"
    if term["finish_reason"] != "max_tokens":
        return f"finished by {term['finish_reason']}"
    if term["tokens"] != streamed or len(streamed) != rec["max_tokens"]:
        return (f"{len(streamed)} tokens streamed, "
                f"{len(term['tokens'] or [])} in the terminal line, "
                f"{rec['max_tokens']} asked for")
    return None


def live_contexts(load: dict, at: float) -> list[int]:
    """Context lengths (prompt plus tokens streamed so far) of the
    requests that were generating at wall-clock time ``at``."""
    out = []
    for r in load["records"]:
        if r.get("exhausted") or r.get("warmup"):
            continue
        times = _token_times(r)
        if times and times[0] <= at and (r["ended"] is None
                                         or r["ended"] > at):
            out.append(r["prompt_len"] + sum(t <= at for t in times))
    return out


def summarize(load: dict, vocab: int) -> dict:
    """Every serving number a cell may report, from one run's records.
    Warm-up requests (``warmup``) are checked and otherwise left out."""
    ws, we = load["window_start"], load["window_end"]
    recs = [r for r in load["records"] if not r.get("warmup")]
    failures = {}
    for r in load["records"]:
        why = check_request(r, vocab)
        if why is not None:
            failures[r["id"]] = why
    in_window_tokens = 0
    gaps_ms: list[float] = []
    for r in recs:
        if r.get("exhausted"):
            continue
        times = _token_times(r)
        in_window_tokens += sum(ws <= t < we for t in times)
        gaps_ms += [(b - a) * 1e3 for a, b in zip(times, times[1:])
                    if ws <= b < we]
    due_in_window = [r for r in recs
                     if not r.get("exhausted") and ws <= r["due"] < we]
    ttft_ms = []
    for r in due_in_window:
        times = _token_times(r)
        ttft_ms.append((times[0] - r["due"]) * 1e3
                       if times and r["id"] not in failures else math.inf)
    late_ms = [(r["sent"] - r["due"]) * 1e3 for r in recs
               if not r.get("exhausted") and r["sent"] is not None]
    finished = sum(1 for r in recs if r.get("terminal"))
    out = {
        "attempted": len(recs), "failed": len(
            [r for r in recs if r["id"] in failures]),
        "failures": dict(list(failures.items())[:5]),
        "finished": finished,
        "cut_off_at_end": sum(1 for r in recs if r.get("aborted")),
        "window_s": we - ws, "tokens_in_window": in_window_tokens,
        "serve_tokens_per_s": in_window_tokens / (we - ws),
        "gap_samples": len(gaps_ms), "ttft_samples": len(ttft_ms),
        "ttft_missing": sum(1 for v in ttft_ms if math.isinf(v)),
    }
    if gaps_ms:
        for q in (50, 90, 95, 99):
            out[f"itl_ms_p{q}"] = percentile(gaps_ms, q / 100)
    if ttft_ms:
        out["ttft_ms_p50"] = percentile(ttft_ms, 0.50)
        out["ttft_ms_p90"] = percentile(ttft_ms, 0.90)
    if late_ms:
        out["loadgen_late_ms_p99"] = percentile(late_ms, 0.99)
    return out
