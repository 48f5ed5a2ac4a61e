"""The host between two decode steps: each gap of the serving loop in
which the chip runs nothing, split by what the host was doing, with the
part that lies beneath the two calls and the trace's own clock offset.
The eleven readers that PR 40 brought are built on this file
(:data:`READERS`; files under ``benchmark/layer_metrics/`` that
``BENCHMARK.json`` lists since PR 42, so a traced run's result line
carries them); ``program_trace.py`` and ``trace_reduce.py`` are used by
import.

    python3 benchmark/lib/host_gaps.py [<trace dir, .xplane.pb or .json.gz>]

prints the split of the newest traced serving run under
``runtime.WORK_ROOT`` (with the loop clock of its heartbeats and what
each of the eleven readers reads on it), or of any trace of a decode
replica, an operator's included.

**Two clocks.** A host span and a device operation are in one file and
not on one clock: in the recorded v5e traces beside the tests (jax
0.9.0, libtpu 0.0.34) a step's execution begins 0.4-0.7 ms BEFORE the
``dml.serve.step.dispatch`` span that launched it opens, so the device
plane lies early against the host plane there, by an amount that is
constant within a trace and differs between profiler sessions (over PR
40's chip runs 0.78-0.84 ms at least in a machine's first session, 0.0
in its later ones). Every number listed from here
is therefore a difference WITHIN one clock, and host is joined to device
by order, not by time: the loop is serial, so the k-th execution of
``jit_decode_step`` on the first chip (every fingerprint of the name, in
start order) is the k-th dispatch span's, once the two sequences are
aligned at the window's edges, where a span or a step may be cut
(:func:`join`). The two clocks are compared only to measure them:
``offset_lo``, the least by which the device plane must be moved later
for every step to begin after its call was made, and ``offset_hi``, the
most it can be moved before a step ends after the fetch that waited for
it returned. Anything here that intersects the two planes
(:func:`idle_no_request_share`) moves the device plane later by
``offset_lo`` first.

**The gap and its parts**, for iteration i (ns in :func:`join`'s rows):
``gap`` = execution i+1's start - execution i's end, on the device's
clock; ``between`` = dispatch i+1's start - fetch i's end, on the
host's; ``beneath`` = gap - between: the chip idles while the host is
inside the two calls, the launch path and the pick-up of the tokens,
whatever the offset. ``between`` is cut by the batcher thread's own
spans into ``emit`` (``dml.serve.sample``, ``.stream``, ``.finish``),
``inputs`` (``.step.inputs``) and ``rest`` (``.admit``, ``.heartbeat``
and what no span covers), so beneath + emit + inputs + rest = gap. An
iteration is *plain* where no execution of ``jit_decode_prefill`` or
``jit_write_prompt_kv`` lies between its two steps: a rule on the
device's plane alone, which ``reduce``'s ``modules`` decide the same way
(:func:`device_gaps_ms`). A prefill's gap is the prefill's
(``prefill_ms_p50``), and after a park the next step follows a prefill.

Everything is one walk over lists in start order (linear in what the
trace holds: PR 38's rule).
"""

from __future__ import annotations

import glob
import json
import os
import sys
from pathlib import Path

if __package__ in (None, ""):            # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmark.lib import program_trace as pt, trace_reduce as tr  # noqa: E402
from benchmark.lib.stats import percentile  # noqa: E402
from benchmark.lib.trace_reduce import TraceError  # noqa: E402

SPAN_FETCH = "dml.serve.step.fetch"
SPAN_INPUTS = "dml.serve.step.inputs"
SPAN_FINISH = "dml.serve.finish"
SPAN_ADMIT = "dml.serve.admit"
SPAN_IDLE = "dml.serve.idle"
SPAN_HEARTBEAT = "dml.serve.heartbeat"
EMIT = (pt.SPAN_SAMPLE, pt.SPAN_STREAM, SPAN_FINISH)
#: a gap that holds an execution of one of these is the prefill's
PREFILL_PROGRAMS = ("jit_decode_prefill", pt.CACHE_WRITE)
#: the four parts of a gap, in the order they are printed
PARTS = ("beneath", "emit", "inputs", "rest")
#: the readers built on this file, ``benchmark/layer_metrics/<name>.py``:
#: all move ``itl_ms_p90`` and read both ``opt-1.3b`` serving cells
READERS = (
    "decode_gap_ms_p50", "decode_gap_ms_p90", "decode_gap_beneath_ms",
    "decode_gap_emit_ms", "decode_gap_inputs_ms", "decode_gap_rest_ms",
    "trace_clock_offset_ms", "serve_idle_no_request_share",
    "decode_loop_host_share", "decode_cache_write_ms_per_step",
    "decode_paged_kernel_ms_per_step")
#: a step starts within this of the call that launched it (ns): the
#: runtime's launch path less the planes' offset, both under a millisecond
NEAR_NS = 5e6
#: what two readings of one instant may differ by (ns)
SLACK_NS = 0.05e6
#: the share of dispatch spans inside the edges that may lack their fetch
UNMATCHED = 0.02
#: how far the window's edges can shift one sequence against the other
_SHIFTS = (0, 1, -1, 2, -2)
#: the runtime's own host events that say where, inside the two calls,
#: the host hands the step over and takes its tokens back
_RUNTIME = ("TpuLoadedExecutable::ExecuteLaunch", "tpu::System::Execute",
            "ArrayImpl.copy_to_host_async", "np.asarray(jax.Array)")


def _end(ev) -> float:
    return ev[1] + ev[2]


# -- the device's plane alone ----------------------------------------------

def gaps_between(steps: list[tuple], others: list[float]) -> list[tuple]:
    """(gap, plain) per pair of neighbours among ``steps`` ((start,
    duration), any order, any one unit): from one's end to the next's
    start, plain where none of ``others`` (start times) lies between."""
    steps, others = sorted(steps), sorted(others)
    out, k = [], 0
    for (start, dur), (nxt, _) in zip(steps, steps[1:]):
        while k < len(others) and others[k] < start + dur:
            k += 1
        out.append((nxt - start - dur,
                    not (k < len(others) and others[k] < nxt)))
    return out


def device_gaps_ms(reduced: dict) -> list[float]:
    """The plain gaps (ms) between the executions of the decode step on
    the first chip, from ``trace_reduce.reduce``'s ``modules`` and
    nothing else: every module of ``main_module``'s name, whatever its
    fingerprint (one a table width), in start order."""
    name, _ = tr.main_module(reduced)
    steps, prefills = [], []
    for module, m in reduced["modules"].items():
        if pt.program_name(module) == name:
            steps += zip(m["starts_ms"], m["durations_ms"])
        elif pt.program_name(module) in PREFILL_PROGRAMS:
            prefills += m["starts_ms"]
    return [gap for gap, plain in gaps_between(steps, prefills) if plain]


# -- host joined to device -------------------------------------------------

def batcher_spans(trace: dict) -> list | None:
    """The program's spans (inside the traced part, in start order) of
    the thread that dispatches the decode step; None where no thread
    does."""
    for events in pt.spans_by_thread(trace).values():
        if any(e[0] == pt.SPAN_DISPATCH for e in events):
            return events
    return None


def _calls(batcher: list) -> list[list]:
    """[dispatch span, the fetch span that follows it or None], in order."""
    out = []
    for ev in batcher:
        if ev[0] == pt.SPAN_DISPATCH:
            out.append([ev, None])
        elif ev[0] == SPAN_FETCH and out and out[-1][1] is None:
            out[-1][1] = ev
    return out


def _shift(steps: list, calls: list) -> int:
    """``s`` such that ``steps[k]`` is ``calls[k + s]``'s: of the few
    shifts the window's edges allow, the one under which every step
    starts within :data:`NEAR_NS` of its call, the nearest where an
    iteration is so short that two do."""
    best, best_far = None, None
    for s in _SHIFTS:
        lo, hi = max(0, -s), min(len(steps), len(calls) - s)
        if hi - lo < min(len(steps), len(calls)) - abs(s) or hi <= lo:
            continue
        far = 0.0
        for k in range(lo, hi):
            d = abs(calls[k + s][0][1] - steps[k][1])
            if d > NEAR_NS:
                break
            far += d
        else:
            if best is None or far / (hi - lo) < best_far:
                best, best_far = s, far / (hi - lo)
    if best is None:
        raise TraceError(
            f"{len(steps)} executions of {pt.DECODE_STEP} and {len(calls)} "
            f"{pt.SPAN_DISPATCH} spans cannot be aligned: under no shift "
            f"of {_SHIFTS} does every step start within "
            f"{NEAR_NS / 1e6:g} ms of its call")
    return best


def _cover(spans: list, names: tuple, lo: float, hi: float) -> float:
    """ns of lo..hi that the spans of these names cover."""
    return tr.total(tr.merge([(max(e[1], lo), min(_end(e), hi))
                              for e in spans if e[0] in names]))


def join(trace: dict) -> dict | None:
    """The decode iterations of the traced part, host joined to device
    by order, and the offset between the two clocks (times in ns). None
    where the trace holds no execution of the step or no dispatch span.
    ``TraceError`` where the join cannot be right: no alignment, over
    :data:`UNMATCHED` of the dispatches inside the edges without their
    fetch, a plain iteration whose host spent longer between the two
    calls than the chip idled between their steps, or planes that
    differ by more than a constant (``offset_lo`` > ``offset_hi``)."""
    steps = sorted(pt.executions(trace, pt.DECODE_STEP)[0],
                   key=lambda e: e[1])
    batcher = batcher_spans(trace)
    if not steps or batcher is None:
        return None
    calls = _calls(batcher)
    s = _shift(steps, calls)
    lo, hi = max(0, -s), min(len(steps), len(calls) - s)
    pairs = [(steps[k], *calls[k + s]) for k in range(lo, hi)]
    cut = {"steps_before": lo, "calls_before": max(0, s),
           "steps_after": len(steps) - hi,
           "calls_after": len(calls) - s - hi,
           "last_call_without_fetch": int(pairs[-1][2] is None)}
    lost = sum(f is None for _, _, f in pairs[:-1])
    if lost > UNMATCHED * len(pairs):
        raise TraceError(f"{lost} of {len(pairs)} {pt.SPAN_DISPATCH} spans "
                         f"inside the edges have no {SPAN_FETCH} after them")
    prefills = [e[1] for name in PREFILL_PROGRAMS
                for e in pt.executions(trace, name)[0]]
    plain = gaps_between([(e[1], e[2]) for e, _, _ in pairs], prefills)
    rows, at = [], 0
    for ((e0, _, f0), (e1, d1, _)), (gap, is_plain) in zip(
            zip(pairs, pairs[1:]), plain):
        if f0 is None:
            continue
        a, b = _end(f0), d1[1]
        while at < len(batcher) and batcher[at][1] < a:
            at += 1
        k = at
        while k < len(batcher) and batcher[k][1] < b:
            k += 1
        inside = batcher[at:k]
        row = {"at": e0[1], "plain": is_plain, "gap": gap,
               "between": b - a, "beneath": gap - (b - a),
               "emit": _cover(inside, EMIT, a, b),
               "inputs": _cover(inside, (SPAN_INPUTS,), a, b),
               # of rest, under a span
               "admit": _cover(inside, (SPAN_ADMIT,), a, b),
               "heartbeat": _cover(inside, (SPAN_HEARTBEAT,), a, b),
               # either clock's reading of the other's instant, raw: the
               # launch is launch_raw + offset, the pick-up pickup_raw -
               # offset, the offset somewhere in offset_lo..offset_hi
               "launch_raw": e1[1] - d1[1], "pickup_raw": _end(f0) - _end(e0)}
        row["rest"] = row["between"] - row["emit"] - row["inputs"]
        if is_plain and row["beneath"] < -SLACK_NS:
            raise TraceError(
                f"the host spent {row['between'] / 1e6:.3f} ms between two "
                f"calls whose steps lie {gap / 1e6:.3f} ms apart on the "
                f"chip (at {e0[1] / 1e6:.3f} ms): the join is wrong")
        rows.append(row)
    # the bracket, after the iterations: a plain iteration's beneath is
    # one pair's two terms of it, and names the place where it fails
    offset_lo = max(0.0, max(d[1] - e[1] for e, d, _ in pairs))
    offset_hi = min((_end(f) - _end(e) for e, _, f in pairs
                     if f is not None), default=float("inf"))
    if offset_lo > offset_hi + SLACK_NS:
        raise TraceError(
            f"the device plane must be moved {offset_lo / 1e6:.3f} ms later "
            "for every step to begin after its call and can be moved "
            f"{offset_hi / 1e6:.3f} ms at most before a step ends after its "
            "fetch: the planes differ by more than a constant")
    return {"steps": len(steps), "calls": len(calls), "matched": len(pairs),
            "cut": cut, "offset_lo": offset_lo, "offset_hi": offset_hi,
            "iterations": rows, "pairs": pairs, "batcher": batcher}


def plain_ms(found: dict, key: str) -> list[float]:
    """``key`` of every plain iteration, ms."""
    return [row[key] / 1e6 for row in found["iterations"] if row["plain"]]


# -- what the readers ask --------------------------------------------------

def instrumented(reduced: dict) -> bool:
    """Whether the program that made the trace opens spans: some idle
    time of the first chip is labelled with one. Asked of ``reduced``
    alone, so that a reader of an uninstrumented program (PR 23's
    parent, the PR 22 recording) reads 0.0, nothing being attributed,
    without looking for the run's files."""
    return any(label.startswith(pt.SPAN_PREFIX)
               for label, _ in reduced["idle_gaps"])


def joined(reduced: dict) -> dict | None:
    """:func:`join` of the run ``reduced`` came from, made once a run."""
    run = pt.this_run(reduced)
    if "host_gaps" not in run:
        run["host_gaps"] = join(run["trace"])
    return run["host_gaps"]


def of_the_join(reduced: dict, value) -> float | None:
    """``value(join)`` of the run ``reduced`` came from: what a reader of
    the spans returns. 0.0 for a program that opens no span; None where
    the spans are there and the dispatch span or the step is not: a lost
    span is not a gain."""
    if not instrumented(reduced):
        return 0.0
    found = joined(reduced)
    return None if found is None else value(found)


def part_ms(reduced: dict, key: str) -> float | None:
    """Mean over the plain iterations of one part of the gap, ms; None
    also where no iteration is plain."""
    def mean(found):
        values = plain_ms(found, key)
        return sum(values) / len(values) if values else None
    return of_the_join(reduced, mean)


def clock_offset_ms(reduced: dict) -> float | None:
    return of_the_join(reduced, lambda found: found["offset_lo"] / 1e6)


def idle_no_request_share(trace: dict, found: dict) -> float:
    """Percent of the first chip's traced window in which it runs
    nothing while the batcher is parked in ``dml.serve.idle``, the device
    plane moved later by ``offset_lo``."""
    cover = [(e[1], _end(e)) for e in found["batcher"] if e[0] == SPAN_IDLE]
    if not cover:
        return 0.0
    idle, lo, hi = pt.device_idle(trace)
    idle = [(a + found["offset_lo"], b + found["offset_lo"])
            for a, b in idle]
    covered = tr.total(idle) - tr.total(tr.subtract(idle, cover))
    return 100.0 * covered / (hi - lo)


def no_request_share(reduced: dict) -> float | None:
    return of_the_join(reduced, lambda found: idle_no_request_share(
        pt.this_run(reduced)["trace"], found))


def of_the_step(reduced: dict, value) -> float | None:
    """``value(scope table)`` of the decode step in the run ``reduced``
    came from (all fingerprints; the table made once a run). 0.0 for a
    program that opens no span, None where the trace has no execution of
    the step."""
    if not instrumented(reduced):
        return 0.0
    run = pt.this_run(reduced)
    if "decode_step_table" not in run:
        run["decode_step_table"] = (
            pt.scope_table(run["trace"], pt.DECODE_STEP)
            if pt.executions(run["trace"], pt.DECODE_STEP)[0] else None)
    table = run["decode_step_table"]
    return None if table is None else value(table)


# -- the loop's own clock --------------------------------------------------

def heartbeats(workdir: Path) -> list[dict]:
    """The replica's heartbeats inside the load's window that carry the
    loop clock (``<workdir>/serve/train_log.jsonl``, ``loop_s``:
    ``obsv/timing.LoopClock``), in the order written."""
    path = Path(workdir) / "serve" / "train_log.jsonl"
    if not path.exists() or not (Path(workdir) / "load.json").exists():
        return []
    with open(Path(workdir) / "load.json", encoding="utf-8") as f:
        load_ = json.load(f)
    lo, hi = load_["window_start"], load_["window_end"]
    with open(path, encoding="utf-8") as f:
        records = [json.loads(line) for line in f if line.strip()]
    return [r for r in records if r.get("event") == "heartbeat"
            and "loop_s" in r and lo <= r.get("time", lo) < hi]


def loop_clock(beats: list[dict]) -> dict | None:
    """Between the first and the last of ``beats``: the decode steps, the
    loop's seconds, and the seconds of each phase (``other`` what the
    named ones leave). None where fewer than two are there or no step
    was dispatched between them."""
    if len(beats) < 2:
        return None
    a, b = beats[0], beats[-1]
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    phases = {k: b["loop_s"][k] - a["loop_s"].get(k, 0.0)
              for k in b["loop_s"]}
    wall = b["loop_wall_s"] - a["loop_wall_s"]
    return {"steps": steps, "wall_s": wall,
            "phases_s": {**phases, "other": wall - sum(phases.values())}}


def host_share(fetch_s: float, wall_s: float, idle_s: float) -> float | None:
    """Percent of the loop's time with a request in it that the batcher
    does not spend waiting for the step."""
    busy = wall_s - idle_s
    return 100.0 * (1.0 - fetch_s / busy) if busy > 0 else None


def host_share_of_spans(batcher: list) -> float | None:
    """:func:`host_share` from the traced part's spans: ``step.fetch``
    against the thread's time from its first span to its last, less
    ``dml.serve.idle``."""
    total = lambda name: sum(e[2] for e in batcher  # noqa: E731
                             if e[0] == name)
    return host_share(total(SPAN_FETCH),
                      max(map(_end, batcher)) - batcher[0][1],
                      total(SPAN_IDLE))


def loop_host_share(reduced: dict) -> float | None:
    """From the heartbeats' loop clock over the load's window; where the
    heartbeats carry none (a program before PR 40), from the traced
    part's spans."""
    if not instrumented(reduced):
        return 0.0
    run = pt.this_run(reduced)
    clock = loop_clock(heartbeats(run["workdir"]))
    if clock is not None:
        p = clock["phases_s"]
        return host_share(p["fetch"], clock["wall_s"], p["idle"])
    return of_the_join(reduced,
                       lambda found: host_share_of_spans(found["batcher"]))


# -- the operator's tool ---------------------------------------------------

def _stats(values: list[float]) -> str:
    if not values:
        return "none"
    return (f"n {len(values):4d}  mean {sum(values) / len(values):7.3f}  "
            f"p50 {percentile(values, 0.5):7.3f}  "
            f"p90 {percentile(values, 0.9):7.3f}")


def _runtime_events(trace: dict, found: dict) -> None:
    """Where inside the dispatch and the fetch spans the runtime's own
    events lie, on the host's clock."""
    events: dict[str, list] = {name: [] for name in _RUNTIME}
    for ev in tr.host_events(trace):
        if ev[0] in events:
            events[ev[0]].append(ev)
    for which, spans in (("dispatch", [d for _, d, _ in found["pairs"]]),
                         ("fetch", [f for _, _, f in found["pairs"]
                                    if f is not None])):
        for name, evs in events.items():
            evs.sort(key=lambda e: e[1])
            starts, ends, k = [], [], 0
            for span in spans:
                while k < len(evs) and evs[k][1] < span[1]:
                    k += 1
                if k < len(evs) and evs[k][1] < _end(span):
                    starts.append((evs[k][1] - span[1]) / 1e6)
                    ends.append((_end(span) - _end(evs[k])) / 1e6)
            if starts:
                print(f"  in {which:8s} {name:36s} starts "
                      f"{sum(starts) / len(starts):6.3f} ms after it opens, "
                      f"ends {sum(ends) / len(ends):6.3f} ms before it "
                      f"closes ({len(starts)})")


def describe(path: str | None = None) -> None:
    workdir = None
    if path is None:
        from benchmark.lib.runtime import WORK_ROOT
        found = (glob.glob(os.path.join(WORK_ROOT, "*", "trace", "plugins",
                                        "profile", "*", "*.xplane.pb"))
                 or glob.glob(os.path.join(WORK_ROOT, "*", "trace",
                                           "*.json.gz")))
        if not found:
            raise TraceError(f"no trace of any run under {WORK_ROOT}")
        path = max(found, key=os.path.getmtime)
        workdir = Path(path[:path.index(os.sep + "trace" + os.sep)])
    elif os.path.isdir(path):
        path = tr.find_xplane(path)
    trace = pt.load(path)
    print(path)
    found = join(trace)
    if found is None:
        print(f"no execution of {pt.DECODE_STEP} or no {pt.SPAN_DISPATCH} "
              "span in the traced part")
        return
    print(f"{found['steps']} executions of {pt.DECODE_STEP}, "
          f"{found['calls']} dispatch spans, {found['matched']} joined; "
          f"cut at the edges: {found['cut']}")
    print(f"clock offset: the device plane lies "
          f"{found['offset_lo'] / 1e6:.3f} ms early at least (offset_lo), "
          f"{found['offset_hi'] / 1e6:.3f} at most (offset_hi)")
    rows = found["iterations"]
    print("gaps between two steps on the chip, ms")
    print(f"  plain            {_stats(plain_ms(found, 'gap'))}")
    print("  holding a prefill "
          + _stats([r["gap"] / 1e6 for r in rows if not r["plain"]]))
    print("the plain gap's parts, ms (they add up to it)")
    for key in PARTS + ("between", "admit", "heartbeat"):
        print(f"  {key:16s} {_stats(plain_ms(found, key))}")
    launch, pickup = plain_ms(found, "launch_raw"), plain_ms(found,
                                                             "pickup_raw")
    if launch:
        lo, hi = found["offset_lo"] / 1e6, found["offset_hi"] / 1e6
        mean = lambda v: sum(v) / len(v)  # noqa: E731
        print(f"beneath, by the bracket: launch (dispatch opens -> the step "
              f"starts) {mean(launch) + lo:.3f}..{mean(launch) + hi:.3f} ms, "
              f"pick-up (the step ends -> fetch returns) "
              f"{mean(pickup) - hi:.3f}..{mean(pickup) - lo:.3f} ms")
    _runtime_events(trace, found)
    print(f"host share of the traced part's spans: "
          f"{host_share_of_spans(found['batcher']):.2f}%; chip idle with "
          f"no request: {idle_no_request_share(trace, found):.2f}%")
    clock = loop_clock(heartbeats(workdir)) if workdir else None
    if clock is None:
        print("no heartbeat with a loop clock beside this trace")
    else:
        p = clock["phases_s"]
        print(f"the loop clock, {clock['steps']} iterations between the "
              "first and last heartbeat of the load's window, ms an "
              "iteration:")
        for name, seconds in p.items():
            print(f"  {name:10s} {1e3 * seconds / clock['steps']:8.3f}")
        share = host_share(p["fetch"], clock["wall_s"], p["idle"])
        print(f"  host share {share:.2f}%")
    if workdir:
        # the readers as a traced run would call them: they find this
        # run's files themselves (``program_trace.this_run``)
        from benchmark.lib import cell as cell_lib
        reduced = tr.reduce(trace)
        for name in READERS:
            print(name, cell_lib.load_reader(name).read(reduced, {}))


if __name__ == "__main__":
    describe(sys.argv[1] if len(sys.argv) > 1 else None)
