"""From a ``jax.profiler`` trace to numbers: device busy time and idle
gaps, time per operation, time in Pallas (Mosaic) custom calls, time in
collectives and its exposed part, and the executions of each compiled
program. Every PR computes these the same way, and a PR that claims a
gain cannot change how.

Two steps, so that the arithmetic can be tested on a small recorded
trace without the profiler: :func:`load` turns an ``.xplane.pb`` into
plain lists (``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns, opcode], ...]}]}]}``), and
:func:`reduce` works on those.

What a v5e trace looks like (looked at by hand, PR 22): one plane
``/device:TPU:<n>`` per chip. Its line ``XLA Modules`` has one event per
execution of a compiled program, named ``<jit name>(<fingerprint>)``
(``jit_shard_fn`` is the train step; a jitted ``functools.partial``, as
the decode step is, shows as ``jit__unknown``). Its line ``XLA Ops`` has
one event per HLO operation executed, named by the instruction's whole
text (``%fusion.83 = bf16[...]{layout} fusion(...)``): :func:`parse_op`
cuts that to name, result shape and opcode. A Pallas kernel is a
``custom-call`` whose text holds ``custom_call_target="tpu_custom_call"``
and whose name is the jitted kernel function's. ``Async XLA Ops`` has
one span per asynchronous operation from its start to its done.
``/host:CPU`` has one line per host thread, with the runtime's own spans
(``PjitFunction(name)``, ``np.asarray(jax.Array)`` for a blocking fetch)
and the benchmark's ``TraceAnnotation``.
"""

from __future__ import annotations

import glob
import os
import re
import sys
from bisect import insort

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
#: the opcode :func:`parse_op` gives a Mosaic (compiled Pallas) call
PALLAS = "tpu_custom_call"
#: the host annotation the drivers put around the traced part
WINDOW_ANNOTATION = "bench.trace_window"

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|ragged-all-to-all)")
_HLO = re.compile(r"^(%[\w.\-]+) = (.*?)\s([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


class TraceError(Exception):
    """The trace does not hold what a reader needs."""


# -- loading ---------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def parse_op(text: str) -> tuple[str, str]:
    """An HLO instruction's text as (short label, opcode): the name,
    the result shape without its layout, and the operation. A Mosaic
    custom call gets the opcode :data:`PALLAS`."""
    m = _HLO.match(text)
    if m is None:
        return text[:160], ""
    name, shape, opcode = m.groups()
    if opcode == "custom-call" and f'custom_call_target="{PALLAS}"' in text:
        opcode = PALLAS
    return f"{name} = {_LAYOUT.sub('', shape)} {opcode}"[:160], opcode


def load(path: str, keep_planes=(DEVICE_PLANE, HOST_PLANE)) -> dict:
    """Read an ``.xplane.pb`` with nothing but jax."""
    from jax.profiler import ProfileData

    def wanted(name: str) -> bool:
        return any(k.match(name) if hasattr(k, "match") else k == name
                   for k in keep_planes)

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not wanted(plane.name):
            continue
        lines = []
        for line in plane.lines:
            hlo = line.name in (OPS_LINE, ASYNC_LINE)
            events = []
            for ev in line.events:
                label, opcode = parse_op(ev.name) if hlo else (ev.name, "")
                events.append([label, float(ev.start_ns),
                               float(ev.duration_ns), opcode])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def cut(trace: dict, lo_ns: float, hi_ns: float) -> dict:
    """The events that lie wholly inside lo..hi, with the window
    annotation cut to it: how the small recorded traces beside the tests
    were made from whole ones."""
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [[e[0], max(e[1], lo_ns),
                       min(e[1] + e[2], hi_ns) - max(e[1], lo_ns), e[3]]
                      if e[0] == WINDOW_ANNOTATION else e
                      for e in line["events"]
                      if (e[1] >= lo_ns and e[1] + e[2] <= hi_ns)
                      or (e[0] == WINDOW_ANNOTATION and e[1] < hi_ns
                          and e[1] + e[2] > lo_ns)]
            if events:
                lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


# -- interval arithmetic ---------------------------------------------------

def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of half-open intervals, as a sorted list of disjoint ones."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """What ``merged`` (disjoint, sorted) leaves uncovered of lo..hi."""
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def subtract(intervals, cover) -> list[tuple[float, float]]:
    """The part of ``intervals`` that ``cover`` does not overlap. Both
    are merged first, so one walk over the two sorted lists does it: the
    trace of a fast decode step has 150,000 idle gaps to take a few
    thousand spans from."""
    cover = merge(cover)
    out, first = [], 0
    for a, b in merge(intervals):
        # what ends before this interval begins meets no later one
        while first < len(cover) and cover[first][1] <= a:
            first += 1
        at, k = a, first
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > at:
                out.append((at, cover[k][0]))
            at = max(at, min(cover[k][1], b))
            k += 1
        if b > at:
            out.append((at, b))
    return out


def self_times(events) -> list[tuple[list, float]]:
    """(event, self time) per event of one line: its duration less what
    the events nested inside it cover. Nested means contained; two
    events that merely overlap are side by side."""
    out, stack = [], []          # stack of [end, index into out]
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        start, dur = ev[1], ev[2]
        while stack and start + dur > stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([ev, dur])
        stack.append([start + dur, len(out) - 1])
    return [(ev, max(0.0, d)) for ev, d in out]


# -- picking the trace apart ---------------------------------------------

def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(trace: dict) -> list[dict]:
    found = [(int(DEVICE_PLANE.match(p["name"]).group(1)), p)
             for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    return [p for _, p in sorted(found, key=lambda t: t[0])]


def host_events(trace: dict) -> list[list]:
    return [ev for p in trace["planes"] if p["name"] == HOST_PLANE
            for line in p["lines"] for ev in line["events"]]


def annotated_window(trace: dict) -> tuple[float, float] | None:
    spans = [(ev[1], ev[1] + ev[2]) for ev in host_events(trace)
             if ev[0] == WINDOW_ANNOTATION]
    return (min(a for a, _ in spans), max(b for _, b in spans)) \
        if spans else None


def is_collective(opcode: str) -> bool:
    """Collective operations, their asynchronous halves included."""
    return bool(_COLLECTIVE.match(opcode))


def _host_label(host: list[list], lo: float, hi: float) -> str:
    """What the host was doing during lo..hi: the span that overlaps it
    most, the shortest such where several cover it whole (the innermost
    of nested spans), the benchmark's own window annotation aside.
    Among equals the first in ``host`` wins."""
    best, best_key = "nothing recorded on the host", (0.0, 0.0)
    for name, start, dur, *_ in host:
        if name == WINDOW_ANNOTATION:
            continue
        overlap = min(hi, start + dur) - max(lo, start)
        if overlap <= 0:
            continue
        key = (overlap, -dur)
        if key > best_key:
            best, best_key = name, key
    return best


def host_labels(host: list[list], spans) -> list[str]:
    """:func:`_host_label` of each of ``spans``, which come in the order
    of their starts (as :func:`gaps` gives them), in one walk over them
    and the host's events: a span is judged against the events open at
    it, a handful (nesting depth times threads), and not against every
    event of the trace. They are kept in the order ``host`` has them, so
    the ties fall as they do there."""
    by_start = sorted(range(len(host)), key=lambda i: host[i][1])
    out, open_, nxt = [], [], 0          # open_: indices into host, sorted
    for lo, hi in spans:
        while nxt < len(by_start) and host[by_start[nxt]][1] < hi:
            insort(open_, by_start[nxt])
            nxt += 1
        # what has ended by here overlaps no later span either
        open_ = [i for i in open_ if host[i][1] + host[i][2] > lo]
        out.append(_host_label([host[i] for i in open_], lo, hi))
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """Everything the per-layer readers and the ``breakdown`` need.
    Times are seconds, averaged over the chips; program executions
    (``modules``) and idle gaps are the first chip's."""
    return reduce_sized(trace, top)[0]


def reduce_sized(trace: dict, top: int = 10) -> tuple[dict, dict]:
    """:func:`reduce`, and beside it the sizes its cost grows with: the
    operations of all chips inside the window, the first chip's idle
    gaps and the host's events."""
    planes = device_planes(trace)
    if not planes:
        raise TraceError("no /device:TPU:<n> plane in the trace: "
                         f"{[p['name'] for p in trace['planes']]}")
    window = annotated_window(trace)
    host = host_events(trace)

    def inside(events):
        return events if window is None else [
            e for e in events
            if e[1] >= window[0] and e[1] + e[2] <= window[1]]

    per_device, op_time, modules0, gap_time = [], {}, {}, {}
    pallas = collective = exposed = 0.0
    sizes = {"ops": 0, "gaps": 0, "host_events": len(host)}
    for plane in planes:
        ops = inside(_line(plane, OPS_LINE))
        if not ops:
            raise TraceError(f"no operation ran on {plane['name']} in "
                             "the traced window")
        sizes["ops"] += len(ops)
        busy = merge([(e[1], e[1] + e[2]) for e in ops])
        # the window is cut to the device's own activity: what the
        # profiler needs to start and stop is not the system's idleness
        lo, hi = busy[0][0], busy[-1][1]
        per_device.append({"busy_s": total(busy) / 1e9,
                           "window_s": (hi - lo) / 1e9})
        for ev, self_ns in self_times(ops):
            op_time[ev[0]] = op_time.get(ev[0], 0.0) + self_ns / 1e9
            if ev[3] == PALLAS:
                pallas += self_ns / 1e9
        # a collective is in flight from its start to its done (the
        # asynchronous line), or for as long as its own operation runs;
        # it is exposed while nothing else computes on this chip
        span = lambda e: (e[1], e[1] + e[2])  # noqa: E731
        coll = ([span(e) for e in ops if is_collective(e[3])]
                + [span(e) for e in inside(_line(plane, ASYNC_LINE))
                   if is_collective(e[3])])
        compute = [span(e) for e in ops
                   if not is_collective(e[3]) and e[3] != "while"]
        collective += total(merge(coll)) / 1e9
        exposed += total(subtract(coll, compute)) / 1e9
        if plane is planes[0]:
            for name, start, dur, *_ in _line(plane, MODULES_LINE):
                # a program's span reaches a little past its operations
                if lo <= start + dur / 2 <= hi:
                    # the whole name, fingerprint and all: every jitted
                    # functools.partial is called jit__unknown
                    m = modules0.setdefault(
                        name, {"starts_ms": [], "durations_ms": []})
                    m["starts_ms"].append(start / 1e6)
                    m["durations_ms"].append(dur / 1e6)
            idle = gaps(busy, lo, hi)
            sizes["gaps"] = len(idle)
            for (a, b), label in zip(idle, host_labels(host, idle)):
                gap_time[label] = gap_time.get(label, 0.0) + (b - a) / 1e9
    n = len(planes)
    ranked = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "devices": n,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "window_s": sum(d["window_s"] for d in per_device) / n,
        "per_device": per_device,
        "pallas_s": pallas / n,
        "collective_s": collective / n,
        "collective_exposed_s": exposed / n,
        "modules": modules0,
        # the operations with most (self) time, and the idle time of the
        # first chip by what the host was doing in each gap
        "device_ops": ranked({k: v / n for k, v in op_time.items()}),
        "idle_gaps": ranked(gap_time),
    }, sizes


def main_module(reduced: dict) -> tuple[str, dict]:
    """The compiled program with most device time in the window: the
    train step in a train cell, the decode step in a serving cell."""
    if not reduced["modules"]:
        raise TraceError("no program execution on the XLA Modules line")
    name, module = max(reduced["modules"].items(),
                       key=lambda kv: sum(kv[1]["durations_ms"]))
    return re.sub(r"\(\d+\)$", "", name), module


# -- looking at a trace by hand -------------------------------------------

def describe(path: str, top: int = 25) -> None:
    """Print planes, lines, and the names with most time on each."""
    trace = load(path, keep_planes=(re.compile(".*"),))
    for plane in trace["planes"]:
        print(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            evs = line["events"]
            if not evs:
                continue
            span = (max(e[1] + e[2] for e in evs) - min(e[1] for e in evs))
            print(f"  LINE {line['name']!r}: {len(evs)} events over "
                  f"{span / 1e6:.1f} ms")
            by_name: dict[str, list] = {}
            for name, _, dur, cat in evs:
                acc = by_name.setdefault(name, [0, 0.0, cat])
                acc[0] += 1
                acc[1] += dur
            for name, (count, dur, cat) in sorted(
                    by_name.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"      {dur / 1e6:10.3f} ms {count:6d}x  "
                      f"{name[:100]}  {cat}")


if __name__ == "__main__":
    target = sys.argv[1]
    if os.path.isdir(target):
        target = find_xplane(target)
    describe(target)
