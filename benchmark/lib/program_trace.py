"""What the program says about itself in a ``jax.profiler`` trace, and
the files of the run that made it: the host spans the program opens
(``dml.*``, ``distributedmnist_tpu/obsv/spans.py``), the device time
under each ``jax.named_scope`` and each kernel name inside one named
program, and the replica's journal inside the load's window. The
readers of the per-layer metrics that PR 23 added are built on this
file; ``trace_reduce.py`` stays as it was and is used by import.

    python3 benchmark/lib/program_trace.py <trace dir or .xplane.pb>

prints the span table and the scope table of any trace: an operator's
``train.profile_steps`` trace as well as a benchmark run's.

Where a v5e trace carries the names (looked at by hand, PR 23, jax
0.9.0, libtpu 0.0.34). A host span is an event of a thread's line on
plane ``/host:CPU``; its keyword facts are the event's own stats, typed.
A device scope is NOT in the event's stats (those are
``device_offset_ps``, ``device_duration_ps``) and NOT in the
instruction text (the profiler strips ``metadata={...}`` from it): it
is in the stats of the event's *metadata* record, which every execution
of one instruction shares: ``tf_op`` holds the ``op_name`` followed by
a colon, ``program_id`` the fingerprint that ``XLA Modules`` puts in
brackets after the program's name (``jit_decode_step(<id>)``), beside
``hlo_category``, ``flops``, ``bytes_accessed`` and ``source``.
``jax.profiler.ProfileData`` shows an event's own stats only, so
:func:`load` reads the protocol buffer's wire format itself (seven
small messages, below). An ``op_name`` is a path:
``jit(shard_fn)/jvp(attention)/dot_general`` in the forward pass,
``jit(shard_fn)/transpose(jvp(head))/dot_general`` in the backward
pass, and for a block under ``jax.checkpoint``
``.../transpose(jvp(jvp()))/checkpoint/ffn/...`` for its backward pass and
``.../checkpoint/rematted_computation/ffn/...`` for its forward pass run
again. A fusion carries the name of one instruction inside it, the
matrix product where it has one and not its root: the weight-gradient
product fused with the momentum update reads ``checkpoint/ffn/dot_general``,
not ``update``. A Mosaic call is named by its kernel
(``%flash_fwd.6 = ... custom-call``; ``tf_op`` ends in
``/flash_fwd/pallas_call``).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import struct
import sys
from bisect import bisect_left
from pathlib import Path

if __package__ in (None, ""):            # run as a script
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmark.lib import trace_reduce as tr  # noqa: E402
from benchmark.lib.trace_reduce import TraceError  # noqa: E402

#: every host span of the program starts with this
SPAN_PREFIX = "dml."
#: the scopes the program opens (its own list is obsv/spans.py SCOPES;
#: the benchmark also runs programs that predate that file)
SCOPES = ("cast", "embed", "attention", "cache_write", "cache_gather",
          "ffn", "head", "loss", "aggregate", "update", "timing")
DECODE_STEP = "jit_decode_step"
CACHE_WRITE = "jit_write_prompt_kv"
SPAN_SAMPLE = "dml.serve.sample"
SPAN_STREAM = "dml.serve.stream"
SPAN_DISPATCH = "dml.serve.step.dispatch"
#: two readings of one start time agree to this (ms): both come from
#: the same picoseconds, rounded on the way
_SAME_MS = 1e-3


# -- the .xplane.pb, read field by field -----------------------------------
# XSpace{1: planes}; XPlane{2: name, 3: lines, 4: event_metadata (map),
# 5: stat_metadata (map)}; XLine{2: name, 3: timestamp_ns, 4: events};
# XEvent{1: metadata_id, 2: offset_ps, 3: duration_ps, 4: stats};
# XEventMetadata{1: id, 2: name, 5: stats}; XStatMetadata{1: id, 2: name};
# XStat{1: metadata_id, 2: double, 3: uint64, 4: int64, 5: str, 7: ref}.

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for bytes, raw bytes for a fixed 4 or 8."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + size]), i + size
        else:
            raise TraceError(f"wire type {wire} in an .xplane.pb")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf) -> tuple[int, memoryview]:
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stats(bufs, stat_names: dict) -> dict:
    out = {}
    for buf in bufs:
        name = value = None
        for f, v in _fields(buf):
            if f == 1:
                name = stat_names.get(v)
            elif f == 2:
                value = struct.unpack("<d", v)[0]
            elif f == 3:
                value = v
            elif f == 4:                      # int64, two's complement
                value = v - (1 << 64) if v >= 1 << 63 else v
            elif f == 5:
                value = _text(v)
            elif f == 7:                      # a string kept once
                value = stat_names.get(v)
        if name is not None and value is not None:
            out[name] = value
    return out


def _plane(buf) -> dict | None:
    name, lines, event_meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            key, value = _map_entry(v)
            event_meta[key] = value
        elif f == 5:
            key, value = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for g, x in _fields(value) if g == 2), "")
    device = bool(tr.DEVICE_PLANE.match(name))
    if not device and name != tr.HOST_PLANE:
        return None
    meta = {}                      # metadata id -> (name, facts)
    for key, value in event_meta.items():
        label, stats = "", []
        for f, v in _fields(value):
            if f == 2:
                label = _text(v)
            elif f == 5:
                stats.append(v)
        facts = _stats(stats, stat_names) if device else {}
        meta[key] = (label, {
            k: facts[k] for k in ("tf_op", "program_id") if k in facts})
    out = []
    for line_buf in lines:
        line_name, t0_ns, events = "", 0, []
        for f, v in _fields(line_buf):
            if f == 2:
                line_name = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        hlo = device and line_name in (tr.OPS_LINE, tr.ASYNC_LINE)
        rows = []
        for ev in events:
            mid = offset_ps = duration_ps = 0
            stats = []
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    offset_ps = v
                elif f == 3:
                    duration_ps = v
                elif f == 4:
                    stats.append(v)
            label, facts = meta.get(mid, ("", {}))
            opcode = ""
            if hlo:
                label, opcode = tr.parse_op(label)
                pid = facts.get("program_id")
                facts = {"op_name": facts.get("tf_op", "").rstrip(":"),
                         # unsigned, as XLA Modules prints it
                         "program_id": pid % (1 << 64)
                         if isinstance(pid, int) else None}
            elif label.startswith(SPAN_PREFIX):
                facts = _stats(stats, stat_names)
            rows.append([label, t0_ns + offset_ps / 1e3, duration_ps / 1e3,
                         opcode, facts])
        out.append({"name": line_name, "events": rows})
    return {"name": name, "lines": out}


def load(path: str) -> dict:
    """An ``.xplane.pb`` as ``trace_reduce.load`` gives it (device
    planes and the host plane; events ``[label, start_ns, duration_ns,
    opcode, ...]``) with a fifth element, the event's facts: ``op_name``
    and ``program_id`` of a device operation, the keyword facts of a
    ``dml.*`` span, nothing for the rest. A ``.json.gz`` is that form
    already (the recorded traces beside the tests)."""
    if str(path).endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = [_plane(v) for f, v in _fields(data) if f == 1]
    return {"planes": [p for p in planes if p is not None]}


# -- names -----------------------------------------------------------------

_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def scope_path(op_name: str) -> tuple[str, ...]:
    """The program's scopes in an operation's name, outermost first:
    ``jit(f)/transpose(jvp(attention))/mul`` -> ``("attention",)``,
    ``jit(f)/attention/cache_gather/gather`` -> ``("attention",
    "cache_gather")``."""
    out = []
    for part in op_name.split("/"):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in SCOPES:
            out.append(part)
    return tuple(out)


def pass_of(op_name: str) -> str:
    """``recomputed`` (a checkpointed block's forward run again),
    ``backward`` or ``forward``."""
    if "rematted_computation" in op_name:
        return "recomputed"
    return "backward" if "transpose(" in op_name else "forward"


def kernel_of(label: str) -> str:
    """A Mosaic call's kernel: ``%flash_fwd.6 = ...`` -> ``flash_fwd``."""
    return re.sub(r"\.\d+$", "", label.split(" ", 1)[0].lstrip("%"))


def program_name(module: str) -> str:
    return re.sub(r"\(\d+\)$", "", module)


# -- the host's spans ------------------------------------------------------

def _inside(events, window):
    return [e for e in events
            if e[1] >= window[0] and e[1] + e[2] <= window[1]]


def window_of(trace: dict) -> tuple[float, float]:
    """The traced part: the benchmark's annotation where there is one,
    else everything the program's spans and the first chip cover."""
    window = tr.annotated_window(trace)
    if window is not None:
        return window
    events = [e for e in tr.host_events(trace)
              if e[0].startswith(SPAN_PREFIX)]
    for plane in tr.device_planes(trace)[:1]:
        events += tr._line(plane, tr.OPS_LINE)
    if not events:
        raise TraceError("neither a span of the program nor a device "
                         "operation in the trace")
    return (min(e[1] for e in events), max(e[1] + e[2] for e in events))


def spans_by_thread(trace: dict) -> dict[str, list]:
    """The program's spans wholly inside the traced part, per host
    thread (``<index>:<thread name>``: names repeat), in start order."""
    window, out = window_of(trace), {}
    for plane in trace["planes"]:
        if plane["name"] != tr.HOST_PLANE:
            continue
        for i, line in enumerate(plane["lines"]):
            mine = sorted((e for e in _inside(line["events"], window)
                           if e[0].startswith(SPAN_PREFIX)),
                          key=lambda e: e[1])
            if mine:
                out[f"{i}:{line['name']}"] = mine
    return out


def span_names(trace: dict) -> set[str]:
    return {e[0] for events in spans_by_thread(trace).values()
            for e in events}


def instrumented(trace: dict) -> bool:
    """Whether the program that made the trace opens spans at all."""
    return bool(span_names(trace))


def span_table(trace: dict) -> dict[str, dict]:
    """Per span name: how many, their total and their self time (ms):
    a span's duration less what the program's spans nested in it cover."""
    out: dict[str, dict] = {}
    for events in spans_by_thread(trace).values():
        for ev, self_ns in tr.self_times(events):
            row = out.setdefault(ev[0], {"count": 0, "total_ms": 0.0,
                                         "self_ms": 0.0})
            row["count"] += 1
            row["total_ms"] += ev[2] / 1e6
            row["self_ms"] += self_ns / 1e6
    return out


def span_ms_per_iteration(trace: dict, span: str) -> float | None:
    """Self time of ``span`` over the decode iterations of the traced
    part (the count of dispatch spans). A program that opens no span at
    all (PR 23's parent, which the driver also runs with these readers)
    reads 0.0, nothing being attributed to the span; one that opens
    spans but not this one, or dispatched no step, reads nothing, which
    fails the run: a lost span is not a gain."""
    table = span_table(trace)
    if not table:
        return 0.0
    if span not in table or SPAN_DISPATCH not in table:
        return None
    return table[span]["self_ms"] / table[SPAN_DISPATCH]["count"]


def device_idle(trace: dict) -> tuple[list, float, float]:
    """The first chip's idle intervals inside the traced part and the
    bounds they are taken in, cut to the device's own activity as
    ``trace_reduce.reduce`` cuts them."""
    planes = tr.device_planes(trace)
    if not planes:
        raise TraceError("no /device:TPU:<n> plane in the trace")
    ops = _inside(tr._line(planes[0], tr.OPS_LINE), window_of(trace))
    if not ops:
        raise TraceError("no operation ran on the first chip in the "
                         "traced part")
    busy = tr.merge([(e[1], e[1] + e[2]) for e in ops])
    lo, hi = busy[0][0], busy[-1][1]
    return tr.gaps(busy, lo, hi), lo, hi


def idle_share_inside(trace: dict, span: str) -> float:
    """Percent of the first chip's traced window in which it runs
    nothing while some thread is inside ``span``."""
    idle, lo, hi = device_idle(trace)
    cover = [(e[1], e[1] + e[2]) for events in spans_by_thread(
        trace).values() for e in events if e[0] == span]
    covered = tr.total(idle) - tr.total(tr.subtract(idle, cover))
    return 100.0 * covered / (hi - lo)


def idle_share_unattributed(trace: dict) -> float:
    """Percent of the first chip's idle time that no span of the
    program covers: the instrumentation's own coverage."""
    idle, _, _ = device_idle(trace)
    if not idle:
        return 0.0
    cover = [(e[1], e[1] + e[2])
             for events in spans_by_thread(trace).values() for e in events]
    return 100.0 * tr.total(tr.subtract(idle, cover)) / tr.total(idle)


# -- the device's scopes ---------------------------------------------------

def executions(trace: dict, program: str) -> tuple[list, set]:
    """The executions of a named program on the first chip that lie
    wholly inside the traced part, and the program's fingerprints (one
    per shape it was compiled for)."""
    planes = tr.device_planes(trace)
    found = [e for e in _inside(tr._line(planes[0], tr.MODULES_LINE),
                                window_of(trace))
             if program_name(e[0]) == program] if planes else []
    return found, {int(re.search(r"\((\d+)\)$", e[0]).group(1))
                   for e in found}


def _scoped(ops: list) -> list[tuple]:
    """(event, scope path, pass) per operation, in start order. An
    operation the compiler made (a layout copy, a convert it moved)
    has no ``op_name`` at all; it takes the outermost scope that the
    named operations next before and next after it share, and the pass
    ``unnamed``: the float32 views of the gathered cache, between the
    gather and the scores of one layer, are attention's. Where the two
    neighbours differ (the copies of the whole cache before the first
    layer) it stays unscoped."""
    ops = sorted(ops, key=lambda e: e[1])
    paths = [scope_path(e[4]["op_name"]) for e in ops]
    named = [i for i, e in enumerate(ops) if e[4]["op_name"]]
    out, k = [], 0
    for i, ev in enumerate(ops):
        if ev[4]["op_name"]:
            out.append((ev, paths[i], pass_of(ev[4]["op_name"])))
            continue
        while k < len(named) and named[k] < i:
            k += 1
        before = paths[named[k - 1]] if k else ()
        after = paths[named[k]] if k < len(named) else ()
        same = before[:1] if before[:1] == after[:1] else ()
        out.append((ev, same, "unnamed"))
    return out


def scope_table(trace: dict, program: str) -> dict:
    """Device time on the first chip inside the whole executions of one
    program, per execution, by (scope path, pass) and by kernel, in ms.
    An operation's time is its self time (a ``while`` less its body).
    An execution counts where it has as many operations as most
    executions of the same compiled program: the profiler stops
    recording a little before the annotation closes, and a trace's last
    execution has some of its operations missing or none."""
    runs, fingerprints = executions(trace, program)
    ops = [e for e in tr._line(tr.device_planes(trace)[0], tr.OPS_LINE)
           if e[4].get("program_id") in fingerprints] if runs else []
    own = {id(ev): ns for ev, ns in tr.self_times(ops)}
    scoped = _scoped(ops)                      # in start order
    starts = [row[0][1] for row in scoped]
    found = [(run[0], scoped[bisect_left(starts, run[1]):
                             bisect_left(starts, run[1] + run[2])])
             for run in runs]
    counts: dict[str, list] = {}
    for name, mine in found:
        counts.setdefault(name, []).append(len(mine))
    usual = {name: max(set(c), key=c.count) for name, c in counts.items()}
    whole = [mine for name, mine in found
             if mine and len(mine) == usual[name]]
    if not whole:
        raise TraceError(f"no whole execution of {program} in the trace")
    by_scope: dict[tuple, float] = {}
    by_kernel: dict[str, float] = {}
    longest_unscoped = 0.0
    for ev, path, which in (row for mine in whole for row in mine):
        ms = own[id(ev)] / 1e6
        key = ("/".join(path) or "(unscoped)", which)
        by_scope[key] = by_scope.get(key, 0.0) + ms / len(whole)
        if not path:
            longest_unscoped = max(longest_unscoped, ms)
        if ev[3] == tr.PALLAS:
            k = kernel_of(ev[0])
            by_kernel[k] = by_kernel.get(k, 0.0) + ms / len(whole)
    return {"executions": len(whole), "by_scope": by_scope,
            "by_kernel": by_kernel, "total_ms": sum(by_scope.values()),
            "longest_unscoped_op_ms": longest_unscoped}


def scope_ms(table: dict, scope: str) -> float:
    """Time per execution under ``scope``, whatever is nested in it."""
    return sum(ms for (path, _), ms in table["by_scope"].items()
               if scope in path.split("/"))


# -- this run's files ------------------------------------------------------

_RUNS: dict[str, dict] = {}          # trace path -> run, loaded once


def this_run(reduced: dict, root: Path | str | None = None) -> dict:
    """The run a reader was called for: its work directory and its
    trace with the facts kept. A reader is handed ``(reduced,
    counters)`` and nothing else, so the newest trace under ``root``
    (``runtime.WORK_ROOT``) is taken and *proved* to be the one
    ``reduced`` came from: every program execution of ``reduced`` starts
    at the same instant in it. Anything else raises ``TraceError``: a
    reader never reads another run's files."""
    if root is None:
        from benchmark.lib.runtime import WORK_ROOT as root
    found = (glob.glob(os.path.join(
        root, "*", "trace", "plugins", "profile", "*", "*.xplane.pb"))
        or glob.glob(os.path.join(root, "*", "trace", "*.json.gz")))
    if not found:
        raise TraceError(f"no trace of any run under {root}")
    path = max(found, key=os.path.getmtime)
    run = _RUNS.get(path)
    if run is None or run["mtime"] != os.path.getmtime(path):
        workdir = Path(path[:path.index(os.sep + "trace" + os.sep)])
        run = _RUNS[path] = {"workdir": workdir, "trace": load(path),
                             "mtime": os.path.getmtime(path)}
    planes = tr.device_planes(run["trace"])
    starts: dict[str, list] = {}
    for name, start, *_ in (tr._line(planes[0], tr.MODULES_LINE)
                            if planes else []):
        starts.setdefault(name, []).append(start / 1e6)
    want = reduced.get("modules") or {}
    if not want or not all(
            any(abs(s - t) <= _SAME_MS for t in starts.get(name, []))
            for name, m in want.items() for s in m["starts_ms"]):
        raise TraceError(
            f"the newest trace under {root} ({path}) is not the run "
            "these numbers were reduced from: its program executions "
            "start at other times")
    return run


def journal(run: dict) -> tuple[list[dict], float, float]:
    """The replica's journal (``<workdir>/serve/serve_log.jsonl``) and
    the load's window on the same wall clock (``<workdir>/load.json``'s
    ``window_start`` and ``window_end``)."""
    with open(run["workdir"] / "load.json", encoding="utf-8") as f:
        load_ = json.load(f)
    with open(run["workdir"] / "serve" / "serve_log.jsonl",
              encoding="utf-8") as f:
        records = [json.loads(line) for line in f if line.strip()]
    return records, load_["window_start"], load_["window_end"]


def admit_waits_ms(run: dict) -> list[float]:
    """Per prefill inside the window, the time its request had waited
    from admission to the start of the prefill: the journal's
    ``prefill.queue_ms``. A journal that predates that field (PR 23's
    parent) holds the same wait in three numbers: the ``prefill``
    record is written when the prefill ends, ``ttft_ms`` is how long it
    took, and the ``admit`` record is written on admission."""
    records, lo, hi = journal(run)
    admitted = {r["id"]: r["time"] for r in records
                if r.get("action") == "admit"}
    out = []
    for r in records:
        if (r.get("action") != "prefill" or r.get("restart")
                or not lo <= r["time"] < hi):
            continue
        if "queue_ms" in r:
            out.append(float(r["queue_ms"]))
        elif r["id"] in admitted:
            out.append((r["time"] - admitted[r["id"]]) * 1e3
                       - float(r["ttft_ms"]))
    return out


def slots_live_from_journal(run: dict) -> float | None:
    """Median over the window's time of the sequences between their
    ``prefill`` and their ``decode_finish`` record: what a program
    without a dispatch span (PR 23's parent) says of its occupancy."""
    records, lo, hi = journal(run)
    began = {r["id"]: r["time"] for r in records
             if r.get("action") == "prefill" and not r.get("restart")}
    ended = {r["id"]: r["time"] for r in records
             if r.get("action") == "decode_finish"}
    edges = sorted(
        edge for req_id, t0 in began.items()
        for edge in ((max(t0, lo), 1), (min(ended.get(req_id, hi), hi), -1))
        if t0 < hi and ended.get(req_id, hi) > lo)
    if not edges:
        return None
    level, at, held = 0, lo, {}
    for t, step in edges:
        held[level] = held.get(level, 0.0) + t - at
        level, at = level + step, t
    held[level] = held.get(level, 0.0) + hi - at
    seen = 0.0
    for lvl in sorted(held):
        seen += held[lvl]
        if seen >= (hi - lo) / 2:
            return float(lvl)
    return None


# -- the operator's tool ---------------------------------------------------

def describe(path: str) -> None:
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    trace = load(path)
    print(f"{path}\nhost spans (inside the traced part), ms")
    print(f"  {'span':34s} {'count':>6s} {'total':>10s} {'self':>10s}")
    for name, row in sorted(span_table(trace).items(),
                            key=lambda kv: -kv[1]["self_ms"]):
        print(f"  {name:34s} {row['count']:6d} {row['total_ms']:10.3f} "
              f"{row['self_ms']:10.3f}")
    for thread, events in spans_by_thread(trace).items():
        print(f"  thread {thread}: {len(events)} spans")
    planes = tr.device_planes(trace)
    if not planes:
        return
    idle, lo, hi = device_idle(trace)
    print(f"first chip: window {(hi - lo) / 1e6:.3f} ms, idle "
          f"{tr.total(idle) / 1e6:.3f} ms, of it under no span of the "
          f"program {idle_share_unattributed(trace):.2f}%")
    programs: dict[str, list] = {}
    for e in _inside(tr._line(planes[0], tr.MODULES_LINE),
                     window_of(trace)):
        programs.setdefault(program_name(e[0]), []).append(e[2] / 1e6)
    for name, durs in sorted(programs.items(), key=lambda kv: -sum(kv[1])):
        try:
            table = scope_table(trace, name)
        except TraceError:       # a one-operation program, cut short
            continue
        print(f"program {name}: {len(durs)} executions, {sum(durs):.3f} "
              f"ms; {table['executions']} with all their operations, "
              f"{table['total_ms']:.3f} ms each")
        if table["total_ms"] < 1.0:
            continue
        for (scope, which), ms in sorted(table["by_scope"].items(),
                                         key=lambda kv: -kv[1]):
            if ms >= 0.0005 * table["total_ms"]:
                print(f"    {scope:28s} {which:10s} {ms:10.3f} ms "
                      f"{100 * ms / table['total_ms']:6.2f}%")
        for kernel, ms in sorted(table["by_kernel"].items(),
                                 key=lambda kv: -kv[1]):
            print(f"    kernel {kernel:21s} {'':10s} {ms:10.3f} ms "
                  f"{100 * ms / table['total_ms']:6.2f}%")
        print(f"    longest unscoped operation "
              f"{table['longest_unscoped_op_ms']:.3f} ms")


if __name__ == "__main__":
    describe(sys.argv[1])
