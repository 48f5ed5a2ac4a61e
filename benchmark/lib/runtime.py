"""What every driver needs from the process it runs in: the TPU gate,
the device record, the work directory, the compile meter, the profiler
and the clock that ``setup_s`` is read from."""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

from . import trace_reduce
from .cell import ROOT, BenchmarkError, Cell
from .compile_meter import CompileMeter
from .peaks import peaks_for

#: everything a run writes besides the compile cache: inside the
#: checkout, git-ignored, emptied at the start of the next run
WORK_ROOT = ROOT / ".benchmark_work"


def gate(chips: int) -> tuple[dict, dict]:
    """Refuse anything but the TPUs the cell asks for, on a chip whose
    peaks are known, before a single thing is built. Returns the device
    record and its peaks."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise BenchmarkError(
            f"needs a TPU; JAX found platform={d0.platform!r} "
            f"({d0.device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise BenchmarkError(f"the cell needs {chips} chip(s), JAX found "
                             f"{len(devs)}")
    peaks = peaks_for(d0.device_kind)   # unknown device: an error
    return ({"platform": d0.platform, "kind": d0.device_kind,
             "count": len(devs)}, peaks)


class Runtime:
    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_process_start: float, device: dict, peaks: dict,
                 work_root: Path = WORK_ROOT):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace = bool(trace)
        self.t_process_start = t_process_start
        self.device, self.peaks = device, peaks
        self.workdir = Path(work_root) / cell.name
        shutil.rmtree(self.workdir, ignore_errors=True)
        self.workdir.mkdir(parents=True)
        self.trace_dir = self.workdir / "trace"
        self.meter = CompileMeter()
        self.setup: dict = {}          # compile seconds and counts of set-up
        self.setup_s: float | None = None
        self.compiles_in_window: int | None = None
        self._annotation = None
        self.traced = False
        #: seconds the run spent on its own trace, and the trace's sizes
        self.trace_cost: dict[str, float] = {}
        self.marks: dict[str, float] = {}   # seconds since process start

    # -- stdout: JSON lines, the result last -----------------------------

    @staticmethod
    def say(**fields) -> None:
        print(json.dumps(fields), flush=True)

    def mark(self, name: str) -> None:
        """Where set-up's time goes: seconds from process start to here,
        printed with the run's values."""
        self.marks[name] = round(time.time() - self.t_process_start, 3)

    # -- the window's edges ----------------------------------------------

    def window_opens(self, at: float | None = None) -> None:
        """Set-up is over: everything from process start to here is
        ``setup_s``, and what compiles from here on is counted against
        the run."""
        self.setup_s = (at if at is not None else time.time()) \
            - self.t_process_start
        self.setup = self.meter.take()

    def window_closes(self) -> None:
        self.compiles_in_window = self.meter.take()["programs"]

    # -- the profiler ----------------------------------------------------

    def start_trace(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        # the python tracer hooks every call and return of the decode
        # loop and the train loop: it would measure itself
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=options)
        self._annotation = jax.profiler.TraceAnnotation(
            trace_reduce.WINDOW_ANNOTATION)
        self._annotation.__enter__()

    def stop_trace(self) -> None:
        import jax

        self._annotation.__exit__(None, None, None)
        self._annotation = None
        t0 = time.time()
        jax.profiler.stop_trace()
        self.trace_cost["stop_s"] = time.time() - t0
        self.traced = True

    def reduced_trace(self) -> dict:
        """The trace as numbers; what reading it cost and the sizes the
        cost grows with go into ``trace_cost``."""
        if not self.traced:
            raise BenchmarkError("the driver took no trace")
        path = trace_reduce.find_xplane(str(self.trace_dir))
        t0 = time.time()
        trace = trace_reduce.load(path)
        t1 = time.time()
        reduced, sizes = trace_reduce.reduce_sized(trace)
        self.trace_cost.update(load_s=t1 - t0, reduce_s=time.time() - t1,
                               **sizes)
        return reduced

    # -- the device ------------------------------------------------------

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = []
        for d in jax.devices()[:self.cell.chips]:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" not in stats:
                raise BenchmarkError(f"{d} reports no peak_bytes_in_use")
            peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks)

    def memory_limit_bytes(self) -> int | None:
        import jax

        return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def fail(message: str, code: int = 2) -> None:
    """No result line, a reason on stderr, a non-zero exit."""
    print(f"benchmark: {message}", file=sys.stderr, flush=True)
    sys.exit(code)
