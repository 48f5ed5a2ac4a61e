#!/usr/bin/env python3
"""One process, one cell, once:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on the machine that holds the chips the
cell asks for. Set-up (bring-up, weights, compile or load, warm-up of
this cell's shapes and no others) is timed as ``setup_s``; then the cell
is measured for ``--seconds``; the program's outputs are checked against
the plain reference of the cell's architecture, the file
``benchmark/archs/<arch>.py`` that its configuration names. The LAST
line of standard output is the result object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, in a traced run
``breakdown``, and last ``compared``: each number the run compared
beside its limit, which are also the last lines of standard error);
everything else is on earlier lines, in a traced run ``{"event":
"trace_reduced", ...}`` next before the result: the seconds the run spent
stopping the profiler, loading the trace, reducing it and in the readers,
and the trace's sizes that those grow with. With ``--trace 0``
the metrics are the cell's end-to-end metrics, taken with the profiler
off; with ``--trace 1`` they are its per-layer metrics, each by its own
reader under ``benchmark/layer_metrics/``.

It exits non-zero and prints no result where JAX's first device is not
a TPU, the chip is not in ``lib/peaks.py``, there are fewer chips than
the cell needs, the program is not in the checkout, or a metric the cell
lists cannot be produced. The compile cache follows
``core/compile_cache.py``'s rule unchanged: ``JAX_COMPILATION_CACHE_DIR``
where set, else ``<checkout>/.jax_cache``."""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.lib import cell as cell_lib  # noqa: E402
from benchmark.lib.runtime import Runtime, fail, gate  # noqa: E402
from benchmark.lib.trace_reduce import TraceError  # noqa: E402


def _number(name: str, value) -> float:
    if (value is None or isinstance(value, bool)
            or not isinstance(value, (int, float))
            or not math.isfinite(value)):
        raise cell_lib.BenchmarkError(
            f"metric {name!r} has no finite value ({value!r})")
    return float(value)


def per_layer_metrics(cell, reduced: dict, counters: dict,
                      root: Path = cell_lib.ROOT) -> dict:
    """Each per-layer metric by its own reader. A reader that finds
    nothing to read returns None; for a metric this cell lists that is
    an error, never a zero."""
    out = {}
    for entry in cell.per_layer:
        value = cell_lib.load_reader(entry["name"], root).read(reduced,
                                                               counters)
        if value is None:
            raise cell_lib.BenchmarkError(
                f"per-layer metric {entry['name']!r} is listed for "
                f"{cell.name} and its reader found nothing to read")
        out[entry["name"]] = {"value": _number(entry["name"], value),
                              "unit": entry["unit"]}
    return out


def measure(cell, rt: Runtime, root: Path = cell_lib.ROOT) -> dict:
    """Run the cell's driver and build the result object."""
    got = cell_lib.load_driver(cell.kind, root).run(cell, rt)
    if rt.setup_s is None or rt.compiles_in_window is None:
        raise cell_lib.BenchmarkError("the driver did not mark its window")
    device = {**rt.device, "memory_peak_bytes": rt.memory_peak_bytes()}
    values = {**got["values"], "setup_s": rt.setup_s}
    counters = {**got["counters"], "chips": cell.chips,
                "peak_bf16_flops_per_s": rt.peaks["bf16_flops_per_s"],
                "setup_compile_s": rt.setup["compile_s"],
                "setup_programs": rt.setup["programs"]}
    rt.say(event="values", memory_limit_bytes=rt.memory_limit_bytes(),
           setup_marks=rt.marks, **{
        k: v for k, v in {**counters, **values}.items()
        if isinstance(v, (int, float)) and math.isfinite(v)})
    result = {"correct": bool(got["correct"]),
              "attempted": int(got["attempted"]),
              "failed": int(got["failed"])}
    if rt.trace:
        reduced = rt.reduced_trace()
        t0 = time.time()
        result["metrics"] = per_layer_metrics(cell, reduced, counters, root)
        # not a metric: how far this cell's traced run is from its limit
        rt.say(event="trace_reduced", **rt.trace_cost,
               readers_s=time.time() - t0)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:5]}
    else:
        result["metrics"] = {
            m["name"]: {"value": _number(m["name"], values.get(m["name"])),
                        "unit": m["unit"]}
            for m in cell.end_to_end}
    result["device"] = device
    # each number compared beside its limit, last in the line
    result["compared"] = got.get("compared", {})
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        import distributedmnist_tpu  # noqa: F401
    except ImportError as e:
        fail(f"the program is not here ({e}); run from the root of a "
             "checkout", 3)
    try:
        cell = cell_lib.load_cell(args.workload)
        device, peaks = gate(cell.chips)   # UnknownDevice is a LookupError
    except (cell_lib.BenchmarkError, LookupError, OSError) as e:
        fail(f"{type(e).__name__}: {e}")
    rt = Runtime(cell, args.seed, args.seconds, bool(args.trace),
                 T_PROCESS_START, device, peaks)
    rt.mark("devices_up")
    rt.say(event="start", workload=cell.name, seed=rt.seed,
           seconds=rt.seconds, trace=rt.trace, device=device)
    try:
        result = measure(cell, rt)
    except (cell_lib.BenchmarkError, TraceError) as e:
        fail(f"{type(e).__name__}: {e}")
    for name, (value, limit) in result["compared"].items():
        print(f"benchmark: compared {name} {value!r} limit {limit!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
