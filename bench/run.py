"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
BENCHMARK.json (see ``benchkit/layout.py``).  With ``--trace 0`` the
result's metrics are the cell's end-to-end metrics; with ``--trace 1``
the window runs under the profiler and the metrics are its per-layer
ones.  JAX's persistent compilation cache is the checkout's
``.jax_cache``, given to the program through ``JAX_COMPILATION_CACHE_DIR``
whatever that was set to.  The last line of stdout is one JSON object:

    {"correct": ..., "attempted": <images>, "failed": <images>,
     "metrics": {name: {"value": v, "unit": u}}, "device": {...},
     ["breakdown": {...},] "checks": {name: {"value": v, "limit": l}}}

and the numbers compared with the reference are also the last lines of
stderr.  The command exits non-zero with no result when device 0 is not
a TPU, when there are fewer chips than the cell asks for, when the
engine would run the Pallas interpreter, or when the checkout has no
``src/repro``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is timed from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))


def _refuse(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(rec, bench: dict, cell: dict, trace: bool, devs) -> dict:
    from benchkit import cellrun, layout

    kind = "per_layer" if trace else "end_to_end"
    out = {"correct": rec.correct,
           "attempted": len(rec.requests),
           "failed": rec.failed,
           "metrics": cellrun.metric_values(
               rec, layout.cell_metrics(bench, cell["name"], kind)),
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs),
                      "memory_peak_bytes": rec.memory_peak_bytes}}
    if trace and rec.trace is not None:
        out["device"]["busy_s"] = rec.trace.busy_ns * 1e-9
        out["device"]["window_s"] = rec.trace.window_ns * 1e-9
        out["breakdown"] = rec.trace.breakdown()
    out["checks"] = rec.checks
    return out


def main(argv=None) -> int:
    args = parse(argv)
    from benchkit import cellrun, layout

    setup = {}
    t = [T0]

    def mark(name: str) -> None:
        """Close the set-up phase `name`, which ran since the last mark."""
        now = time.perf_counter()
        setup[f"{name}_s"] = now - t[0]
        t[0] = now

    try:
        bench = layout.load_benchmark()
        cell = layout.cell(bench, args.workload)
        cfg = layout.config(bench, cell["config"])
        mix = layout.traffic(cell["traffic"])
    except (layout.LayoutError, OSError, ValueError) as e:
        return _refuse(str(e))
    src = layout.find_checkout_src()
    if src is None:
        return _refuse("the checkout has no src/repro: nothing to measure")
    sys.path.insert(0, str(src))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(layout.ROOT / ".jax_cache")
    mark("start")

    import jax
    mark("jax_import")

    devs = jax.devices()
    mark("runtime")
    if devs[0].platform != "tpu":
        return _refuse(f"device 0 is {devs[0].platform!r}, not a TPU; "
                       "this benchmark runs on the chip only")
    if len(devs) < cell["chips"]:
        return _refuse(f"cell {cell['name']} needs {cell['chips']} chips, "
                       f"JAX found {len(devs)}")
    try:
        peaks = layout.peaks(devs[0].device_kind)
    except layout.LayoutError as e:
        return _refuse(str(e))

    from repro import compile_cache
    from repro.core.backend import PallasBackend
    mark("repro_import")

    cache = compile_cache.enable()
    mark("cache")
    engine = PallasBackend()
    mark("engine")
    if engine.resolved_interpret:
        return _refuse("PallasBackend resolved interpret=True on a TPU")
    cellrun.log(f"device {devs[0].platform} {devs[0].device_kind} "
                f"x{len(devs)}, jax {jax.__version__}, cell {cell['name']}, "
                f"seed {args.seed}, compile cache {cache}")

    rec = cellrun.run(cell["name"], cfg, mix, args.seed, args.seconds,
                      bool(args.trace), T0, peaks=peaks,
                      chips=cell["chips"], engine=engine, setup=setup)
    line = result_line(rec, bench, cell, bool(args.trace), devs)
    if not args.trace:
        readable = cellrun.metric_values(
            rec, layout.cell_metrics(bench, cell["name"], "per_layer"))
        cellrun.log("per-layer, from this run's counters: " + ", ".join(
            f"{k} {v['value']}" for k, v in readable.items()))
    for name, c in rec.checks.items():
        cellrun.log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
