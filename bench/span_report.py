"""Where the host's time goes in one cell, by the program's own spans.

    python3 bench/span_report.py --workload <cell> --seed <n> \
        --seconds <s> [--xspace <file>]

Runs the cell once as ``bench/run.py --trace 0`` does, inside one
profiler session that also records the program's ``vta.*`` spans
(``benchkit/spans.py``), and reduces the window in memory: each span's
time per image (window totals over the calls finished in the window,
divided by the calls per image), the engine gang's self time, the
device's idle time split by the program span the host was in, the ten
longest idle gaps labelled with it, and the serving plane's wait
counters.  It prints those lines to stderr and one JSON object as the
last line of stdout; with ``--xspace`` it also writes the trace.  This
is a traced run for finding where the time goes: its rates are not the
benchmark's.  It exits non-zero with no result where ``bench/run.py``
would refuse.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is timed from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

from benchkit import cellrun, layout, spans, trace  # noqa: E402


def report(rec: cellrun.RunRecord, pd, chips: int) -> Dict:
    """The program-span reduction of one run's window, from its trace
    ``pd`` (a ``jax.profiler.ProfileData``) and its record."""
    ops, bench_spans = trace.from_profile(pd, chips)
    lo, hi = trace.window_of(bench_spans)
    prog = spans.from_profile(pd)
    calls = sum(1 for s in bench_spans if s.name.startswith("bench.call.")
                and lo < s.end <= hi)
    images = calls / len(rec.call_names)
    per_img = 1e-6 / images if images else float("nan")   # ns -> ms/img
    segs = spans.segments(prog, lo, hi)
    idle: Dict[str, float] = {}
    gap_list = []
    for chip_ops in ops or [[]]:
        gaps = spans.idle_gaps(chip_ops, lo, hi)
        for k, v in spans.idle_by_span(segs, gaps).items():
            idle[k] = idle.get(k, 0.0) + v / max(1, len(ops))
        gap_list.extend(gaps)
    idle_ns = sum(idle.values())
    park = [sum(st.park_s for c in r.stats for st in c)
            for r in rec.finished]
    queue = [sum(st.queue_s for c in r.stats for st in c)
             for r in rec.finished]
    return {
        "window_s": (hi - lo) * 1e-9, "images": images,
        "span_ms_per_img": {k: v * per_img for k, v in sorted(
            spans.totals(prog, lo, hi).items())},
        "gang_self_ms_per_img": spans.self_ns(prog, spans.GANG, lo, hi)
        * per_img,
        "idle_s": idle_ns * 1e-9,
        "idle_share_by_span": {k: 100.0 * v / idle_ns for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])} if idle_ns else {},
        "idle_gaps": [[n, s] for n, s in spans.gap_labels(
            gap_list, segs, bench_spans)],
        "park_ms_per_img": 1e3 * sum(park) / len(park) if park else None,
        "queue_ms_per_img": 1e3 * sum(queue) / len(queue) if queue else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--xspace", type=Path, default=None)
    args = ap.parse_args(argv)
    try:
        bench = layout.load_benchmark()
        cell = layout.cell(bench, args.workload)
        cfg = layout.config(bench, cell["config"])
        mix = layout.traffic(cell["traffic"])
    except (layout.LayoutError, OSError, ValueError) as e:
        print(f"bench/span_report.py: {e}", file=sys.stderr)
        return 2
    src = layout.find_checkout_src()
    if src is None:
        print("bench/span_report.py: the checkout has no src/repro",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(layout.ROOT / ".jax_cache")
    import jax

    devs = jax.devices()       # the runtime starts before the session
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench/span_report.py: cell {cell['name']} needs "
              f"{cell['chips']} TPU chip(s); device 0 is "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 2
    from repro import compile_cache
    from repro.core.backend import PallasBackend

    compile_cache.enable()
    engine = PallasBackend()
    if engine.resolved_interpret:
        print("bench/span_report.py: PallasBackend resolved "
              "interpret=True on a TPU", file=sys.stderr)
        return 2
    session = cellrun._start_trace()
    rec = cellrun.run(cell["name"], cfg, mix, args.seed, args.seconds,
                      False, T0, peaks=layout.peaks(devs[0].device_kind),
                      chips=cell["chips"], engine=engine)
    xspace = session.stop()
    if args.xspace is not None:
        args.xspace.parent.mkdir(parents=True, exist_ok=True)
        args.xspace.write_bytes(xspace)
    out = report(rec, jax.profiler.ProfileData.from_serialized_xspace(
        xspace), cell["chips"])
    out["correct"] = rec.correct
    for k, v in out["span_ms_per_img"].items():
        cellrun.log(f"span {k}: {v:.3f} ms/img")
    cellrun.log(f"span {spans.GANG} self: "
                f"{out['gang_self_ms_per_img']:.3f} ms/img")
    cellrun.log(f"idle {out['idle_s']:.3f} s of {out['window_s']:.3f} s, "
                "by program span: " + ", ".join(
                    f"{k} {v:.2f}%"
                    for k, v in out["idle_share_by_span"].items())
                + f"; serving-plane wait per image: park "
                f"{out['park_ms_per_img']} ms, queue "
                f"{out['queue_ms_per_img']} ms")
    for name, s in out["idle_gaps"]:
        cellrun.log(f"idle gap {s * 1e3:.3f} ms under {name}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
