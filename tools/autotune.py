"""Design-space autotuner CLI — the one DSE entry point in tools/.

Runs the two-stage seeded search of ``repro.core.autotune`` (calibrated
TimingModel replay as the cheap oracle over every candidate, measured
wall time + cross-engine byte validation for the top-N) over a conv
and/or matmul workload, prints the trajectory, diffs the winner against
a stored baseline JSON (the old hillclimb-style report), and persists
the winning decisions into a TuningCache file that ``Program.compile``
auto-loads via ``REPRO_TUNE_CACHE``.

Exits 1 unless, for every workload, the winner is validated, beats the
base template on the oracle, is at least 1.1x faster measured, and a
recompile of its program resolves every accelerator op from the tuning
cache.

Usage:
  PYTHONPATH=src python tools/autotune.py conv --seed 0 --candidates 24
  PYTHONPATH=src python tools/autotune.py matmul --m 128 --k 256 --n 256
  PYTHONPATH=src python tools/autotune.py both \\
      --cache tuning_cache.json --out run.json --baseline base.json
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import autotune, hwspec                     # noqa: E402
from repro.core.conv import ConvShape                       # noqa: E402

#: the least measured speed-up over the base template that counts as a win
MIN_MEASURED_SPEEDUP = 1.1


def _winner_failures(wl, res) -> list:
    """Why the search's winner on `wl` does not stand ([] where it does):
    it must be validated, ahead of the base on the oracle and at least
    MIN_MEASURED_SPEEDUP faster measured, and a recompile of its program
    must resolve every accelerator op from the tuning cache."""
    w = res.winner
    if w is None or not w.validated:
        return [f"{wl.name}: no validated winner"]
    bad = []
    if not w.predicted_cycles < res.baseline.predicted_cycles:
        bad.append(f"{wl.name}: winner does not beat the base on the oracle")
    if (res.speedup_measured or 0.0) < MIN_MEASURED_SPEEDUP:
        bad.append(f"{wl.name}: measured speed-up "
                   f"{res.speedup_measured or 0.0:.2f}x < "
                   f"{MIN_MEASURED_SPEEDUP}x")
    prog, _, _ = wl.build(w.candidate.spec, w.candidate.virtual_threads,
                          w.candidate.lowering)
    n_ops = sum(1 for n in prog.nodes if n.op in ("conv2d", "matmul"))
    c = prog.compile(use_cache=False)
    if (c.tune_hits, c.tune_misses) != (n_ops, 0):
        bad.append(f"{wl.name}: recompiled winner resolved {c.tune_hits} "
                   f"of {n_ops} op(s) from the tuning cache")
    return bad


def _diff_vs_baseline(result_json: dict, baseline_path: str) -> None:
    """Hillclimb-style report: percent deltas of the winner's predicted
    cycles and measured wall against the stored trajectory JSON."""
    if not os.path.exists(baseline_path):
        print(f"(no baseline at {baseline_path} — skipping diff)")
        return
    with open(baseline_path) as f:
        base = json.load(f)
    base_by_name = {w["workload"]: w for w in base.get("workloads", [])}
    print("\n=== delta vs baseline ===")
    for w in result_json["workloads"]:
        b = base_by_name.get(w["workload"])
        if b is None or b.get("winner") is None or w["winner"] is None:
            print(f"{w['workload']:24s}: no comparable baseline winner")
            continue
        for k, scale, unit in (("predicted_cycles", 1, "cyc"),
                               ("measured_s", 1e3, "ms")):
            bv, cv = b["winner"].get(k), w["winner"].get(k)
            if not bv or not cv:
                continue
            pct = (cv - bv) / bv * 100
            print(f"{w['workload']:24s} {k:16s}: {bv * scale:10.2f} -> "
                  f"{cv * scale:10.2f} {unit}  ({pct:+.1f}%)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload", choices=("conv", "matmul", "both"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--candidates", type=int, default=24,
                    help="sampled design points (oracle stage)")
    ap.add_argument("--top", type=int, default=4,
                    help="candidates measured + validated (stage 2)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--conv-hw", type=int, default=14,
                    help="conv spatial size (H=W)")
    ap.add_argument("--conv-c", type=int, default=32,
                    help="conv channels (ic=oc)")
    ap.add_argument("--conv-khw", type=int, default=3,
                    help="conv kernel size (kh=kw), stride 1, same pad")
    ap.add_argument("--spec", choices=("pynq", "calibrated"),
                    default="calibrated",
                    help="base template instance to search around")
    ap.add_argument("--cache", default=None,
                    help="TuningCache JSON to merge winners into "
                         "(load+save; point REPRO_TUNE_CACHE here)")
    ap.add_argument("--baseline", default=None,
                    help="stored trajectory JSON (an earlier --out) to "
                         "diff the winner against")
    ap.add_argument("--out", default=None,
                    help="write this run's trajectory JSON here")
    args = ap.parse_args(argv)

    base_spec = (hwspec.calibrated() if args.spec == "calibrated"
                 else hwspec.pynq())
    cache = autotune.global_cache()
    if args.cache and os.path.exists(args.cache):
        print(f"loaded {cache.load(args.cache)} record(s) from "
              f"{args.cache}")

    workloads = []
    if args.workload in ("conv", "both"):
        khw, hw, c = args.conv_khw, args.conv_hw, args.conv_c
        workloads.append(autotune.conv_workload(
            ConvShape(n=1, h=hw, w=hw, ic=c, oc=c, kh=khw, kw=khw,
                      stride=1, pad=khw // 2), seed=args.seed))
    if args.workload in ("matmul", "both"):
        workloads.append(autotune.matmul_workload(
            args.m, args.k, args.n, seed=args.seed))

    out = {"seed": args.seed, "base_spec": autotune.spec_key(base_spec),
           "workloads": []}
    failures = []
    for wl in workloads:
        res = autotune.search(wl, base_spec=base_spec, seed=args.seed,
                              n_candidates=args.candidates,
                              top_n=args.top, repeats=args.repeats,
                              cache=cache, log=print)
        out["workloads"].append(res.to_json())
        failures += _winner_failures(wl, res)
        if res.winner is not None:
            cfg = res.sched_config()
            print(f"  serving knobs: gang_width={cfg.gang_width} "
                  f"window_us={cfg.window_us:.0f}")

    if args.cache:
        cache.save(args.cache)
        print(f"saved {len(cache)} record(s) to {args.cache}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"trajectory written to {args.out}")
    if args.baseline:
        _diff_vs_baseline(out, args.baseline)
    for msg in failures:
        print(f"FAIL {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
