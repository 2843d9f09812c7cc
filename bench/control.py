"""The control for a cell's comparison: the plain reference, computed one
precision below the configuration's (int4 weights for int8), put in the
program's place.  It has to come out not correct; its smallest reading
is the upper end that the comparison's limit is set against.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

Per seed it makes the cell's data from the seed (as a run does), and
puts the control's outputs for every input set the traffic mix uses
through the comparison a run makes (``cellrun.compare``), in the place
of the program's.  The last line of stdout is one JSON object with the
readings and the verdict.  The benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))


def readings(cfg: dict, model, data: dict, n_sets: int,
             wgt_bits: int = 4) -> list:
    """Per input set: the checks a run would report, had the program
    answered with the control's outputs."""
    from benchkit import cellrun

    return [cellrun.compare([(model.reference(cfg, data, s,
                                              wgt_bits=wgt_bits),
                              model.reference(cfg, data, s))])
            for s in range(n_sets)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchkit import layout

    bench = layout.load_benchmark()
    cell = layout.cell(bench, args.workload)
    cfg = layout.config(bench, cell["config"])
    mix = layout.traffic(cell["traffic"])
    model = layout.model(cfg["model"])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from benchkit import cellrun

    per_seed = {}
    for seed in args.seeds:
        data = model.make_data(cfg, seed, int(mix["input_sets"]))
        r = readings(cfg, model, data, int(mix["input_sets"]))
        wrong = [c["wrong_values"]["value"] for c in r]
        per_seed[seed] = {"correct": all(cellrun.passes(c) for c in r),
                          "wrong_values_min_image": min(wrong),
                          "wrong_values_all_images": sum(wrong),
                          "max_abs_err_min_image": min(
                              c["max_abs_err"]["value"] for c in r)}
        print(f"seed {seed}: {per_seed[seed]}", file=sys.stderr, flush=True)
    print(json.dumps({"workload": cell["name"], "control": "int4 weights",
                      "platform": jax.devices()[0].platform,
                      "seeds": per_seed}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
