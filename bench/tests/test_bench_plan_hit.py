"""``engine.plan_hit_share``: its reader on known RunStats and on a
program without the counter, and a CPU rehearsal (Pallas kernels in
interpret mode) in which every call after warm-up replays its plan."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import run as run_cli  # noqa: E402
from benchkit import cellrun, layout, loadgen  # noqa: E402

NAME = "engine.plan_hit_share"
BENCH = layout.load_benchmark()
CELL = BENCH["workloads"][0]
PEAKS = layout.peaks("TPU v5 lite")


def _rec(requests):
    return cellrun.RunRecord(
        cell="x", cfg={}, mix={}, seconds=2.0, call_names=["a", "b"],
        work=[], requests=requests, t_start=0.0, t_end=2.0, t_drained=3.0,
        setup={"setup_s": 1.0}, peaks=PEAKS)


def _request(*calls):
    return loadgen.Request(client=0, seq=0, set_idx=0, due=0.0, start=0.0,
                           done=1.0, call_done=[0.5, 1.0],
                           stats=[list(c) for c in calls])


def test_reader_on_known_stats_and_without_the_counter():
    from repro.core.simulator import RunStats

    def st(hit):
        return RunStats(plan_hit=hit)
    rec = _rec([_request([st(1), st(0)], [st(1)]),
                _request([st(1)], [st(1), st(1), st(0)])])
    assert layout.metric_reader(NAME).read(rec) == pytest.approx(5 / 7)

    class OldStats:         # a RunStats from before the counter
        gang_size, wall_time_s, tile_batches = 1, 0.1, 3
    old = _rec([_request([OldStats()], [OldStats()])])
    assert layout.metric_reader(NAME).read(old) is None
    assert layout.metric_reader(NAME).read(_rec([])) is None


def test_rehearsal_replays_every_call_after_warm_up():
    cfg = layout.config(BENCH, CELL["config"])
    cfg["layers"] = {"T3": dict(h=8, ic=16, oc=16, k=3, stride=1, shift=9),
                     "T1": dict(h=8, ic=16, oc=32, k=1, stride=2, shift=8)}
    cfg["calls"] = [{"layer": "T3", "relu": True},
                    {"layer": "T1", "relu": False}]
    mix = {"loop": "closed", "clients": 2, "pool_size": 2,
           "sched": {"gang_width": 2}, "input_sets": 4}
    rec = cellrun.run(CELL["name"], cfg, mix, 3_000_000_031, 0.6, False,
                      time.perf_counter(), peaks=PEAKS)
    assert rec.correct and rec.finished

    class _Dev:
        platform, device_kind = "cpu", "cpu"
    line = run_cli.result_line(rec, BENCH, CELL, True, [_Dev()])
    assert line["metrics"][NAME] == {"value": 1.0, "unit": "hits/segment"}
