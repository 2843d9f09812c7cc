"""``engine.wgt_load_skip_share``: its reader on known RunStats and on a
program without the counters, and a CPU rehearsal (Pallas kernels in
interpret mode) in which no call after warm-up copies a weight byte out
of DRAM."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import run as run_cli  # noqa: E402
from benchkit import cellrun, layout, loadgen  # noqa: E402

SKIP = "engine.wgt_load_skip_share"
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


def test_reader_on_known_stats_and_without_the_counters():
    from repro.core.simulator import RunStats

    def st(named, skipped, gang=1):
        return RunStats(wgt_load_bytes=named, wgt_skip_bytes=skipped,
                        gang_size=gang)
    # every segment of a gang counts: each member's loads are its own
    rec = _rec([_request([st(100, 100)], [st(200, 50, gang=2),
                                          st(200, 200, gang=2)]),
                _request([st(300, 0)])])
    assert layout.metric_reader(SKIP).read(rec) \
        == pytest.approx(100 * 350 / 800)

    class OldStats:         # a RunStats from before the counters
        gang_size, wall_time_s, wgt_bytes, wgt_hit_bytes = 1, 0.1, 8, 8
    old = _rec([_request([OldStats()], [OldStats()])])
    assert layout.metric_reader(SKIP).read(old) is None
    assert layout.metric_reader(SKIP).read(_rec([])) is None
    no_load = _rec([_request([st(0, 0)])])
    assert layout.metric_reader(SKIP).read(no_load) is None


def test_rehearsal_copies_no_weight_byte_after_warm_up():
    cfg = layout.config(BENCH, CELL["config"])
    cfg["layers"] = {"T3": dict(h=8, ic=16, oc=16, k=3, stride=1, shift=9),
                     "T1": dict(h=8, ic=16, oc=32, k=1, stride=2, shift=8)}
    cfg["calls"] = [{"layer": "T3", "relu": True},
                    {"layer": "T1", "relu": False}]
    mix = {"loop": "closed", "clients": 2, "pool_size": 2,
           "sched": {"gang_width": 2}, "input_sets": 4}
    rec = cellrun.run(CELL["name"], cfg, mix, 3_000_000_043, 0.6, False,
                      time.perf_counter(), peaks=PEAKS)
    assert rec.correct and rec.finished
    calls = [st for r in rec.requests for call in r.stats for st in call]
    assert calls and all(st.wgt_skip_bytes == st.wgt_load_bytes > 0
                         for st in calls)

    class _Dev:
        platform, device_kind = "cpu", "cpu"
    line = run_cli.result_line(rec, BENCH, CELL, True, [_Dev()])
    assert line["metrics"][SKIP] == {"value": 100.0, "unit": "%"}
