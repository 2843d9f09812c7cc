"""``engine.upload_mb_per_img`` and ``engine.wgt_resident_share``: their
readers on known RunStats and on a program without the counters, and a
CPU rehearsal (Pallas kernels in interpret mode) in which every call
after warm-up finds its weight operands on the device."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import run as run_cli  # noqa: E402
from benchkit import cellrun, layout, loadgen  # noqa: E402

UPLOAD = "engine.upload_mb_per_img"
SHARE = "engine.wgt_resident_share"
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


def test_readers_on_known_stats_and_without_the_counters():
    from repro.core.simulator import RunStats

    def st(up, need, hit, gang=1):
        return RunStats(upload_bytes=up, wgt_bytes=need, wgt_hit_bytes=hit,
                        gang_size=gang)
    # image 1: 3 MB alone, then a gang of 2 sharing 4 MB; image 2: 1 MB
    rec = _rec([_request([st(3e6, 100, 100)], [st(4e6, 200, 50, gang=2)]),
                _request([st(1e6, 300, 300)])])
    assert layout.metric_reader(UPLOAD).read(rec) == pytest.approx(3.0)
    assert layout.metric_reader(SHARE).read(rec) \
        == pytest.approx(100 * 450 / 600)

    class OldStats:         # a RunStats from before the counters
        gang_size, wall_time_s, tile_batches = 1, 0.1, 3
    old = _rec([_request([OldStats()], [OldStats()])])
    for name in (UPLOAD, SHARE):
        assert layout.metric_reader(name).read(old) is None
        assert layout.metric_reader(name).read(_rec([])) is None
    no_gemm = _rec([_request([st(1e6, 0, 0)])])
    assert layout.metric_reader(SHARE).read(no_gemm) is None


def test_rehearsal_finds_every_weight_on_the_device_after_warm_up():
    cfg = layout.config(BENCH, CELL["config"])
    cfg["layers"] = {"T3": dict(h=8, ic=16, oc=16, k=3, stride=1, shift=9),
                     "T1": dict(h=8, ic=16, oc=32, k=1, stride=2, shift=8)}
    cfg["calls"] = [{"layer": "T3", "relu": True},
                    {"layer": "T1", "relu": False}]
    mix = {"loop": "closed", "clients": 2, "pool_size": 2,
           "sched": {"gang_width": 2}, "input_sets": 4}
    rec = cellrun.run(CELL["name"], cfg, mix, 3_000_000_041, 0.6, False,
                      time.perf_counter(), peaks=PEAKS)
    assert rec.correct and rec.finished
    calls = [st for r in rec.requests for call in r.stats for st in call]
    assert calls and all(st.wgt_hit_bytes == st.wgt_bytes > 0
                         for st in calls)

    class _Dev:
        platform, device_kind = "cpu", "cpu"
    line = run_cli.result_line(rec, BENCH, CELL, True, [_Dev()])
    assert line["metrics"][SHARE] == {"value": 100.0, "unit": "%"}
    assert line["metrics"][UPLOAD]["unit"] == "MB/img"
    assert line["metrics"][UPLOAD]["value"] > 0
