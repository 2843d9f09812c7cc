"""The program's own spans and counters as the benchmark reads them:
the span reduction (``benchkit/spans.py``) on synthetic spans whose
answers are known and on a small trace recorded on one TPU v5e; the five
engine and serving-plane readers on known RunStats and on traced CPU
rehearsals; ``span_report.py`` on a CPU run and its refusal off the
chip."""
from __future__ import annotations

import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

import run as run_cli  # noqa: E402
import span_report  # noqa: E402
from benchkit import cellrun, layout, loadgen, spans, trace  # noqa: E402
from benchkit.spans import HostSpan  # noqa: E402

RECORDED = BENCH_DIR / "tests" / "data" / \
    "v5e_tpu_like_c12_c11_spans.xplane.pb"
BENCH = layout.load_benchmark()
CELL = BENCH["workloads"][0]
PEAKS = layout.peaks("TPU v5 lite")
ENGINE = ("engine.host_ms_per_img", "engine.stage_ms_per_img",
          "engine.launch_ms_per_img", "engine.sync_ms_per_img")
NEW = ENGINE + ("serve.wait_ms_per_img",)


# a gang with two phases on line 0, staging before it; line 1 waits
SYNTH = [HostSpan("vta.pool.stage_inputs", 0, 10, 0),
         HostSpan("vta.engine.gang", 10, 100, 0),
         HostSpan("vta.engine.stage", 20, 30, 0),
         HostSpan("vta.engine.launch", 30, 35, 0),
         HostSpan("vta.engine.sync", 35, 60, 0),
         HostSpan("vta.pool.idle", 100, 140, 0),
         HostSpan("vta.sched.hold", 50, 130, 1),
         HostSpan("vta.engine.launch", 40, 45, 2)]   # another thread


def test_totals_are_clipped_to_the_window():
    t = spans.totals(SYNTH, 25, 120)
    assert t["vta.engine.gang"] == 75
    assert t["vta.engine.stage"] == 5
    assert t["vta.engine.launch"] == 5 + 5
    assert t["vta.pool.idle"] == 20
    assert t["vta.sched.hold"] == 70
    assert "vta.pool.stage_inputs" not in t


def test_self_time_leaves_out_spans_nested_on_its_own_line():
    # 90 less stage 10, launch 5, sync 25; line 2's launch is not a child
    assert spans.self_ns(SYNTH, spans.GANG, 0, 200) == 50
    # clipped: [25, 120] holds 75 of the gang, 5 + 5 + 25 of its phases
    assert spans.self_ns(SYNTH, spans.GANG, 25, 120) == 40
    nested = [HostSpan("vta.engine.gang", 0, 10, 0),
              HostSpan("vta.engine.stage", 1, 4, 0),
              HostSpan("vta.engine.stage", 2, 3, 0)]   # inside the first
    assert spans.self_ns(nested, spans.GANG, 0, 10) == 7


def test_segments_name_the_innermost_working_span_before_any_wait():
    segs = spans.segments(SYNTH, 0, 150)
    assert [s[0] for s in segs] == sorted(s[0] for s in segs)
    assert sum(b - a for a, b, _ in segs) == 150
    at = {t: spans.label_at(segs, t)
          for t in (5, 15, 25, 32, 42, 55, 65, 110, 135, 145)}
    assert at == {5: "vta.pool.stage_inputs", 15: "vta.engine.gang",
                  25: "vta.engine.stage", 32: "vta.engine.launch",
                  # the other thread's launch started last
                  42: "vta.engine.launch",
                  # a working span outranks the hold on line 1
                  55: "vta.engine.sync", 65: "vta.engine.gang",
                  # of two waits the held batch names the moment
                  110: "vta.sched.hold", 135: "vta.pool.idle",
                  145: spans.NONE}


def test_idle_split_and_gap_labels():
    segs = spans.segments(SYNTH, 0, 150)
    idle = [(12, 22), (50, 70), (120, 150)]
    split = spans.idle_by_span(segs, idle)
    assert split == {"vta.engine.gang": 8 + 10, "vta.engine.stage": 2,
                     "vta.engine.sync": 10, "vta.sched.hold": 10,
                     "vta.pool.idle": 10, spans.NONE: 10}
    bench = [trace.Span("bench.call.C2", 0, 100)]
    labels = spans.gap_labels(idle, segs, bench, top=2)
    assert [n for n, _ in labels] == ["vta.pool.idle+(no bench span)",
                                     "vta.engine.gang+bench.call.C2"]
    assert labels[0][1] == pytest.approx(30e-9)


def test_a_trace_without_program_spans_reads_as_empty():
    assert spans.totals([], 0, 10) == {}
    assert spans.self_ns([], spans.GANG, 0, 10) == 0
    segs = spans.segments([], 0, 10)
    assert segs == [(0, 10, spans.NONE)]
    assert spans.idle_by_span(segs, [(2, 5)]) == {spans.NONE: 3}
    assert spans.idle_gaps([trace.Op("a", 0, 1)], 0, 10) == [(1, 10)]


def test_recorded_v5e_device_ops_lie_inside_engine_gangs():
    """Two tpu_like convs (C12, C11), each a gang of two through the
    pool, recorded on one v5e: every ``vta_gemm`` op on the device lies
    inside a ``vta.engine.gang`` host span, so the host spans and the
    device planes share one clock."""
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(
        RECORDED.read_bytes())
    ops, bench_spans = trace.from_profile(pd, chips=1)
    lo, hi = trace.window_of(bench_spans)
    prog = spans.from_profile(pd)
    gangs = [s for s in prog if s.name == spans.GANG]
    assert len(gangs) == 2
    gemm = [o for o in ops[0] if trace.kernel_of(o) == "vta_gemm"
            and lo <= o.start and o.end <= hi]
    assert gemm, "no vta_gemm op in the recorded window"
    for o in gemm:
        assert any(g.start <= o.start and o.end <= g.end for g in gangs), o
    t = spans.totals(prog, lo, hi)
    phases = sum(t[f"vta.engine.{p}"] for p in ("stage", "launch", "sync"))
    assert 0 < phases < t[spans.GANG]
    assert spans.self_ns(prog, spans.GANG, lo, hi) == pytest.approx(
        t[spans.GANG] - phases)


# ---------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------
def _rec(requests):
    return cellrun.RunRecord(
        cell="x", cfg={}, mix={}, seconds=2.0, call_names=["a", "b"],
        work=[], requests=requests, t_start=0.0, t_end=2.0, t_drained=3.0,
        setup={"setup_s": 1.0}, peaks=PEAKS)


def test_new_readers_on_known_stats():
    from repro.core.simulator import RunStats

    def st(gang, wall, stage, launch, sync, park, queue):
        return RunStats(gang_size=gang, wall_time_s=wall, stage_s=stage,
                        launch_s=launch, sync_s=sync, park_s=park,
                        queue_s=queue)
    r1 = loadgen.Request(client=0, seq=0, set_idx=0, due=0.0, start=0.0,
                         done=1.0, call_done=[0.5, 1.0],
                         stats=[[st(2, 0.4, 0.1, 0.04, 0.06, 0.3, 0.01)],
                                [st(2, 0.2, 0.02, 0.02, 0.02, 0.0, 0.05)]])
    r2 = loadgen.Request(client=1, seq=0, set_idx=1, due=0.0, start=0.0,
                         done=3.0, call_done=[2.0, 3.0],
                         stats=[[st(1, 0.1, 0.01, 0.01, 0.01, 0.0, 0.2)],
                                [st(1, 0.1, 0.02, 0.0, 0.03, 0.0, 0.1)]])
    rec = _rec([r1, r2])

    def val(name):
        return layout.metric_reader(name).read(rec)
    assert val("engine.stage_ms_per_img") == pytest.approx(
        1e3 * ((0.1 + 0.02) / 2 + 0.03) / 2)
    assert val("engine.launch_ms_per_img") == pytest.approx(
        1e3 * ((0.04 + 0.02) / 2 + 0.01) / 2)
    assert val("engine.sync_ms_per_img") == pytest.approx(
        1e3 * ((0.06 + 0.02) / 2 + 0.04) / 2)
    assert val("engine.host_ms_per_img") == pytest.approx(
        1e3 * ((0.2 + 0.14) / 2 + (0.07 + 0.05)) / 2)
    assert sum(val(n) for n in ENGINE) == pytest.approx(
        val("engine.wall_ms_per_img"))
    # each request's own wait, not shared out over its gang
    assert val("serve.wait_ms_per_img") == pytest.approx(
        1e3 * ((0.3 + 0.01 + 0.05) + (0.2 + 0.1)) / 2)


def test_new_readers_leave_out_a_program_without_the_fields():
    class OldStats:         # a RunStats from before the phase fields
        gang_size, wall_time_s, tile_batches = 1, 0.1, 3
    r = loadgen.Request(client=0, seq=0, set_idx=0, due=0.0, start=0.0,
                        done=1.0, call_done=[0.5, 1.0],
                        stats=[[OldStats()], [OldStats()]])
    rec = _rec([r])
    for name in NEW:
        assert layout.metric_reader(name).read(rec) is None, name
    assert layout.metric_reader("engine.wall_ms_per_img").read(rec) == \
        pytest.approx(200.0)
    assert all(layout.metric_reader(n).read(_rec([])) is None for n in NEW)


# ---------------------------------------------------------------------
# CPU rehearsals (interpret mode) of a traced run and of span_report
# ---------------------------------------------------------------------
def tiny_cfg() -> dict:
    cfg = layout.config(BENCH, CELL["config"])
    cfg["layers"] = {"T3": dict(h=8, ic=16, oc=16, k=3, stride=1, shift=9),
                     "T1": dict(h=8, ic=16, oc=32, k=1, stride=2, shift=8)}
    cfg["calls"] = [{"layer": "T3", "relu": True},
                    {"layer": "T1", "relu": False}]
    return cfg


MIX = {"loop": "closed", "clients": 2, "pool_size": 2,
       "sched": {"gang_width": 2}, "input_sets": 4}


class _Dev:
    platform, device_kind = "cpu", "cpu"


def test_traced_rehearsal_reports_the_new_metrics():
    rec = cellrun.run(CELL["name"], tiny_cfg(), dict(MIX), 3_000_000_023,
                      0.6, True, time.perf_counter(), peaks=PEAKS)
    assert rec.correct
    m = run_cli.result_line(rec, BENCH, CELL, True, [_Dev()])["metrics"]
    assert set(NEW) <= set(m)
    assert all(m[n]["unit"] == "ms/img" for n in NEW)
    assert all(m[n]["value"] > 0 for n in NEW)
    engine = sum(m[n]["value"] for n in ENGINE)
    assert engine == pytest.approx(m["engine.wall_ms_per_img"]["value"],
                                   rel=0.10)


def test_span_report_on_a_cpu_run_agrees_with_the_counters():
    session = cellrun._start_trace()
    try:
        rec = cellrun.run(CELL["name"], tiny_cfg(), dict(MIX),
                          3_000_000_029, 0.6, False, time.perf_counter(),
                          peaks=PEAKS)
    finally:
        xspace = session.stop()
    import jax

    out = span_report.report(
        rec, jax.profiler.ProfileData.from_serialized_xspace(xspace), 1)
    assert rec.correct and out["images"] > 0
    ms = out["span_ms_per_img"]
    assert {spans.GANG, "vta.engine.stage", "vta.engine.launch",
            "vta.engine.sync", "vta.pool.stage_inputs"} <= set(ms)
    # every call ran inside the window span, so the spans' window
    # totals and the counters sum the same gang windows
    calls = [st for r in rec.requests for c in r.stats for st in c]
    n_img = out["images"]
    for phase in ("stage", "launch", "sync"):
        counted = 1e3 * sum(getattr(st, f"{phase}_s") / st.gang_size
                            for st in calls) / n_img
        assert ms[f"vta.engine.{phase}"] == pytest.approx(counted, rel=0.10)
    gang = 1e3 * sum(st.wall_time_s / st.gang_size for st in calls) / n_img
    assert ms[spans.GANG] == pytest.approx(gang, rel=0.10)
    assert out["gang_self_ms_per_img"] == pytest.approx(
        ms[spans.GANG] - sum(ms[f"vta.engine.{p}"]
                             for p in ("stage", "launch", "sync")))
    # no device planes on the CPU: the whole window is idle
    assert out["idle_s"] == pytest.approx(out["window_s"])
    assert sum(out["idle_share_by_span"].values()) == pytest.approx(100.0)
    assert len(out["idle_gaps"]) == 1
    assert out["park_ms_per_img"] > 0 and out["queue_ms_per_img"] > 0


def test_span_report_refuses_without_a_tpu():
    import os

    p = subprocess.run(
        [sys.executable, "bench/span_report.py", "--workload", CELL["name"],
         "--seed", "3000000001", "--seconds", "1"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr
