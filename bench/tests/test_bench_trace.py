"""The trace reduction: busy union, kernel time, labelled idle gaps, on
synthetic events whose answers are known and on a small trace recorded
on one TPU v5e (two tpu_like ResNet-18 convs, C12 and C11, each as a
gang of two, inside ``bench.window``)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

from benchkit import trace  # noqa: E402
from benchkit.trace import Op, Span  # noqa: E402

RECORDED = BENCH_DIR / "tests" / "data" / "v5e_tpu_like_c12_c11.xplane.pb"


def test_merge_and_gaps_of_known_intervals():
    merged = trace.merge([(5, 7), (0, 2), (1, 3), (6, 9), (12, 12), (11, 13)])
    assert merged == [(0, 3), (5, 9), (11, 13)]
    assert trace.gaps(merged, 0, 20) == [(3, 5), (9, 11), (13, 20)]
    assert trace.gaps(merged, 1, 6) == [(3, 5)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_summary_of_synthetic_chips_and_spans():
    ops = [[Op("fusion.1", 0, 10, "jit_vta_gemm_pallas(3)"),
            Op("custom-call.2", 5, 20, "jit_vta_gemm_pallas(3)"),
            Op("fusion.7", 40, 50, "jit_tensor_alu_pallas(9)"),
            Op("copy.1", 90, 130, "jit_pad(1)")],      # clipped at 100
           [Op("fusion.1", 0, 50, "jit_lut_gemm_pallas(2)")]]
    spans = [Span(trace.WINDOW_SPAN, 0, 100),
             Span("bench.call.C2", 15, 60),
             Span("bench.call.C3", 55, 100),
             Span("bench.call.C2", 20, 45)]
    s = trace.summarize(ops, spans, 0, 100)
    # chip 0: [0,20] + [40,50] + [90,100] = 40; chip 1: 50 -> mean 45
    assert s.busy_ns == 45 and s.window_ns == 100
    assert s.idle_share == pytest.approx(0.55)
    assert s.kernel_ns == {"vta_gemm": 25, "tensor_alu": 10, "lut_gemm": 50}
    assert s.n_ops == 5
    # longest gaps first: chip 1 [50,100], chip 0 [50,90], [20,40]
    assert [round(g[1] * 1e9) for g in s.gaps] == [50, 40, 20]
    assert s.gaps[0][0] == "bench.call.C3"          # midpoint 75
    assert s.gaps[1][0] == "bench.call.C3"          # midpoint 70
    assert s.gaps[2][0] == "bench.call.C2"          # midpoint 30
    bd = s.breakdown()
    assert bd["device_ops"][0][0] == "jit_lut_gemm_pallas/fusion"
    assert bd["device_ops"][0][1] == pytest.approx(50e-9)
    assert len(bd["idle_gaps"]) == 3


def test_op_key_reads_hlo_text():
    op = Op("%vta_gemm_pallas.1 = s8[2,896,512]{2,1,0:T(8,128)(4,1)} "
            "custom-call(s8[2,896,4608]{2,1,0} %a.1)", 0, 1,
            "jit_vta_gemm_pallas(8884183566529078820)")
    assert trace.op_key(op) == "jit_vta_gemm_pallas/vta_gemm_pallas s8[2,896,512]"
    assert trace.kernel_of(op) == "vta_gemm"
    assert trace.op_key(Op("copy.3", 0, 1)) == "copy"


def test_gap_without_a_span_is_labelled_so():
    s = trace.summarize([[Op("a", 0, 1)]], [], 0, 10)
    assert len(s.gaps) == 1 and s.gaps[0][0] == "(no bench span)"
    assert s.gaps[0][1] == pytest.approx(9e-9)


def test_recorded_v5e_trace():
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(
        RECORDED.read_bytes())
    ops, spans = trace.from_profile(pd, chips=1)
    assert len(ops) == 1 and ops[0], "no TPU ops found in the recording"
    win = trace.window_of(spans)
    assert win is not None
    calls = sorted(s.name for s in spans if s.name.startswith("bench.call"))
    assert calls == ["bench.call.C11", "bench.call.C12"]
    s = trace.summarize(ops, spans, *win)
    assert 0 < s.busy_ns < s.window_ns
    assert s.kernel_ns.get("vta_gemm", 0) > 0
    assert sum(s.kernel_ns.values()) <= s.busy_ns * 1.0001
    assert s.gaps and all(g[1] > 0 for g in s.gaps)
    assert any(g[0].startswith("bench.call.") for g in s.gaps)
