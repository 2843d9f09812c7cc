"""General conv2d on the Pallas fast path: zero eager-GEMM iterations.

The tentpole claim of the conv-lowering generalization: ResNet C2/C4-style
kh*kw>1 layers — not just pointwise 1x1s — execute on ``PallasBackend``
entirely through coalesced ``vta_gemm`` tiles, bit-exact against the
numpy oracle, with the eager per-uop loop never taken.  The
``RunStats.eager_*`` counters (and the ``assert_fast_path`` helper) are
the proof; a ``mock.patch`` on the simulator's eager methods double-checks
the counters aren't lying.
"""
from unittest import mock

import numpy as np
import pytest

from repro.core import hwspec
from repro.core.backend import CrossBackendChecker, assert_fast_path
from repro.core.conv import (ConvShape, conv2d_reference, read_conv_result,
                             schedule_conv2d)
from repro.core.isa import COMPUTE_Q, LOAD_Q, STORE_Q, MemId
from repro.core.program import Program
from repro.core.runtime import Runtime
from repro.core.scheduler import Epilogue
from repro.core.simulator import Simulator

# channel-scaled C2 (56x56 s1) and C4 (56x56 s2) — full spatial extent,
# real 3x3 kernels; channels trimmed so the numpy oracle stays quick
C2_LIKE = ConvShape(n=1, h=56, w=56, ic=32, oc=32, kh=3, kw=3,
                    stride=1, pad=1)
C4_LIKE = ConvShape(n=1, h=56, w=56, ic=32, oc=64, kh=3, kw=3,
                    stride=2, pad=1)


def _run_pallas(shape, ep=None, lowering=None, spec=None):
    spec = spec or hwspec.pynq()
    rng = np.random.default_rng(shape.h * shape.ic + shape.oc)
    x = rng.integers(-64, 64, size=(shape.n, shape.ic, shape.h, shape.w),
                     dtype=np.int8)
    w = rng.integers(-16, 16, size=(shape.oc, shape.ic, shape.kh, shape.kw),
                     dtype=np.int8)
    rt = Runtime(spec)
    plan = schedule_conv2d(rt, x, w, shape, epilogue=ep, lowering=lowering)
    stats = rt.synchronize(backend="pallas")
    got = read_conv_result(rt, plan)
    want = conv2d_reference(x, w, shape, epilogue=ep)
    np.testing.assert_array_equal(got, want)
    return stats


@pytest.mark.parametrize("shape", [C2_LIKE, C4_LIKE],
                         ids=["C2-like", "C4-like"])
def test_resnet_conv_layers_take_zero_eager_gemms(shape):
    stats = _run_pallas(shape, ep=Epilogue(shift=6, relu=True))
    assert stats.eager_gemm_insns == 0
    assert stats.eager_alu_insns == 0
    assert stats.coalesced_gemm_insns > 0
    assert_fast_path(stats)


def test_counters_agree_with_eager_entry_points():
    """Belt and braces: with the eager simulator methods mocked to raise,
    a C2-like layer still executes (so eager_* == 0 is not a counting
    bug)."""
    with mock.patch.object(Simulator, "_do_gemm",
                           side_effect=AssertionError("eager GEMM taken")), \
         mock.patch.object(Simulator, "_do_alu",
                           side_effect=AssertionError("eager ALU taken")):
        _run_pallas(C2_LIKE, ep=Epilogue(shift=6, relu=True))


@pytest.mark.parametrize("lowering", ["direct", "im2col"])
def test_bias_epilogue_stays_on_fast_path(lowering):
    spec = hwspec.pynq()
    shape = ConvShape(n=1, h=14, w=14, ic=32, oc=32, kh=3, kw=3,
                      stride=1, pad=1)
    rng = np.random.default_rng(3)
    bias = rng.integers(-500, 500, size=shape.oc, dtype=np.int32)
    bb = np.repeat(bias.reshape(-1, 1, spec.block_out), spec.batch, axis=1)
    stats = _run_pallas(shape, ep=Epilogue(bias_blocked=bb, shift=5,
                                           relu=True), lowering=lowering)
    assert_fast_path(stats)


def test_batch_blocked_1x1_via_matmul_fast_path():
    """The generalized transposed lowering: batch>1 template instances put
    image blocks in the tensor-register rows and still hit the GEMM fast
    path (the old spec.batch==1 restriction is gone)."""
    spec = hwspec.HardwareSpec(batch=2)
    shape = ConvShape(n=5, h=6, w=6, ic=32, oc=32, kh=1, kw=1,
                      stride=1, pad=0)
    stats = _run_pallas(shape, ep=Epilogue(shift=4, relu=True),
                        lowering="via_matmul", spec=spec)
    assert stats.eager_gemm_insns == 0
    assert stats.coalesced_gemm_insns > 0


def test_batch_blocked_direct_conv_fast_path():
    spec = hwspec.HardwareSpec(batch=2)
    shape = ConvShape(n=4, h=8, w=8, ic=16, oc=32, kh=3, kw=3,
                      stride=1, pad=1)
    stats = _run_pallas(shape, ep=Epilogue(shift=3), spec=spec)
    assert_fast_path(stats)


@pytest.mark.parametrize("spec", [hwspec.pynq(), hwspec.tpu_like()],
                         ids=["pynq", "tpu_like"])
def test_subgrid_coalescing_switch_reverts_to_eager(spec):
    """A GEMM whose micro-op loop writes one accumulator twice has no
    blocked-matmul structure, so the engine runs it with the simulator's
    eager semantics: the correctness net, bit-exact against the
    simulator, and flagged by ``assert_fast_path``."""
    rng = np.random.default_rng(5)
    a = rng.integers(-128, 128, size=(2, spec.batch, spec.block_in),
                     dtype=np.int8)
    w = rng.integers(-128, 128, size=(2, spec.block_out, spec.block_in),
                     dtype=np.int8)
    rt = Runtime(spec)
    a_addr = rt.copy_to_device(a, align=spec.inp_elem_bytes)
    w_addr = rt.copy_to_device(w, align=spec.wgt_elem_bytes)
    c_addr = rt.buffer_alloc(spec.out_elem_bytes, align=spec.out_elem_bytes)
    rt.load_buffer_2d(MemId.INP, 0, rt.to_elem_addr(a_addr, MemId.INP),
                      1, 2, 2)
    rt.load_buffer_2d(MemId.WGT, 0, rt.to_elem_addr(w_addr, MemId.WGT),
                      1, 2, 2)
    rt.dep_push(LOAD_Q, COMPUTE_Q)
    rt.dep_pop(LOAD_Q, COMPUTE_Q)

    def reset(b):
        b.push(dst=0, src=0)

    def twice(b):                       # acc[0] += a[i] @ w[i].T, i < 2
        b.loop_begin(2, dst_factor=0, src_factor=1, wgt_factor=1)
        b.push(dst=0, src=0, wgt=0)
        b.loop_end()

    rt.push_gemm(rt.uop_kernel(reset, key="t.rst"), reset=True)
    rt.push_gemm(rt.uop_kernel(twice, key="t.twice"))
    rt.dep_push(COMPUTE_Q, STORE_Q)
    rt.dep_pop(COMPUTE_Q, STORE_Q)
    rt.store_buffer_2d(0, rt.to_elem_addr(c_addr, MemId.OUT), 1, 1, 1)
    report = CrossBackendChecker().check_runtime(rt)
    assert report.matches, f"{report.mismatched_bytes} bytes differ"
    want = sum(a[i].astype(np.int32) @ w[i].T.astype(np.int32)
               for i in range(2)).astype(np.int8)
    np.testing.assert_array_equal(
        rt.copy_from_device(c_addr, spec.out_elem_bytes, np.int8,
                            (spec.batch, spec.block_out)), want)
    stats = report.stats_for("pallas")
    assert stats.eager_gemm_insns > 0
    assert stats.coalesced_gemm_insns == 0
    with pytest.raises(AssertionError, match="eager"):
        assert_fast_path(stats)


def test_program_conv_chain_fast_path_counters():
    """Whole-graph check: a direct 3x3 -> 1x1 chain through the Program
    JIT reports zero eager hits across every accelerator segment."""
    spec = hwspec.pynq()
    s2 = ConvShape(n=1, h=14, w=14, ic=32, oc=32, kh=3, kw=3,
                   stride=1, pad=1)
    s3 = ConvShape(n=1, h=14, w=14, ic=32, oc=48, kh=1, kw=1,
                   stride=1, pad=0)
    rng = np.random.default_rng(9)
    x = rng.integers(-64, 64, size=(1, 32, 14, 14), dtype=np.int8)
    k2 = rng.integers(-8, 8, size=(32, 32, 3, 3), dtype=np.int8)
    k3 = rng.integers(-8, 8, size=(48, 32, 1, 1), dtype=np.int8)
    ep = Epilogue(shift=5, relu=True)
    p = Program(spec)
    t = p.conv2d(p.input("x", x.shape), p.input("k2", k2.shape), s2,
                 epilogue=ep)
    p.conv2d(t, p.input("k3", k3.shape), s3, epilogue=ep)
    c = p.compile(use_cache=False)
    got = c(backend="pallas", x=x, k2=k2, k3=k3)
    ref = conv2d_reference(conv2d_reference(x, k2, s2, epilogue=ep),
                           k3, s3, epilogue=ep)
    np.testing.assert_array_equal(got, ref)
    assert sum(s.eager_gemm_insns for s in c.last_stats) == 0
    assert_fast_path(c.last_stats)
