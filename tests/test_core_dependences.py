"""Dependence-token correctness: the Fig. 5 argument, as executable tests.

(1) results must be invariant to instruction latency (any timing model);
(2) stripping WAR tokens from a double-buffered stream corrupts results
    or deadlocks — dependences are load-bearing, not decorative;
(3) net-negative token balance is rejected by the runtime validator.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import hwspec
from repro.core.backend import resolve_backend
from repro.core.isa import DepFlags, FinishInsn, Insn, Opcode, route_queue
from repro.core.runtime import Runtime
from repro.core.scheduler import matmul_reference, read_matmul_result, \
    schedule_matmul
from repro.core.simulator import (DeadlockError, RunStats, Simulator,
                                  TimingModel, run_program)


class JitterTiming(TimingModel):
    """Random (but deterministic per-seed) per-instruction latencies."""

    def __init__(self, spec, seed):
        super().__init__(spec)
        self.rng = np.random.default_rng(seed)

    def latency(self, insn, spec):
        return int(self.rng.integers(1, 1000))


def _schedule(vt, seed=0, M=64, N=64, K=256):
    spec = hwspec.pynq()
    rng = np.random.default_rng(seed)
    a = rng.integers(-128, 128, size=(M, K), dtype=np.int8)
    w = rng.integers(-128, 128, size=(N, K), dtype=np.int8)
    rt = Runtime(spec)
    plan = schedule_matmul(rt, a, w, virtual_threads=vt)
    return rt, plan, a, w


@given(seed=st.integers(0, 2**16), vt=st.sampled_from([1, 2]))
@settings(max_examples=12, deadline=None)
def test_result_invariant_under_latency_jitter(seed, vt):
    """With correct tokens, ANY latency assignment yields the same result —
    the defining property of a correctly synchronized decoupled
    access-execute stream."""
    rt, plan, a, w = _schedule(vt, seed=seed % 7)
    rt.synchronize(timing=JitterTiming(rt.spec, seed))
    got = read_matmul_result(rt, plan)
    np.testing.assert_array_equal(got, matmul_reference(a, w))


def _strip_flags(insns, which):
    out = []
    for i in insns:
        d = i.dep
        nd = DepFlags(
            pop_prev=d.pop_prev and "pop_prev" not in which,
            pop_next=d.pop_next and "pop_next" not in which,
            push_prev=d.push_prev and "push_prev" not in which,
            push_next=d.push_next and "push_next" not in which)
        i.dep = nd
        out.append(i)
    return out


def test_stripping_war_tokens_corrupts_or_deadlocks():
    """Fig. 5: without WAR dependences a producer can overwrite SRAM before
    the consumer reads it.  We strip the c2l WAR edge (compute->load
    push_prev / load pop_next) and expect wrong results."""
    rt, plan, a, w = _schedule(vt=2, M=256, N=64, K=512)
    stripped = _strip_flags(rt.stream, {"push_prev", "pop_next"})
    stripped.append(FinishInsn(dep=DepFlags()))
    stream = rt.isa.encode_stream(stripped)
    # slow compute, fast loads => loads of iteration k+1 overwrite inputs
    class SlowCompute(TimingModel):
        def latency(self, insn, spec):
            from repro.core.isa import GemmInsn
            return 10_000 if isinstance(insn, GemmInsn) else 1
    run_program(rt.spec, rt.device, stream, timing=SlowCompute(rt.spec))
    got = read_matmul_result(rt, plan)
    want = matmul_reference(a, w)
    assert not np.array_equal(got, want), \
        "stripping WAR tokens should corrupt a double-buffered schedule"


def test_stripping_raw_tokens_corrupts():
    """Without RAW tokens the compute module runs ahead of the loader."""
    rt, plan, a, w = _schedule(vt=2)
    stripped = _strip_flags(rt.stream, {"push_next", "pop_prev"})
    stripped.append(FinishInsn(dep=DepFlags()))
    stream = rt.isa.encode_stream(stripped)
    class SlowLoad(TimingModel):
        def latency(self, insn, spec):
            from repro.core.isa import LoadStoreInsn
            return 10_000 if (isinstance(insn, LoadStoreInsn)
                              and insn.opcode == Opcode.LOAD) else 1
    run_program(rt.spec, rt.device, stream, timing=SlowLoad(rt.spec))
    got = read_matmul_result(rt, plan)
    assert not np.array_equal(got, matmul_reference(a, w))


def test_validator_rejects_negative_balance():
    spec = hwspec.pynq()
    rt = Runtime(spec)
    from repro.core.isa import MemId
    rt.dep_pop(2, 3)  # pending pop with no matching push
    rt.store_buffer_2d(0, 0, 1, 1, 1)
    with pytest.raises(ValueError):
        rt.validate_stream()


@pytest.mark.parametrize("engine", ["simulator", "pallas"])
def test_deadlock_detection(engine):
    """A pop with no pending producer must be detected, not hang, on
    either engine."""
    spec = hwspec.pynq()
    rt = Runtime(spec)
    rt.dep_pop(2, 3)
    rt.store_buffer_2d(0, 0, 1, 1, 1)
    stream = rt.isa.encode_stream(rt.stream + [FinishInsn(dep=DepFlags())])
    with pytest.raises(DeadlockError):
        resolve_backend(engine).execute(spec, rt.device, stream)


def test_tokens_actually_flow():
    rt, plan, a, w = _schedule(vt=2)
    stats = rt.synchronize()
    assert stats.tokens_pushed > 0
    assert stats.modules["load"].insn_count > 0
    assert stats.modules["store"].insn_count > 0
