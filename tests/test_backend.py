"""Cross-backend equivalence: one task-ISA stream, two engines (§3).

The same encoded instruction stream `schedule_matmul` lowers must execute
bit-exactly on the numpy simulator AND the Pallas engine, and both must
match the pure-numpy oracle — the paper's simulator-vs-hardware
differential flow with the simulator as oracle for the fast path.
"""
import zlib

import numpy as np
import pytest

from repro.core import hwspec
from repro.core.backend import (CrossBackendChecker, PallasBackend,
                                SimulatorBackend, resolve_backend)
from repro.core.isa import AluInsn, AluOp
from repro.core.runtime import Runtime
from repro.core.scheduler import (Epilogue, matmul_reference,
                                  read_matmul_result, read_vector_result,
                                  schedule_matmul, schedule_vector_binop)
from repro.core.simulator import RunStats


def _bias_epilogue(N, spec, rng, **kw):
    bias_n = rng.integers(-1000, 1000, size=N, dtype=np.int32)
    nb = N // spec.block_out
    blocked = np.repeat(bias_n.reshape(nb, 1, spec.block_out),
                        spec.batch, axis=1)
    return Epilogue(bias_blocked=blocked, **kw)


def _make_epilogue(name, N, spec, rng):
    if name == "default":
        return None                                     # plain clip
    if name == "shift_clip":
        return Epilogue(shift=5)                        # requant fast path
    if name == "relu":
        return Epilogue(relu=True)                      # folds into clip_lo
    if name == "relu_noclip":
        return Epilogue(relu=True, clip_lo=None, clip_hi=None)
    if name == "relu_cliplo":
        return Epilogue(relu=True, clip_lo=-4, shift=2)  # fold w/ shift
    if name == "wrap":
        # no clip: the int8 truncating out-store wraps around
        return Epilogue(clip_lo=None, clip_hi=None)
    if name == "bias_shift_relu":
        return _bias_epilogue(N, spec, rng, shift=6, relu=True)
    raise ValueError(name)


# >= 8 shape/epilogue configurations, including the int8 truncating-store
# edge cases ("wrap") and both virtual-threading modes
CONFIGS = [
    (16, 16, 16, "default", 1),
    (16, 16, 16, "default", 2),
    (32, 16, 48, "shift_clip", 2),
    (48, 32, 32, "relu", 1),
    (64, 64, 64, "shift_clip", 2),
    (32, 32, 64, "bias_shift_relu", 2),
    (16, 32, 32, "wrap", 1),
    (64, 32, 128, "wrap", 2),
    (48, 16, 80, "relu_cliplo", 2),
    (32, 48, 32, "relu_noclip", 2),
]


def _run_backend(backend, a, w, ep, vt, spec):
    rt = Runtime(spec)
    plan = schedule_matmul(rt, a, w, epilogue=ep, virtual_threads=vt)
    stats = rt.synchronize(backend=backend)
    return read_matmul_result(rt, plan), stats


@pytest.mark.parametrize("M,N,K,ep_name,vt", CONFIGS)
def test_cross_backend_matmul_exact(M, N, K, ep_name, vt):
    spec = hwspec.pynq()
    # crc32, not hash(): str hashing is salted per-process and would make
    # a failing config unreproducible across runs
    rng = np.random.default_rng(zlib.crc32(repr((M, N, K, ep_name, vt))
                                           .encode()))
    a = rng.integers(-128, 128, size=(M, K), dtype=np.int8)
    w = rng.integers(-128, 128, size=(N, K), dtype=np.int8)
    ep = _make_epilogue(ep_name, N, spec, rng)
    sim_out, sim_stats = _run_backend("simulator", a, w, ep, vt, spec)
    pal_out, pal_stats = _run_backend("pallas", a, w, ep, vt, spec)
    ref = matmul_reference(a, w, epilogue=ep, spec=spec)
    np.testing.assert_array_equal(sim_out, ref)
    np.testing.assert_array_equal(pal_out, ref)
    assert sim_stats.backend == "simulator"
    assert pal_stats.backend == "pallas"
    # both engines executed the same stream: identical MAC counts
    assert sim_stats.gemm_macs == pal_stats.gemm_macs > 0


def test_checker_diffs_dram_images():
    spec = hwspec.pynq()
    rng = np.random.default_rng(7)
    a = rng.integers(-128, 128, size=(64, 96), dtype=np.int8)
    w = rng.integers(-128, 128, size=(32, 96), dtype=np.int8)
    rt = Runtime(spec)
    plan = schedule_matmul(rt, a, w, epilogue=Epilogue(shift=3),
                           virtual_threads=2)
    report = CrossBackendChecker().check_runtime(rt)
    assert report.matches, f"{report.mismatched_bytes} bytes differ"
    assert {r.backend for r in report.runs} == {"simulator", "pallas"}
    # adopted image stays readable through the usual helper
    got = read_matmul_result(rt, plan)
    np.testing.assert_array_equal(
        got, matmul_reference(a, w, epilogue=Epilogue(shift=3), spec=spec))
    # per-clone reads agree too
    for run in report.runs:
        np.testing.assert_array_equal(
            read_matmul_result(rt, plan, device=run.device), got)


def test_vector_binop_cross_backend_and_balanced():
    """Listing-1 path: exact on both engines, and the fixed dependence
    protocol leaves every token FIFO drained even across chunks."""
    spec = hwspec.pynq().replace(acc_buff_bytes=4 * 1024,
                                 out_buff_bytes=4 * 1024)
    rng = np.random.default_rng(3)
    n = 600                       # > acc_depth//2 elements => multiple chunks
    a = rng.integers(-64, 64, size=n, dtype=np.int32)
    b = rng.integers(-63, 63, size=n, dtype=np.int32)
    want = (a + b).astype(np.int8)
    for backend in ("simulator", "pallas"):
        rt = Runtime(spec)
        c_addr, shape = schedule_vector_binop(rt, a, b, op=AluOp.ADD)
        assert shape[0] > spec.acc_depth // 2   # really multi-chunk
        rt.validate_stream(require_net_zero=True)  # no dangling s2c token
        rt.synchronize(backend=backend)
        got = read_vector_result(rt, c_addr, shape, n)
        np.testing.assert_array_equal(got, want, err_msg=backend)


def test_vector_binop_composes_after_matmul():
    """The net-zero token check is scoped to the binop's own stream suffix:
    scheduling it after a matmul (whose protocol legitimately leaves
    trailing WAR tokens) must not raise, and the composed stream still
    validates and executes on both engines."""
    spec = hwspec.pynq()
    rng = np.random.default_rng(9)
    a = rng.integers(-128, 128, size=(32, 32), dtype=np.int8)
    w = rng.integers(-128, 128, size=(32, 32), dtype=np.int8)
    va = rng.integers(-64, 64, size=100, dtype=np.int32)
    vb = rng.integers(-63, 63, size=100, dtype=np.int32)
    for backend in ("simulator", "pallas"):
        rt = Runtime(spec)
        schedule_matmul(rt, a, w, virtual_threads=2)
        c_addr, shape = schedule_vector_binop(rt, va, vb, op=AluOp.ADD)
        rt.synchronize(backend=backend)   # no ValueError, runs to FINISH
        got = read_vector_result(rt, c_addr, shape, 100)
        np.testing.assert_array_equal(got, (va + vb).astype(np.int8),
                                      err_msg=backend)


def test_relu_folds_into_clip_pass():
    """relu=True with a clip emits no extra ALU pass (MAX 0 + MAX -128
    was a no-op pair) and still matches the oracle."""
    spec = hwspec.pynq()
    rng = np.random.default_rng(5)
    a = rng.integers(-128, 128, size=(32, 32), dtype=np.int8)
    w = rng.integers(-128, 128, size=(32, 32), dtype=np.int8)

    def n_alu_insns(ep):
        rt = Runtime(spec)
        schedule_matmul(rt, a, w, epilogue=ep, virtual_threads=1)
        return sum(isinstance(i, AluInsn) for i in rt.stream)

    assert Epilogue(relu=True).n_alu_passes == Epilogue().n_alu_passes == 2
    assert n_alu_insns(Epilogue(relu=True)) == n_alu_insns(Epilogue())
    # relu without a clip still needs its own pass
    assert Epilogue(relu=True, clip_lo=None).n_alu_passes == 1
    # folded lower bound: relu dominates a negative clip_lo
    assert Epilogue(relu=True, clip_lo=-4).folded_clip_lo == 0
    assert Epilogue(relu=True, clip_lo=5).folded_clip_lo == 5


def test_out_load_over_pending_tile_matches_simulator():
    """Hand-built stream: a LOAD into OUT SRAM lands *between* a GEMM and
    its STORE.  The loaded bytes must win over the GEMM's write-through
    mirror on both engines (forces the Pallas engine to resolve the lazy
    tile before the OUT load executes)."""
    spec = hwspec.pynq()
    rng = np.random.default_rng(13)
    a = rng.integers(-128, 128, size=(spec.batch, spec.block_in),
                     dtype=np.int8)
    w = rng.integers(-128, 128, size=(spec.block_out, spec.block_in),
                     dtype=np.int8)
    injected = rng.integers(-128, 128,
                            size=(1, spec.batch, spec.block_out),
                            dtype=np.int8)
    from repro.core.isa import COMPUTE_Q, LOAD_Q, MemId, STORE_Q
    outs = {}
    for backend in ("simulator", "pallas"):
        rt = Runtime(spec)
        a_addr = rt.copy_to_device(a, align=spec.inp_elem_bytes)
        w_addr = rt.copy_to_device(w, align=spec.wgt_elem_bytes)
        o_addr = rt.copy_to_device(injected, align=spec.out_elem_bytes)
        c_addr = rt.buffer_alloc(spec.out_elem_bytes,
                                 align=spec.out_elem_bytes)
        rt.load_buffer_2d(MemId.INP, 0, rt.to_elem_addr(a_addr, MemId.INP),
                          1, 1, 1)
        rt.load_buffer_2d(MemId.WGT, 0, rt.to_elem_addr(w_addr, MemId.WGT),
                          1, 1, 1)
        rt.dep_push(LOAD_Q, COMPUTE_Q)
        rt.dep_pop(LOAD_Q, COMPUTE_Q)

        def reset(b):
            b.push(dst=0, src=0)

        def gemm(b):
            b.push(dst=0, src=0, wgt=0)

        rt.push_gemm(rt.uop_kernel(reset, key="t.rst"), reset=True)
        rt.push_gemm(rt.uop_kernel(gemm, key="t.mm"))
        # overwrite the out mirror AFTER the gemm, BEFORE the store
        rt.load_buffer_2d(MemId.OUT, 0, rt.to_elem_addr(o_addr, MemId.OUT),
                          1, 1, 1)
        rt.dep_push(COMPUTE_Q, STORE_Q)
        rt.dep_pop(COMPUTE_Q, STORE_Q)
        rt.store_buffer_2d(0, rt.to_elem_addr(c_addr, MemId.OUT), 1, 1, 1)
        rt.synchronize(backend=backend)
        outs[backend] = rt.copy_from_device(
            c_addr, spec.out_elem_bytes, np.int8,
            (spec.batch, spec.block_out))
    np.testing.assert_array_equal(outs["simulator"], injected[0])
    np.testing.assert_array_equal(outs["pallas"], injected[0])


def test_backend_resolution():
    assert isinstance(resolve_backend(None), SimulatorBackend)
    assert isinstance(resolve_backend("simulator"), SimulatorBackend)
    assert isinstance(resolve_backend("pallas"), PallasBackend)
    inst = PallasBackend()
    assert resolve_backend(inst) is inst
    with pytest.raises(ValueError):
        resolve_backend("fpga")


def test_pallas_backend_reports_wall_time_and_bytes():
    spec = hwspec.pynq()
    rng = np.random.default_rng(11)
    a = rng.integers(-128, 128, size=(32, 32), dtype=np.int8)
    w = rng.integers(-128, 128, size=(32, 32), dtype=np.int8)
    stats = {}
    for backend in ("simulator", "pallas"):
        rt = Runtime(spec)
        schedule_matmul(rt, a, w, virtual_threads=2)
        stats[backend] = rt.synchronize(backend=backend)
    for s in stats.values():
        assert isinstance(s, RunStats)
        assert s.wall_time_s > 0
    # identical stream => identical DMA traffic on both engines
    assert stats["simulator"].dram_rd_bytes == stats["pallas"].dram_rd_bytes
    assert stats["simulator"].dram_wr_bytes == stats["pallas"].dram_wr_bytes


def test_decode_cache_is_a_bounded_lru_with_counted_evictions():
    """The process-wide decoded-stream cache holds at most
    set_decode_cache_cap entries, evicts least-recently-HIT first, and
    every eviction is counted — unbounded growth under a many-program
    serving mix is a regression, silent eviction is too."""
    from repro.core.backend import decode_cache_info, set_decode_cache_cap

    class _FakeIsa:
        insn_words = 2

        def decode_stream(self, raw):
            return [("decoded", raw.tobytes())]

    spec = hwspec.pynq()
    eng = PallasBackend()
    isa = _FakeIsa()

    def raw(i):
        return np.full((1, 2), 7_000_000 + i, dtype=np.uint64)

    base = decode_cache_info()
    old_cap = base["cap"]
    try:
        set_decode_cache_cap(3)
        assert decode_cache_info()["size"] <= 3
        start = decode_cache_info()["evictions"]
        # fill: 3 distinct streams fit (anything older gets trimmed)
        for i in range(3):
            _, ev = eng._decode_cached(spec, isa, raw(i))
        filled = decode_cache_info()
        assert filled["size"] == 3 and filled["cap"] == 3
        # hit stream 0 to refresh its recency, then insert a 4th:
        # stream 1 (now the LRU) must be the one evicted
        hit, ev = eng._decode_cached(spec, isa, raw(0))
        assert ev == 0 and hit.insns == [("decoded", raw(0).tobytes())]
        _, ev = eng._decode_cached(spec, isa, raw(3))
        assert ev == 1, "insert over cap must evict exactly one entry"
        _, ev = eng._decode_cached(spec, isa, raw(0))
        assert ev == 0, "recently-hit stream must have survived"
        _, ev = eng._decode_cached(spec, isa, raw(1))
        assert ev == 1, "LRU stream must have been evicted"
        assert decode_cache_info()["evictions"] >= start + 2
        # shrinking the cap trims immediately and counts the trims
        trimmed = set_decode_cache_cap(1)
        assert trimmed == 2 and decode_cache_info()["size"] == 1
        # cap 0 disables retention: nothing is kept, nothing grows
        set_decode_cache_cap(0)
        _, _ = eng._decode_cached(spec, isa, raw(4))
        assert decode_cache_info()["size"] == 0
        with pytest.raises(ValueError):
            set_decode_cache_cap(-1)
    finally:
        set_decode_cache_cap(old_cap)


def test_decode_evictions_flow_into_runstats_merge():
    """RunStats carries per-call decode_evictions and merged() sums it —
    the serving loop's visibility into cache churn."""
    a = RunStats(decode_evictions=2)
    b = RunStats(decode_evictions=1)
    assert RunStats.merged([a, b]).decode_evictions == 3
