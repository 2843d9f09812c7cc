"""The engine's per-stream interpretation plan.

The first run of a decoded stream records what ``PallasBackend`` derives
from the stream and the uop SRAM (decoded uops, index structure, tile
bookkeeping, the token check); later runs replay it and do only the data
work.  Every run here is checked bit-exactly, against the simulator
through ``CrossBackendChecker`` or against the numpy reference, and
``RunStats.plan_hit`` says which runs replayed.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import hwspec
from repro.core.backend import (CrossBackendChecker, PallasBackend,
                                decode_cache_info, set_decode_cache_cap)
from repro.core.conv import ConvShape, conv2d_reference, schedule_conv2d
from repro.core.isa import GemmInsn, IsaLayout, LoadStoreInsn, MemId, Opcode
from repro.core.microop import UOp, UopLayout
from repro.core.program import Program
from repro.core.runtime import Runtime
from repro.core.scheduler import (Epilogue, matmul_reference,
                                  read_matmul_result, schedule_matmul)
from repro.core.serve import DevicePool

_EP = Epilogue(shift=6, relu=True)
_CONV = ConvShape(n=1, h=8, w=8, ic=32, oc=32, kh=3, kw=3, stride=1, pad=1)
# RunStats fields a replay may read differently from a fresh analysis:
# the clocks, the plan hit, and what the process-wide weight-operand
# cache already held (and so which weight bytes were never copied)
_TIMING = {"wall_time_s", "stage_s", "launch_s", "sync_s", "park_s",
           "queue_s", "plan_hit", "upload_bytes", "wgt_hit_bytes",
           "wgt_skip_bytes"}


def _empty() -> None:
    """Empty the decoded-stream cache: no stream, no plan."""
    cap = decode_cache_info()["cap"]
    set_decode_cache_cap(0)
    set_decode_cache_cap(cap)


@pytest.fixture
def empty_cache():
    cap = decode_cache_info()["cap"]
    _empty()
    yield
    set_decode_cache_cap(cap)


def _stream(kind: str):
    """(runtime, encoded stream, matmul plan or None) of one small
    program: a direct or im2col 3x3 conv, or a blocked matmul."""
    spec = hwspec.pynq()
    rng = np.random.default_rng(11)
    rt = Runtime(spec)
    plan = None
    if kind == "matmul":
        a = rng.integers(-128, 128, size=(48, 64), dtype=np.int8)
        w = rng.integers(-128, 128, size=(32, 64), dtype=np.int8)
        plan = schedule_matmul(rt, a, w, epilogue=_EP, virtual_threads=2)
    else:
        x = rng.integers(-64, 64, size=(1, _CONV.ic, _CONV.h, _CONV.w),
                         dtype=np.int8)
        w = rng.integers(-16, 16, size=(_CONV.oc, _CONV.ic, 3, 3),
                         dtype=np.int8)
        schedule_conv2d(rt, x, w, _CONV, epilogue=_EP, lowering=kind)
    return rt, rt.finalize_stream(), plan


def _counters(stats) -> dict:
    return {k: v for k, v in dataclasses.asdict(stats).items()
            if k not in _TIMING}


KINDS = ["direct", "im2col", "matmul"]


@pytest.mark.parametrize("kind", KINDS)
def test_first_run_records_and_later_runs_replay(empty_cache, kind):
    rt, stream, _ = _stream(kind)
    checker = CrossBackendChecker(("simulator", PallasBackend()))
    hits = []
    for _ in range(3):
        rep = checker.run(rt.spec, rt.device, stream)
        assert rep.matches, f"{rep.mismatched_bytes} bytes differ"
        st = rep.stats_for("pallas")
        assert st.eager_gemm_insns == 0 and st.coalesced_gemm_insns > 0
        hits.append(st.plan_hit)
    assert hits == [0, 1, 1]


@pytest.mark.parametrize("kind", KINDS)
def test_replay_counts_what_a_fresh_analysis_counts(empty_cache, kind):
    rt, stream, _ = _stream(kind)
    eng = PallasBackend()
    runs = []
    for fresh in (True, False, True):
        if fresh:
            _empty()
        runs.append(eng.execute(rt.spec, rt.device.clone(), stream))
    assert [r.plan_hit for r in runs] == [0, 1, 0]
    assert _counters(runs[1]) == _counters(runs[0]) == _counters(runs[2])
    assert runs[1].tile_batches > 0 and runs[1].tokens_pushed > 0


def _alter_first_accumulating_uop(spec, device, stream) -> None:
    """In DRAM, point the first uop of the first accumulating GEMM
    kernel at its neighbour's weight row; the stream's words stay."""
    lay = UopLayout(spec)
    uop_loads = []
    for insn in IsaLayout(spec).decode_stream(stream):
        if isinstance(insn, LoadStoreInsn) and insn.opcode == Opcode.LOAD \
                and insn.memory_type == MemId.UOP:
            uop_loads.append(insn)
        elif isinstance(insn, GemmInsn) and not insn.reset \
                and insn.uop_end - insn.uop_bgn >= 2:
            ld = next(ld for ld in reversed(uop_loads)
                      if ld.y_size == 1 and ld.sram_base <= insn.uop_bgn
                      < ld.sram_base + ld.x_size)
            addr = (ld.dram_base + insn.uop_bgn - ld.sram_base) \
                * spec.uop_elem_bytes
            w0, w1 = device.dram.read(addr, 2 * spec.uop_elem_bytes,
                                      dtype=np.uint32)
            u0, u1 = lay.decode(w0), lay.decode(w1)
            assert u0.wgt != u1.wgt
            device.dram.write(addr, np.array(
                [lay.encode(UOp(u0.dst, u0.src, u1.wgt))], np.uint32))
            return
    raise AssertionError("no accumulating GEMM of two or more uops")


def test_other_uop_bytes_fall_back_and_stay_exact(empty_cache):
    rt, stream, plan = _stream("matmul")
    checker = CrossBackendChecker(("simulator", PallasBackend()))
    first = checker.run(rt.spec, rt.device, stream)
    assert first.matches and first.stats_for("pallas").plan_hit == 0
    altered = rt.device.clone()
    _alter_first_accumulating_uop(rt.spec, altered, stream)
    rep = checker.run(rt.spec, altered, stream)
    assert rep.matches, f"{rep.mismatched_bytes} bytes differ"
    assert rep.stats_for("pallas").plan_hit == 0
    # the altered uop changed the answer: the replay could not have kept it
    assert not np.array_equal(
        read_matmul_result(rt, plan, device=rep.device_for("pallas")),
        read_matmul_result(rt, plan, device=first.device_for("pallas")))
    # the plan is kept as the first run recorded it
    again = checker.run(rt.spec, rt.device, stream)
    assert again.matches and again.stats_for("pallas").plan_hit == 1


def test_no_plan_is_kept_without_the_decode_cache(empty_cache):
    rt, stream, _ = _stream("direct")
    checker = CrossBackendChecker(("simulator", PallasBackend()))
    set_decode_cache_cap(0)
    for _ in range(3):
        rep = checker.run(rt.spec, rt.device, stream)
        assert rep.matches and rep.stats_for("pallas").plan_hit == 0
    assert decode_cache_info()["size"] == 0


def _conv_program(rng):
    w = rng.integers(-16, 16, size=(_CONV.oc, _CONV.ic, 3, 3),
                     dtype=np.int8)
    p = Program(hwspec.pynq())
    x = p.input("x", (1, _CONV.ic, _CONV.h, _CONV.w))
    p.output(p.conv2d(x, p.constant("w", w), _CONV, epilogue=_EP))

    def feed():
        return {"x": rng.integers(-64, 64, size=(1, _CONV.ic, _CONV.h,
                                                  _CONV.w), dtype=np.int8)}

    def ref(f):
        return conv2d_reference(f["x"], w, _CONV, epilogue=_EP)
    return p.compile(use_cache=False), feed, ref


def test_gang_replays_the_plan_of_a_width_one_run(empty_cache):
    c, feed, ref = _conv_program(np.random.default_rng(3))
    feeds = [feed() for _ in range(4)]
    with DevicePool(c, size=4, backend=PallasBackend()) as pool:
        f = pool.submit_batch(0, feeds[:1])[0]
        np.testing.assert_array_equal(f.wait(timeout=240), ref(feeds[0]))
        assert [(s.gang_size, s.plan_hit) for s in f.stats] \
            == [(1, 0)] * len(f.stats)
        futs = pool.submit_batch(0, feeds)
        outs = [f.wait(timeout=240) for f in futs]
    for f, out, fd in zip(futs, outs, feeds):
        np.testing.assert_array_equal(out, ref(fd))
        assert [(s.gang_size, s.plan_hit) for s in f.stats] \
            == [(4, 1)] * len(f.stats)


def _weights_in_program(rng, m=32, d=64):
    p = Program()
    x = p.input("x", (m, d))
    p.matmul(x, p.input("w", (d, d)), epilogue=_EP)
    return p.compile(use_cache=False)


def test_replay_groups_launches_by_each_calls_weight_bytes(empty_cache,
                                                          monkeypatch):
    """Weights are request inputs: a gang of four whose members share
    weight bytes in pairs row-concats each pair into one GEMM launch,
    where four distinct weights take one vmapped launch — on a replay
    as on a fresh analysis."""
    import repro.kernels.vta_gemm.kernel as vta_gemm_kernel

    gemm_calls = []
    real = vta_gemm_kernel.vta_gemm_pallas

    def counted(*a, **kw):
        gemm_calls.append(a[0].shape)
        return real(*a, **kw)
    monkeypatch.setattr(vta_gemm_kernel, "vta_gemm_pallas", counted)
    rng = np.random.default_rng(9)
    c = _weights_in_program(rng)

    def w():
        return rng.integers(-128, 128, size=(64, 64), dtype=np.int8)
    a, b = w(), w()
    xs = [rng.integers(-128, 128, size=(32, 64), dtype=np.int8)
          for _ in range(4)]
    paired = [{"x": x, "w": wt} for x, wt in zip(xs, (a, a, b, b))]
    distinct = [{"x": x, "w": w()} for x in xs]

    def run(pool, feeds):
        del gemm_calls[:]
        futs = pool.submit_batch(0, feeds)
        for f, fd in zip(futs, feeds):
            np.testing.assert_array_equal(
                f.wait(timeout=240), matmul_reference(fd["x"], fd["w"], _EP))
        return futs[0].stats, len(gemm_calls)

    with DevicePool(c, size=4, backend=PallasBackend()) as pool:
        first, _ = run(pool, distinct)
        hit_paired, n_paired = run(pool, paired)
        hit_distinct, n_distinct = run(pool, distinct)
        _empty()
        fresh_paired, n_fresh = run(pool, paired)
    assert [s.plan_hit for s in first] == [0] * len(first)
    for segs in (hit_paired, hit_distinct):
        assert [(s.gang_size, s.plan_hit) for s in segs] \
            == [(4, 1)] * len(segs)
    assert [s.plan_hit for s in fresh_paired] == [0] * len(fresh_paired)
    assert [_counters(s) for s in hit_paired] \
        == [_counters(s) for s in fresh_paired]
    assert (n_paired, n_distinct, n_fresh) == (2, 1, 2)


@pytest.mark.parametrize("seed", range(6))
def test_random_graphs_replay_exactly_on_new_inputs(empty_cache, seed):
    """The differential fuzzer's random graphs (matmul and conv chains,
    mixed lowerings, host splits): a second call on new inputs replays
    every segment's plan and still matches the numpy reference."""
    import test_fuzz_backends as fuzz

    rng = np.random.default_rng(fuzz.FUZZ_SEED + 7000 + seed)
    p, feeds = fuzz.build_random_program(rng)
    compiled = p.compile(use_cache=False)
    eng = PallasBackend()
    for call in range(2):
        if call:
            feeds = {k: rng.integers(-64, 64, size=v.shape, dtype=v.dtype)
                     for k, v in feeds.items()}
        refs = fuzz.evaluate_reference(p, feeds)
        res = compiled.run_on(compiled.device.clone(trim=True),
                              backend=eng, inputs=feeds)
        outs = res.outputs if isinstance(res.outputs, dict) else \
            {p.nodes[compiled.output_ids[0]].name: res.outputs}
        for i in compiled.output_ids:
            np.testing.assert_array_equal(outs[p.nodes[i].name], refs[i])
    assert res.stats and all(s.plan_hit == 1 for s in res.stats)


def test_threads_record_and_replay_one_stream_exactly(empty_cache):
    """Eight threads run one stream at once from an empty cache, with a
    short switch interval: whichever run publishes the plan, every run
    leaves the simulator's DRAM image, and a later run replays."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    from repro.core.backend import SimulatorBackend

    rt, stream, _ = _stream("matmul")
    want = rt.device.clone()
    SimulatorBackend().execute(rt.spec, want, stream)
    eng = PallasBackend()

    def run(_):
        dev = rt.device.clone()
        st = eng.execute(rt.spec, dev, stream)
        return np.array_equal(dev.dram.mem, want.dram.mem), st.plan_hit

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(max_workers=8) as ex:
            results = list(ex.map(run, range(16), timeout=240))
    finally:
        sys.setswitchinterval(old)
    assert all(exact for exact, _ in results)
    assert run(None) == (True, 1)
