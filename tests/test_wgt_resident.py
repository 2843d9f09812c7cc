"""The engine's device-resident weight operands.

``PallasBackend`` pads, transposes and uploads a GEMM weight tile once
and hands the device copy to every later launch that needs the same
bytes.  The cache is keyed by the tile's exact content, kept in LRU
order and bounded in bytes.  Every run here is checked bit-exactly
against the simulator or the numpy reference, and ``RunStats``
``wgt_bytes`` / ``wgt_hit_bytes`` / ``upload_bytes`` say what the cache
served.  CPU, Pallas kernels in interpret mode.
"""
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core import backend, hwspec
from repro.core.backend import PallasBackend, SimulatorBackend, \
    wgt_cache_info
from repro.core.program import Program
from repro.core.scheduler import Epilogue, matmul_reference
from repro.core.serve import DevicePool

_EP = Epilogue(shift=6, relu=True)


@pytest.fixture
def cold(monkeypatch):
    """An empty weight-operand cache for one test; the process's own is
    put back after it."""
    monkeypatch.setattr(backend, "_WGT_CACHE", {})
    monkeypatch.setattr(backend, "_WGT_CACHE_BYTES", 0)
    monkeypatch.setattr(backend, "_WGT_EVICTIONS", 0)
    return monkeypatch


def _sum(stats, field):
    return sum(getattr(s, field) for s in stats)


def _share(stats) -> float:
    return _sum(stats, "wgt_hit_bytes") / _sum(stats, "wgt_bytes")


def _matmul(spec, w, m, *, weight_input=False):
    """One matmul with a requant + relu epilogue: weights a constant of
    the program, or (``weight_input``) an input of each request."""
    p = Program(spec)
    x = p.input("x", (m, w.shape[1]))
    wref = p.input("w", w.shape) if weight_input else p.constant("w", w)
    p.output(p.matmul(x, wref, epilogue=_EP))
    return p.compile(use_cache=False)


def _chain(rng, n, m, d):
    """`n` chained (m, d) x (d, d) matmuls under constant weights, one
    weight-cache entry each, and the numpy reference."""
    ws = [rng.integers(-128, 128, size=(d, d), dtype=np.int8)
          for _ in range(n)]
    p = Program(hwspec.pynq())
    t = p.input("x", (m, d))
    for i, w in enumerate(ws):
        t = p.matmul(t, p.constant(f"w{i}", w), epilogue=_EP)
    p.output(t)

    def ref(x):
        for w in ws:
            x = matmul_reference(x, w, _EP)
        return x
    return p.compile(use_cache=False), ref


def _consistent():
    """The cache's byte count is the sum of its entries, within bound."""
    info = wgt_cache_info()
    held = sum(v.nbytes for v in backend._WGT_CACHE.values())
    assert info["bytes"] == held <= info["cap_bytes"]
    return info


@pytest.mark.parametrize("spec,bits,m", [
    (hwspec.pynq(), 8, 32),
    (hwspec.tpu_like(), 8, 24),
    (hwspec.lowbit(4), 4, 2),           # decode-shaped: the LUT-GEMM
], ids=["pynq", "tpu_like", "int4-lut"])
def test_repeated_calls_are_exact_and_hit_from_the_second(cold, spec, bits,
                                                           m):
    rng = np.random.default_rng(bits + m)
    lo, hi = -(1 << (bits - 1)), 1 << (bits - 1)
    w = rng.integers(lo, hi, size=(160, 192)).astype(np.int8)
    c = _matmul(spec, w, m)
    runs = []
    for _ in range(3):
        x = rng.integers(-128, 128, size=(m, 192)).astype(np.int8)
        want = c(backend=SimulatorBackend(), x=x)
        np.testing.assert_array_equal(c(backend=PallasBackend(), x=x), want)
        runs.append(c.last_stats)
    first, second, third = runs
    assert _sum(first, "wgt_bytes") > 0
    assert _share(first) < 1.0
    assert _share(second) == _share(third) == 1.0
    # a hit uploads the activations and not the weights
    assert _sum(first, "upload_bytes") - _sum(second, "upload_bytes") \
        == _sum(first, "wgt_bytes") - _sum(first, "wgt_hit_bytes")
    assert _sum(second, "upload_bytes") > 0
    if bits < 8:
        assert _sum(second, "lut_launches") >= 1
    _consistent()


def test_equal_shapes_with_other_bytes_never_share_an_entry(cold):
    """Two programs whose weights differ in one byte, run interleaved,
    and a gang whose members bring those two weights: each answer is
    its own weights', and the differing tile is a miss of its own."""
    rng = np.random.default_rng(3)
    wa = rng.integers(-128, 128, size=(64, 64), dtype=np.int8)
    wb = wa.copy()
    wb[5, 7] ^= 1
    ca, cb = _matmul(hwspec.pynq(), wa, 32), _matmul(hwspec.pynq(), wb, 32)
    sizes = []
    for _ in range(2):
        for c, w in ((ca, wa), (cb, wb)):
            x = rng.integers(-128, 128, size=(32, 64), dtype=np.int8)
            np.testing.assert_array_equal(c(backend=PallasBackend(), x=x),
                                          matmul_reference(x, w, _EP))
            sizes.append(wgt_cache_info()["size"])
    # the tile holding the changed byte is an entry of its own
    assert sizes[0] < sizes[1] == sizes[2] == sizes[3]

    cw = _matmul(hwspec.pynq(), wa, 128, weight_input=True)
    xs = [rng.integers(-128, 128, size=(128, 64), dtype=np.int8)
          for _ in range(2)]
    feeds = [{"x": xs[0], "w": wa}, {"x": xs[1], "w": wb}]
    with DevicePool(cw, size=2, backend=PallasBackend()) as pool:
        for _ in range(2):
            futs = pool.submit_batch(0, feeds)
            for f, fd in zip(futs, feeds):
                np.testing.assert_array_equal(
                    f.wait(timeout=240),
                    matmul_reference(fd["x"], fd["w"], _EP))
    assert [s.gang_size for s in futs[0].stats] == [2]
    assert _share(futs[0].stats) == 1.0
    _consistent()


@pytest.mark.parametrize("m,vmapped", [(32, False), (128, True)],
                         ids=["row-concat", "vmap"])
def test_both_gang_paths_hit(cold, monkeypatch, m, vmapped):
    """A gang of two under one constant weight: 32-row members share
    one row-concatenated launch, 128-row members take vmap lanes over
    one broadcast device copy.  Both hit once warm."""
    import repro.kernels.vta_gemm.kernel as vta_gemm_kernel

    traced = []
    real = vta_gemm_kernel.vta_gemm_pallas

    def spy(*a, **kw):
        traced.append(isinstance(a[0], jax.core.Tracer))
        return real(*a, **kw)
    monkeypatch.setattr(vta_gemm_kernel, "vta_gemm_pallas", spy)
    rng = np.random.default_rng(4)
    w = rng.integers(-128, 128, size=(64, 64), dtype=np.int8)
    c = _matmul(hwspec.pynq(), w, m)
    shares = []
    with DevicePool(c, size=2, backend=PallasBackend()) as pool:
        for _ in range(3):
            feeds = [{"x": rng.integers(-128, 128, size=(m, 64),
                                        dtype=np.int8)} for _ in range(2)]
            futs = pool.submit_batch(0, feeds)
            for f, fd in zip(futs, feeds):
                np.testing.assert_array_equal(
                    f.wait(timeout=240), matmul_reference(fd["x"], w, _EP))
            assert [s.gang_size for s in futs[0].stats] == [2]
            shares.append(_share(futs[0].stats))
    assert traced and set(traced) == {vmapped}
    assert shares[0] < 1.0 and shares[1:] == [1.0, 1.0]
    _consistent()


@pytest.mark.parametrize("cap", ["half", "zero"])
def test_a_small_byte_bound_evicts_counts_and_stays_exact(cold, cap):
    rng = np.random.default_rng(5)
    c, ref = _chain(rng, 3, 32, 64)

    def call():
        x = rng.integers(-128, 128, size=(32, 64), dtype=np.int8)
        np.testing.assert_array_equal(c(backend=PallasBackend(), x=x),
                                      ref(x))
    call()
    full = _consistent()
    assert full["size"] == 3 and full["evictions"] == 0
    # the same calls from an empty cache under a bound that holds one
    # of the three entries, or none
    cold.setattr(backend, "_WGT_CACHE", {})
    cold.setattr(backend, "_WGT_CACHE_BYTES", 0)
    cold.setattr(backend, "_WGT_CACHE_CAP_BYTES",
                 full["bytes"] // 2 if cap == "half" else 0)
    call()
    call()
    info = _consistent()
    assert info["evictions"] > 0 if cap == "half" else info["size"] == 0
    assert info["bytes"] <= full["bytes"] // 2
    assert _share(c.last_stats) < 1.0     # the cycle outruns the bound


@pytest.mark.parametrize("cap", ["default", "tight"])
def test_four_threads_on_a_pool_of_four_stay_exact(cold, cap):
    """Four client threads, each alternating a request to a
    DevicePool(size=4) with a direct run on its own device clone, so
    engines on several threads fill, hit and (under a tight bound)
    evict the cache at once."""
    rng = np.random.default_rng(6)
    c, ref = _chain(rng, 3, 16, 128)
    if cap == "tight":                  # two of the three 16 KiB entries
        cold.setattr(backend, "_WGT_CACHE_CAP_BYTES", 2 * 128 * 128)
    feeds = [[rng.integers(-128, 128, size=(16, 128), dtype=np.int8)
              for _ in range(3)] for _ in range(4)]
    bad = []
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with DevicePool(c, size=4, backend=PallasBackend()) as pool:
            def client(k):
                eng = PallasBackend()
                for x in feeds[k]:
                    want = ref(x)
                    got = pool.submit(x=x).wait(timeout=240)
                    res = c.run_on(c.device.clone(), backend=eng,
                                   inputs={"x": x})
                    if not (np.array_equal(got, want)
                            and np.array_equal(res.outputs, want)):
                        bad.append(k)
            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert bad == []
    info = _consistent()
    assert info["evictions"] > 0 if cap == "tight" else info["size"] > 0


def test_merged_sums_the_upload_and_weight_counters():
    from repro.core.simulator import RunStats
    a = RunStats(upload_bytes=10, wgt_bytes=8, wgt_hit_bytes=0)
    b = RunStats(upload_bytes=3, wgt_bytes=8, wgt_hit_bytes=8)
    m = RunStats.merged([a, b])
    assert (m.upload_bytes, m.wgt_bytes, m.wgt_hit_bytes) == (13, 16, 8)
