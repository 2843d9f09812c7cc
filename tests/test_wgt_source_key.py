"""Weight operands found by their DRAM source.

A WGT LOAD on ``PallasBackend`` records the DRAM range its rows come
from and that range's digest (``Dram.region_digest``, memoized by the
range and its pages' write epochs), and copies nothing.  A GEMM weight
operand is keyed by those digests, so a replayed call whose weights sit
in the device-resident cache never copies, gathers or hashes weight
bytes on the host; ``RunStats.wgt_load_bytes`` / ``wgt_skip_bytes`` say
how much of what the WGT LOADs name was never copied.  Every run here is
checked bit-exactly against ``SimulatorBackend``.  CPU, Pallas kernels
in interpret mode.
"""
import numpy as np
import pytest

from repro.core import backend, driver, hwspec, layout
from repro.core.backend import PallasBackend, SimulatorBackend, \
    wgt_cache_info
from repro.core.compiler import _WindowDram
from repro.core.driver import Device, Dram
from repro.core.isa import COMPUTE_Q, LOAD_Q, STORE_Q, MemId
from repro.core.program import Program
from repro.core.runtime import Runtime
from repro.core.scheduler import Epilogue, matmul_reference
from repro.core.serve import DevicePool

_EP = Epilogue(shift=6, relu=True)
SPECS = [hwspec.pynq(), hwspec.tpu_like(), hwspec.lowbit(4)]
SPEC_IDS = ["pynq", "tpu_like", "int4"]


@pytest.fixture
def cold(monkeypatch):
    """An empty weight-operand cache and digest memo for one test, with
    the memo's SHA-1 passes counted; the process's own are put back."""
    monkeypatch.setattr(backend, "_WGT_CACHE", {})
    monkeypatch.setattr(backend, "_WGT_CACHE_BYTES", 0)
    monkeypatch.setattr(backend, "_WGT_EVICTIONS", 0)
    monkeypatch.setattr(driver, "_DIGESTS", {})
    hashed = []
    real = driver.hashlib.sha1

    class _Counted:
        @staticmethod
        def sha1(data):
            hashed.append(len(memoryview(data).cast("B")))
            return real(data)
    monkeypatch.setattr(driver, "hashlib", _Counted)
    return hashed


def _sum(stats, name):
    return sum(getattr(s, name) for s in stats)


def _weights(spec, rng, shape):
    bits = spec.wgt_bits
    return rng.integers(-(1 << (bits - 1)), 1 << (bits - 1),
                        size=shape).astype(np.int8)


def _chain(spec, ws, m):
    """Chained (m, d) matmuls under constant weights `ws`."""
    p = Program(spec)
    t = p.input("x", (m, ws[0].shape[1]))
    for i, w in enumerate(ws):
        t = p.matmul(t, p.constant(f"w{i}", w), epilogue=_EP)
    p.output(t)
    return p.compile(use_cache=False)


def _both(c, device, x):
    """The program's output on a clone of `device` under each engine,
    and the Pallas run's stats; the two outputs must agree."""
    sim = c.run_on(device.clone(), backend=SimulatorBackend(),
                   inputs={"x": x})
    got = c.run_on(device, backend=PallasBackend(), inputs={"x": x})
    np.testing.assert_array_equal(got.outputs, sim.outputs)
    return got.outputs, got.stats


def _x(rng, m, k):
    return rng.integers(-128, 128, size=(m, k)).astype(np.int8)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_from_the_second_call_no_weight_byte_is_copied(cold, spec):
    rng = np.random.default_rng(spec.wgt_bits + spec.block_out)
    m = 2 if spec.wgt_packed else 24       # int4: decode-shaped, LUT-GEMM
    ws = [_weights(spec, rng, (160, 192)), _weights(spec, rng, (96, 160))]
    c = _chain(spec, ws, m)
    runs = []
    for _ in range(3):
        _, stats = _both(c, c.device, _x(rng, m, 192))
        runs.append(stats)
    first, second, third = runs
    assert _sum(first, "wgt_load_bytes") > 0
    assert _sum(first, "wgt_skip_bytes") < _sum(first, "wgt_load_bytes")
    for stats in (second, third):
        assert _sum(stats, "wgt_skip_bytes") \
            == _sum(stats, "wgt_load_bytes") == _sum(first, "wgt_load_bytes")
        assert _sum(stats, "wgt_hit_bytes") == _sum(stats, "wgt_bytes")
    # the constant ranges were hashed on the first call only
    assert len(cold) == len(driver._DIGESTS) > 0


def _weight_region(c, i=0):
    name, addr, nbytes = c.integrity_regions()[i]
    assert name == f"w{i}"
    return addr, nbytes


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
@pytest.mark.parametrize("how", ["write", "restage", "flip_flush"])
def test_a_rewritten_weight_range_gets_a_new_key(cold, spec, how):
    """Weights changed between calls (``Dram.write``, a restage from
    the pristine image, or a raw flip made known by ``flush_cache``)
    are digested again: the engine answers with the bytes in DRAM."""
    rng = np.random.default_rng(7 + spec.block_out)
    m = 2 if spec.wgt_packed else 16
    w = _weights(spec, rng, (64, 128))
    c = _chain(spec, [w], m)
    dev = c.device.clone()
    x = _x(rng, m, 128)
    base, _ = _both(c, dev, x)
    size = wgt_cache_info()["size"]
    addr, nbytes = _weight_region(c)
    if how == "write":
        dev.dram.write(addr, np.roll(dev.dram.read(addr, nbytes), 1))
    elif how == "restage":
        w2 = _weights(spec, rng, w.shape)
        other = _chain(spec, [w2], m).device
        c.restage_constants(dev, pristine=other)
    else:
        dev.dram.mem[addr:addr + 64] ^= 0x55
        dev.flush_cache(addr, 64)
    out, stats = _both(c, dev, x)
    assert not np.array_equal(out, base)
    assert wgt_cache_info()["size"] > size          # new keys, new entries
    assert _sum(stats, "wgt_skip_bytes") < _sum(stats, "wgt_load_bytes")
    # the pristine weights put back: the first key again, all hits
    c.restage_constants(dev)
    out, stats = _both(c, dev, x)
    np.testing.assert_array_equal(out, base)
    assert _sum(stats, "wgt_skip_bytes") == _sum(stats, "wgt_load_bytes")


def test_a_flip_under_an_integrity_pool_is_repaired_and_exact(cold):
    """DevicePool(integrity=True): a raw flip in a slot's weights, made
    known by ``flush_cache`` as the pool's chaos flip does, is restaged
    by the pre-gang audit, and the answer is the pristine weights'."""
    spec = hwspec.pynq()
    rng = np.random.default_rng(21)
    w = _weights(spec, rng, (64, 64))
    c = _chain(spec, [w], 16)
    addr, _ = _weight_region(c)
    with DevicePool(c, size=2, backend=PallasBackend(),
                    integrity=True) as pool:
        for k in range(3):
            dev = pool.slots[k % 2].device
            dev.dram.mem[addr + 5 + k] ^= 0x55
            dev.flush_cache(addr + 5 + k, 1)
            x = _x(rng, 16, 64)
            np.testing.assert_array_equal(pool.submit(x=x).wait(timeout=240),
                                          matmul_reference(x, w, _EP))
        assert sum(s.stats.integrity_restages for s in pool.slots) >= 1


def _hand_stream(spec, rt, w_addr, variant):
    """A hand-built stream over one accumulator element: INP and WGT
    loads, a reset and an accumulating GEMM, stores.  `variant`:

    * ``eager`` -- after the first tile is stored, a second WGT LOAD
      into the same row and a GEMM accumulating onto the stored values,
      which the engine runs eagerly over the WGT SRAM;
    * ``chunk_then_store`` -- after the GEMM is recorded, a STORE of a
      zero OUT row over the weights' DRAM range, before the tile is
      stored;
    * ``store_then_chunk`` -- that STORE between the WGT LOAD and the
      GEMM that reads the loaded row."""
    def reset(b):
        b.push(dst=0, src=0)

    def gemm(b):
        b.push(dst=0, src=0, wgt=0)

    def wgt(addr):
        return rt.to_elem_addr(addr, MemId.WGT)

    def out(addr):
        return rt.to_elem_addr(addr, MemId.OUT)

    s = spec
    a_addr = rt.copy_to_device(
        np.arange(s.batch * s.block_in).astype(np.int8) % 7 - 3,
        align=s.inp_elem_bytes)
    c_addr = rt.buffer_alloc(2 * s.out_elem_bytes, align=s.out_elem_bytes)
    rt.load_buffer_2d(MemId.INP, 0, rt.to_elem_addr(a_addr, MemId.INP),
                      1, 1, 1)
    rt.load_buffer_2d(MemId.WGT, 0, wgt(w_addr[0]), 1, 1, 1)
    rt.dep_push(LOAD_Q, COMPUTE_Q)
    rt.dep_pop(LOAD_Q, COMPUTE_Q)
    rt.push_gemm(rt.uop_kernel(reset, key="h.rst"), reset=True)
    if variant == "store_then_chunk":
        rt.dep_push(COMPUTE_Q, STORE_Q)
        rt.dep_pop(COMPUTE_Q, STORE_Q)
        rt.store_buffer_2d(1, out(w_addr[0]), 1, 1, 1)
        rt.dep_push(STORE_Q, COMPUTE_Q)
        rt.dep_pop(STORE_Q, COMPUTE_Q)
    rt.push_gemm(rt.uop_kernel(gemm, key="h.mm"))
    rt.dep_push(COMPUTE_Q, STORE_Q)
    rt.dep_pop(COMPUTE_Q, STORE_Q)
    if variant == "chunk_then_store":
        rt.store_buffer_2d(1, out(w_addr[0]), 1, 1, 1)
    rt.store_buffer_2d(0, out(c_addr), 1, 1, 1)
    if variant == "eager":
        rt.dep_push(COMPUTE_Q, LOAD_Q)        # row 0 read: reload it
        rt.dep_pop(COMPUTE_Q, LOAD_Q)
        rt.load_buffer_2d(MemId.WGT, 0, wgt(w_addr[1]), 1, 1, 1)
        rt.dep_push(LOAD_Q, COMPUTE_Q)
        rt.dep_push(STORE_Q, COMPUTE_Q)       # the tile is stored
        rt.dep_pop(LOAD_Q, COMPUTE_Q)
        rt.dep_pop(STORE_Q, COMPUTE_Q)
        rt.push_gemm(rt.uop_kernel(gemm, key="h.mm"))
        rt.dep_push(COMPUTE_Q, STORE_Q)
        rt.dep_pop(COMPUTE_Q, STORE_Q)
        rt.store_buffer_2d(0, out(c_addr) + 1, 1, 1, 1)
    return c_addr


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
@pytest.mark.parametrize("variant",
                         ["eager", "chunk_then_store", "store_then_chunk"])
def test_hand_built_streams_that_read_or_overwrite_deferred_weights(
        cold, spec, variant):
    """An eager GEMM over the WGT SRAM after deferred loads, and a
    STORE over a deferred load's DRAM range before or after the GEMM
    that reads it: both engines agree, on a cold and on a warm cache."""
    rng = np.random.default_rng(41)
    rt = Runtime(spec)
    w_addr = []
    for _ in range(2):
        w = _weights(spec, rng, (1, spec.block_out, spec.block_in))
        raw = layout.pack_wgt_elems(w, spec.wgt_bits) if spec.wgt_packed \
            else w
        w_addr.append(rt.copy_to_device(raw, align=spec.wgt_elem_bytes))
    c_addr = _hand_stream(spec, rt, w_addr, variant)
    stream = rt.finalize_stream()
    n = 2 if variant == "eager" else 1
    for _ in range(2):                  # cold, then warm weight cache
        devs = {}
        for eng in (SimulatorBackend(), PallasBackend()):
            dev = rt.device.clone()
            stats = eng.execute(spec, dev, stream)
            devs[eng.name] = dev.dram.read(c_addr, n * spec.out_elem_bytes)
        np.testing.assert_array_equal(devs["pallas"], devs["simulator"])
        assert devs["pallas"].any()
        assert stats.eager_gemm_insns == (variant == "eager")
        assert stats.wgt_load_bytes == n * spec.wgt_elem_bytes
        if variant != "chunk_then_store":
            # the eager GEMM, or the gather after the STORE, read the
            # SRAM: every deferred load was copied into it
            assert stats.wgt_skip_bytes == 0


@pytest.mark.parametrize("how", ["tight_cap", "emptied_before_launch"])
def test_an_operand_evicted_between_chunk_and_launch_is_built_exact(
        cold, monkeypatch, how):
    """The cache loses an operand after its chunk was recorded (a bound
    that holds one of three weight tiles, or emptied right before each
    lookup): the launch builds it from the DRAM range the key names."""
    spec = hwspec.pynq()
    rng = np.random.default_rng(8)
    ws = [_weights(spec, rng, (64, 64)) for _ in range(3)]
    c = _chain(spec, ws, 32)
    if how == "tight_cap":
        monkeypatch.setattr(backend, "_WGT_CACHE_CAP_BYTES", 128 * 128)
    else:
        real = backend._wgt_operand

        def emptied(*a, **kw):
            with backend._WGT_LOCK:
                backend._WGT_CACHE.clear()
                backend._WGT_CACHE_BYTES = 0
            return real(*a, **kw)
        monkeypatch.setattr(backend, "_wgt_operand", emptied)
    for _ in range(3):
        x = _x(rng, 32, 64)
        out, stats = _both(c, c.device, x)
        want = x
        for w in ws:
            want = matmul_reference(want, w, _EP)
        np.testing.assert_array_equal(out, want)
        assert _sum(stats, "wgt_hit_bytes") < _sum(stats, "wgt_bytes")
        assert _sum(stats, "wgt_skip_bytes") < _sum(stats, "wgt_load_bytes")


def test_a_gang_of_four_clones_shares_entries_and_digests(cold):
    """A DevicePool(size=4) gang under constant weights: one cache entry
    per weight operand, as a lone run makes, and each DRAM range hashed
    once across the pristine image and its four clones."""
    spec = hwspec.tpu_like()
    rng = np.random.default_rng(12)
    ws = [_weights(spec, rng, (256, 128)), _weights(spec, rng, (128, 256))]
    c = _chain(spec, ws, 8)
    _both(c, c.device.clone(), _x(rng, 8, 128))
    alone = wgt_cache_info()["size"]
    hashed = len(cold)
    assert alone > 0 and hashed == len(driver._DIGESTS) > 0
    with DevicePool(c, size=4, backend=PallasBackend()) as pool:
        for _ in range(2):
            feeds = [{"x": _x(rng, 8, 128)} for _ in range(4)]
            futs = pool.submit_batch(0, feeds)
            for f, fd in zip(futs, feeds):
                want = fd["x"]
                for w in ws:
                    want = matmul_reference(want, w, _EP)
                np.testing.assert_array_equal(f.wait(timeout=240), want)
            assert [s.gang_size for s in futs[0].stats] \
                == [4] * len(futs[0].stats)
            assert _sum(futs[0].stats, "wgt_skip_bytes") \
                == _sum(futs[0].stats, "wgt_load_bytes") > 0
    assert wgt_cache_info()["size"] == alone
    assert len(cold) == hashed


def test_dram_epochs_and_the_digest_memo(cold):
    import hashlib
    d = Dram(1 << 16)
    d.alloc(12000)                  # a trimmed clone keeps [0, 12032)
    assert d.epochs.shape == (16,) and not d.epochs.any()
    zero = d.region_digest(100, 5000)
    assert zero == hashlib.sha1(bytes(5000)).digest() and len(cold) == 1
    assert d.region_digest(100, 5000) is zero and len(cold) == 1
    d.write(9000, np.ones(10, np.uint8))       # page 2, outside the range
    assert list(np.flatnonzero(d.epochs)) == [2]
    assert d.region_digest(100, 5000) is zero and len(cold) == 1
    d.write(5000, np.full(4, 7, np.uint8))     # page 1, inside
    moved = d.region_digest(100, 5000)
    assert moved != zero and len(cold) == 2
    assert moved == hashlib.sha1(d.mem[100:5100]).digest()

    c = d.clone()                              # the same bytes and epochs
    t = d.clone(trim=True)
    assert c.region_digest(100, 5000) == moved and len(cold) == 2
    assert t.epochs.size == -(-t.size // 4096)
    assert t.region_digest(100, 5000) == moved and len(cold) == 2
    c.write(120, np.full(2, 9, np.uint8))      # the clone diverges
    assert c.region_digest(100, 5000) != moved
    assert d.region_digest(100, 5000) == moved and len(cold) == 3
    d.copy_from(c)                             # adopts its epochs too
    assert d.region_digest(100, 5000) == c.region_digest(100, 5000)
    assert len(cold) == 3

    # raw bytes are seen once the range is flushed
    dev = Device(1 << 16)
    dev.dram.write(0, np.arange(64, dtype=np.uint8))
    before = dev.dram.region_digest(0, 64)
    dev.dram.mem[10] ^= 0xFF
    dev.flush_cache(10, 1)
    assert dev.cache_flushes == 1
    assert dev.dram.region_digest(0, 64) \
        == hashlib.sha1(dev.dram.mem[:64]).digest() != before
    with pytest.raises(IndexError):
        dev.dram.region_digest((1 << 16) - 8, 16)

    # a window onto part of an address space keeps its epochs by offset
    win = _WindowDram(1 << 20, (1 << 20) + 8192, 64)
    win.write((1 << 20) + 4100, np.ones(4, np.uint8))
    assert list(np.flatnonzero(win.epochs)) == [1]
    assert win.region_digest((1 << 20) + 4096, 16) \
        == hashlib.sha1(win.read((1 << 20) + 4096, 16)).digest()
