"""Concurrency differential suite for the async serving subsystem.

Every pooled/async execution is byte-diffed against serial single-device
execution of the SAME compiled artifact on the same inputs — the
simulator pool is the concurrency oracle, the pallas pool is the ganged
fast path, and both must agree with the synchronous ``CompiledProgram``
call bit for bit: interleaved submits, out-of-order waits, pool sizes
1/2/4, both engines, both fence modes, plus a >=64-submit stress run
under a hard deadline.  The per-slot invariants the PR converts from
single-device invariants are asserted directly: zero per-call DRAM
growth per slot (trimmed clones make allocation an ERROR), and
request-local RunStats that two concurrent pooled calls can never
cross-contaminate.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import hwspec
from repro.core.conv import ConvShape, conv2d_reference
from repro.core.program import Program
from repro.core.scheduler import Epilogue, matmul_reference
from repro.core.serve import (BatchServer, DevicePool, PoolClosed,
                              serve_batch)

BACKENDS = ("simulator", "pallas")


def _mlp(rng, layers=2, m=32, d=64, constants=True):
    """Small serving-shaped program (constant weights) + a request
    generator + the numpy reference."""
    ws = [rng.integers(-128, 128, size=(d, d), dtype=np.int8)
          for _ in range(layers)]
    ep = Epilogue(shift=6, relu=True)
    p = Program()
    t = p.input("x", (m, d))
    for i, w in enumerate(ws):
        wref = p.constant(f"w{i}", w) if constants \
            else p.input(f"w{i}", w.shape)
        t = p.matmul(t, wref, epilogue=ep)

    def make_request():
        x = rng.integers(-128, 128, size=(m, d), dtype=np.int8)
        feed = {"x": x}
        if not constants:
            feed.update({f"w{i}": w for i, w in enumerate(ws)})
        return feed

    def reference(feed):
        r = feed["x"]
        for w in ws:
            r = matmul_reference(r, w, ep)
        return r

    return p, make_request, reference


def _hetero_conv(rng):
    """conv -> cpu_only conv -> conv: exercises host steps between
    accelerator segments inside the pool scheduler."""
    s = ConvShape(n=1, h=8, w=8, ic=16, oc=16, kh=3, kw=3, stride=1, pad=1)
    ks = [rng.integers(-8, 8, size=(16, 16, 3, 3), dtype=np.int8)
          for _ in range(3)]
    ep = Epilogue(shift=5, relu=True)
    p = Program()
    t = p.input("x", (1, 16, 8, 8))
    for i, k in enumerate(ks):
        t = p.conv2d(t, p.constant(f"k{i}", k), s, epilogue=ep,
                     cpu_only=(i == 1))

    def make_request():
        return {"x": rng.integers(-64, 64, size=(1, 16, 8, 8),
                                  dtype=np.int8)}

    def reference(feed):
        r = feed["x"]
        for k in ks:
            r = conv2d_reference(r, k, s, epilogue=ep)
        return r

    return p, make_request, reference


# ----------------------------------------------------------------------
# the differential grid: pool sizes x engines x fence modes
# ----------------------------------------------------------------------
@pytest.mark.parametrize("fence_mode", ("buffer", "barrier"))
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("size", (1, 2, 4))
def test_pool_matches_serial(size, backend, fence_mode):
    rng = np.random.default_rng(100 * size + len(backend) + len(fence_mode))
    p, make_request, reference = _mlp(rng)
    c = p.compile(use_cache=False, fence_mode=fence_mode)
    feeds = [make_request() for _ in range(3 * size)]
    # serial single-device execution of the same inputs — the oracle
    serial = [c(backend=backend, **f) for f in feeds]
    with DevicePool(c, size=size, backend=backend) as pool:
        futs = [pool.submit(**f) for f in feeds]        # interleaved
        # out-of-order waits: last submitted, first waited
        for f, feed, want in reversed(list(zip(futs, feeds, serial))):
            got = f.wait(timeout=120)
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(got, reference(feed))


def test_pool_dram_image_matches_serial_byte_for_byte():
    """Stronger than output equality: after serving, a slot's trimmed
    DRAM image equals the serial device's allocated image byte for byte
    (same addresses, same data — the clone IS the device)."""
    rng = np.random.default_rng(7)
    p, make_request, _ = _mlp(rng)
    c = p.compile(use_cache=False)
    feed = make_request()
    with DevicePool(c, size=2, backend="pallas") as pool:
        futs = [pool.submit(**feed) for _ in range(2)]   # same feed, both
        [f.wait(timeout=120) for f in futs]
        c(backend="pallas", **feed)                      # serial, after
        used = min(s.device.dram.size for s in pool.slots)
        for slot in pool.slots:
            assert np.array_equal(slot.device.dram.mem[:used],
                                  c.device.dram.mem[:used]), \
                f"slot {slot.id} DRAM image diverged from serial device"


def test_pool_heterogeneous_cpu_steps_overlap():
    """Host segments (cpu_only conv) run through the pool's host worker
    and stay byte-exact vs the serial heterogeneous execution."""
    rng = np.random.default_rng(11)
    p, make_request, reference = _hetero_conv(rng)
    c = p.compile(use_cache=False)
    feeds = [make_request() for _ in range(6)]
    serial = [c(backend="pallas", **f) for f in feeds]
    with DevicePool(c, size=2, backend="pallas") as pool:
        futs = [pool.submit(**f) for f in feeds]
        for f, feed, want in zip(futs, feeds, serial):
            got = f.wait(timeout=240)
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(got, reference(feed))
        stats = pool.slot_stats()
        assert sum(s.cpu_steps for s in stats) == len(feeds)
        assert sum(s.accel_steps for s in stats) == 2 * len(feeds)


def test_pool_gangs_and_stays_exact_with_per_request_weights():
    """Non-constant weights break the shared-W row-concat optimization;
    the gang must fall back to vmap lanes and stay bit-exact."""
    rng = np.random.default_rng(13)
    p, make_request, reference = _mlp(rng, constants=False)
    c = p.compile(use_cache=False)
    feeds = [make_request() for _ in range(8)]
    with DevicePool(c, size=4, backend="pallas") as pool:
        futs = [pool.submit(**f) for f in feeds]
        for f, feed in zip(futs, feeds):
            np.testing.assert_array_equal(f.wait(timeout=240),
                                          reference(feed))
        assert any(s.ganged_steps for s in pool.slot_stats())


# ----------------------------------------------------------------------
# stress: >= 64 concurrent submits under a deadline
# ----------------------------------------------------------------------
@pytest.mark.timeout(240)
def test_stress_64_concurrent_submits_under_deadline():
    rng = np.random.default_rng(17)
    p, make_request, reference = _mlp(rng)
    c = p.compile(use_cache=False)
    feeds = [make_request() for _ in range(64)]
    with DevicePool(c, size=4, backend="pallas",
                    policy="least_loaded") as pool:
        pool.submit(**feeds[0]).wait(timeout=120)        # warm jit caches
        t0 = time.perf_counter()
        # submits race in from 4 producer threads (interleaved arrival)
        futs = [None] * len(feeds)

        def producer(lo):
            for i in range(lo, len(feeds), 4):
                futs[i] = pool.submit(**feeds[i])
        threads = [threading.Thread(target=producer, args=(lo,))
                   for lo in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in rng.permutation(len(feeds)):            # random wait order
            np.testing.assert_array_equal(futs[i].wait(timeout=120),
                                          reference(feeds[i]))
        elapsed = time.perf_counter() - t0
    assert elapsed < 120, f"64 pooled requests took {elapsed:.1f}s"


# ----------------------------------------------------------------------
# per-slot invariants
# ----------------------------------------------------------------------
def test_zero_per_call_dram_growth_per_slot_and_alloc_is_an_error():
    rng = np.random.default_rng(19)
    p, make_request, _ = _mlp(rng)
    c = p.compile(use_cache=False)
    with DevicePool(c, size=2, backend="pallas") as pool:
        [pool.submit(**make_request()) for _ in range(4)]
        pool.drain(timeout=120)
        marks = [s.device.dram._next for s in pool.slots]
        [pool.submit(**make_request()) for _ in range(8)]
        pool.drain(timeout=120)
        assert [s.device.dram._next for s in pool.slots] == marks, \
            "pooled serving grew a slot's DRAM image"
        # trimmed slot clones turn any allocation into a loud error
        with pytest.raises(MemoryError):
            pool.slots[0].device.dram.alloc(64)


def test_runstats_are_request_local_no_cross_contamination():
    """Satellite bugfix lock-in: two pooled calls must never share a
    RunStats object or leak each other's counters.  Requests with
    different staging sizes run concurrently; each future's stats must
    carry exactly its own staging bytes and segment counts."""
    rng = np.random.default_rng(23)
    p_small, req_small, _ = _mlp(rng, layers=2)
    c = p_small.compile(use_cache=False)
    with DevicePool(c, size=2, backend="pallas") as pool:
        futs = [pool.submit(**req_small()) for _ in range(10)]
        [f.wait(timeout=120) for f in futs]
        seen = set()
        for f in futs:
            assert len(f.stats) == 1                 # one accel segment
            (st,) = f.stats
            assert id(st) not in seen, "RunStats object shared!"
            seen.add(id(st))
            assert st.staging_bytes_per_call == f.staging_bytes > 0
            assert st.n_buffer_fences == 1 and st.n_join_barriers == 0
            assert st.backend == "pallas"
    # the synchronous path serializes fully under the artifact's lock
    # (one shared device image): hammering __call__ from 6 threads must
    # produce each thread's OWN result, not an interleaved one
    p2, req2, ref2 = _mlp(np.random.default_rng(24))
    c2 = p2.compile(use_cache=False)
    before = c2.calls
    feeds = [req2() for _ in range(6)]
    results = [None] * len(feeds)
    errs = []

    def hammer(i):
        try:
            results[i] = c2(backend="simulator", **feeds[i])
        except Exception as e:                       # pragma: no cover
            errs.append(e)
    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(len(feeds))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert c2.calls == before + len(feeds)
    for got, feed in zip(results, feeds):
        np.testing.assert_array_equal(got, ref2(feed))


def test_pool_stats_count_gangs_and_slots_serve_evenly_round_robin():
    rng = np.random.default_rng(29)
    p, make_request, _ = _mlp(rng)
    c = p.compile(use_cache=False)
    with DevicePool(c, size=4, backend="pallas",
                    policy="round_robin") as pool:
        futs = [pool.submit(**make_request()) for _ in range(16)]
        [f.wait(timeout=120) for f in futs]
        stats = pool.slot_stats()
        assert [s.calls for s in stats] == [4, 4, 4, 4]
        assert any(s.ganged_steps for s in stats)
        gang_sizes = {st.gang_size for f in futs for st in f.stats}
        assert max(gang_sizes) > 1, "no request ever ran ganged"
        d = pool.describe()
        assert "pool[4 slots" in d and "slot3:" in d


def test_least_loaded_policy_balances_uneven_queues():
    rng = np.random.default_rng(31)
    p, make_request, reference = _mlp(rng)
    c = p.compile(use_cache=False)
    with DevicePool(c, size=2, backend="simulator",
                    policy="least_loaded") as pool:
        feeds = [make_request() for _ in range(8)]
        futs = [pool.submit(**f) for f in feeds]
        for f, feed in zip(futs, feeds):
            np.testing.assert_array_equal(f.wait(timeout=240),
                                          reference(feed))
        calls = sorted(s.calls for s in pool.slot_stats())
        assert sum(calls) == 8 and calls[0] >= 2, calls


# ----------------------------------------------------------------------
# API edges
# ----------------------------------------------------------------------
def test_batch_server_gathers_in_submission_order():
    rng = np.random.default_rng(37)
    p, make_request, reference = _mlp(rng)
    c = p.compile(use_cache=False)
    feeds = [make_request() for _ in range(9)]
    outs = serve_batch(c, feeds, size=3, backend="pallas")
    assert len(outs) == len(feeds)
    for o, feed in zip(outs, feeds):
        np.testing.assert_array_equal(o, reference(feed))


def test_closed_pool_rejects_submits_but_finishes_inflight():
    rng = np.random.default_rng(41)
    p, make_request, reference = _mlp(rng)
    c = p.compile(use_cache=False)
    pool = DevicePool(c, size=2, backend="simulator")
    feed = make_request()
    fut = pool.submit(**feed)
    pool.close()
    np.testing.assert_array_equal(fut.wait(timeout=120), reference(feed))
    with pytest.raises(PoolClosed):
        pool.submit(**feed)


def test_bad_inputs_fail_fast_in_submit_and_bad_pool_args_raise():
    rng = np.random.default_rng(43)
    p, make_request, _ = _mlp(rng)
    c = p.compile(use_cache=False)
    with pytest.raises(ValueError, match="policy"):
        DevicePool(c, size=2, policy="wat")
    with pytest.raises(ValueError, match="size"):
        DevicePool(c, size=0)
    with DevicePool(c, size=1, backend="simulator") as pool:
        with pytest.raises(ValueError, match="mismatch"):
            pool.submit(nope=np.zeros((32, 64), np.int8))
        # a request failing inside the scheduler surfaces on ITS future
        bad = dict(make_request())
        bad["x"] = np.zeros((1, 1), np.int8)         # wrong shape
        fut = pool.submit(**bad)
        with pytest.raises(ValueError, match="expected shape"):
            fut.wait(timeout=120)
        ok = make_request()
        np.testing.assert_array_equal(
            pool.submit(**ok).wait(timeout=120),
            c(backend="simulator", **ok))

