"""Program spans and serving-plane wait counters.

The engine opens ``vta.engine.gang`` around each segment, with one
``stage``/``launch``/``sync`` span per kernel launch nested inside it on
the same thread; the pool and the scheduler name their waits; each
request's RunStats carry its seconds parked in the Scheduler and queued
in the pool.  Spans are checked in a real ``jax.profiler`` session on the
CPU (Pallas kernels in interpret mode).
"""
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.core import spans
from repro.core.backend import PallasBackend
from repro.core.program import Program
from repro.core.sched import SchedConfig, Scheduler
from repro.core.scheduler import Epilogue, matmul_reference
from repro.core.serve import DevicePool
from repro.core.simulator import RunStats

_EP = Epilogue(shift=6, relu=True)


def _mlp(rng, m=32, d=64, constants=True):
    """Two matmuls with a relu epilogue (a tensor_alu chain after each
    GEMM) and the numpy reference.  A gang's tiles under constant weights
    row-concat into one GEMM; under weights drawn per request they take
    vmap lanes."""
    ws = [rng.integers(-128, 128, size=(d, d), dtype=np.int8)
          for _ in range(2)]
    p = Program()
    t = p.input("x", (m, d))
    for i, w in enumerate(ws):
        wref = p.constant(f"w{i}", w) if constants \
            else p.input(f"w{i}", w.shape)
        t = p.matmul(t, wref, epilogue=_EP)

    def make():
        feed = {"x": rng.integers(-128, 128, size=(m, d), dtype=np.int8)}
        if not constants:
            feed.update({f"w{i}": rng.integers(-128, 128, size=(d, d),
                                               dtype=np.int8)
                         for i in range(2)})
        return feed

    def ref(feed):
        r = feed["x"]
        for i, w in enumerate(ws):
            r = matmul_reference(r, feed.get(f"w{i}", w), _EP)
        return r

    return p.compile(use_cache=False), make, ref


def _vta_events(xspace: bytes):
    """(name, start, end, line, args) of every ``vta.*`` host event, the
    line numbered across the host planes."""
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    out, n = [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("vta."):
                    out.append((ev.name, ev.start_ns, ev.end_ns, n,
                                dict(ev.stats)))
            n += 1
    return out


def _session():
    import jax
    from jax._src.lib import _profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return _profiler.ProfilerSession(opts)


@pytest.mark.parametrize("constants", [True, False],
                         ids=["row-concat", "vmap"])
def test_gang_of_two_nests_one_phase_triple_per_launch(monkeypatch,
                                                       constants):
    import repro.kernels.tensor_alu as tensor_alu_pkg

    calls = {"tensor_alu": 0, "_alu_chain": 0, "_alu_eager_region": 0}

    def counting(owner, name):
        real = getattr(owner, name)

        def counted(*a, **kw):
            calls[name] += 1
            return real(*a, **kw)
        monkeypatch.setattr(owner, name, counted)
    counting(tensor_alu_pkg, "tensor_alu")
    counting(PallasBackend, "_alu_chain")
    counting(PallasBackend, "_alu_eager_region")

    c, make, ref = _mlp(np.random.default_rng(5), constants=constants)
    feeds = [make(), make()]
    with DevicePool(c, size=2, backend=PallasBackend(interpret=True)) \
            as pool:
        pool.submit_batch(0, feeds)[0].wait(timeout=240)   # warm
        calls.update(dict.fromkeys(calls, 0))
        session = _session()
        futs = pool.submit_batch(0, feeds)
        outs = [f.wait(timeout=240) for f in futs]
        events = _vta_events(session.stop())
    for out, feed in zip(outs, feeds):
        np.testing.assert_array_equal(out, ref(feed))
    segs = futs[0].stats
    assert [s.gang_size for s in segs] == [2] * len(segs) and segs
    gangs = [e for e in events if e[0] == "vta.engine.gang"]
    assert len(gangs) == len(segs)
    for g in gangs:
        assert g[4] == {"width": 2, "prog": 0, "seq0": futs[0].seq}
    phases = [e for e in events if e[0] in ("vta.engine.stage",
                                            "vta.engine.launch",
                                            "vta.engine.sync")]
    for name, s, e, line, _ in phases:
        assert any(g[3] == line and g[1] <= s and e <= g[2]
                   for g in gangs), (name, "outside every gang span")
    n = {k: sum(1 for e in phases if e[0] == k)
         for k in ("vta.engine.stage", "vta.engine.launch",
                   "vta.engine.sync")}
    gemm = sum(s.tile_batches for s in segs)
    assert gemm > 0 and calls["tensor_alu"] > 0
    assert n["vta.engine.launch"] == gemm + calls["tensor_alu"]
    # one read-back per GEMM launch and per ALU chain or region
    assert n["vta.engine.sync"] == \
        gemm + calls["_alu_chain"] + calls["_alu_eager_region"]
    assert n["vta.engine.stage"] >= n["vta.engine.sync"]
    staged = [e for e in events if e[0] == "vta.pool.stage_inputs"]
    assert sorted(e[4]["seq"] for e in staged) == sorted(f.seq for f in futs)


def test_phase_seconds_lie_inside_the_engine_window():
    c, make, _ = _mlp(np.random.default_rng(6))
    with DevicePool(c, size=1, backend=PallasBackend(interpret=True)) \
            as pool:
        f = pool.submit(**make())
        f.wait(timeout=240)
    for st in f.stats:
        phases = st.stage_s + st.launch_s + st.sync_s
        assert st.stage_s > 0 and st.launch_s > 0 and st.sync_s > 0
        assert phases <= st.wall_time_s
    # submitted to the pool directly: nothing parked, but it queued
    assert f.stats[0].park_s == 0.0 and f.stats[0].queue_s > 0


def test_closed_loop_through_the_scheduler_records_park_and_queue():
    c, make, ref = _mlp(np.random.default_rng(7), m=16, d=32)
    feeds = [[make() for _ in range(3)] for _ in range(2)]
    results = {}
    with DevicePool(c, size=2, backend=PallasBackend(interpret=True)) \
            as pool:
        sched = Scheduler(pool, SchedConfig(window_us=2000.0,
                                            gang_width=2))

        def client(k):
            got = []
            for feed in feeds[k]:
                f = sched.submit(**feed)
                got.append((f.wait(timeout=240), f.pool_future))
            results[k] = got
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        sched.close()
    for k in range(2):
        for (out, pf), feed in zip(results[k], feeds[k]):
            np.testing.assert_array_equal(out, ref(feed))
            first = pf.stats[0]
            assert first.park_s > 0 and first.queue_s > 0
            assert all(s.park_s == 0.0 for s in pf.stats[1:])


def test_merged_sums_phase_and_wait_seconds():
    a = RunStats(stage_s=1.0, launch_s=2.0, sync_s=3.0, park_s=0.5,
                 queue_s=0.25)
    b = RunStats(stage_s=0.5, launch_s=0.25, sync_s=1.0, park_s=0.5,
                 queue_s=1.0)
    m = RunStats.merged([a, b])
    assert (m.stage_s, m.launch_s, m.sync_s, m.park_s, m.queue_s) == \
        (1.5, 2.25, 4.0, 1.0, 1.25)


def test_tagged_ids_are_per_block_and_nest():
    assert spans.tags() == {}
    with spans.tagged(prog=1, seq0=4):
        with spans.tagged(prog=2, seq0=9):
            assert spans.tags() == {"prog": 2, "seq0": 9}
        assert spans.tags() == {"prog": 1, "seq0": 4}
    assert spans.tags() == {}


def test_spans_need_no_jax_until_jax_is_imported():
    code = ("import sys\n"
            "import repro.core\n"
            "from repro.core import spans\n"
            "with spans.span('engine.gang', width=2):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'repro.core imported jax'\n"
            "import jax.profiler\n"
            "assert type(spans.span('x')).__name__ == 'TraceAnnotation'\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr


@pytest.mark.parametrize("name", ["vta.pool.idle", "vta.sched.hold"])
def test_serving_plane_names_its_waits(name):
    c, make, _ = _mlp(np.random.default_rng(8), m=16, d=32)
    with DevicePool(c, size=2, backend=PallasBackend(interpret=True)) \
            as pool:
        # gang width 1 on two slots never aligns: every release holds
        # until the previous one has retired
        sched = Scheduler(pool, SchedConfig(window_us=200.0,
                                            gang_width=1))
        sched.submit(x=make()["x"]).wait(timeout=240)      # warm
        session = _session()
        futs = [sched.submit(**make()) for _ in range(3)]
        for f in futs:
            f.wait(timeout=240)
        events = _vta_events(session.stop())
        sched.close()
    waits = [e for e in events if e[0] == name]
    assert waits and all(e[2] >= e[1] for e in waits)
    if name == "vta.sched.hold":
        assert {e[4]["width"] for e in waits} == {1}
        assert max(e[2] - e[1] for e in waits) > 0
