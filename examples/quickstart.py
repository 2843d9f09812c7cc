"""Quickstart: the full VTA stack in ~100 lines.

1. Quantize a float matmul workload to int8 (the paper's PTQ step).
2. Lower it with the scheduler (tensorization + virtual threading).
3. JIT the VTA instruction stream with the runtime.
4. Execute on the behavioral simulator; cross-check against numpy.
5. Time it with the cycle-level pipeline model, with and without
   virtual threading — the paper's latency-hiding result in miniature.
6. Route the *same* encoded stream through the second engine
   (PallasBackend) and differentially check it against the simulator —
   the paper's heterogeneous-execution story (§3).
7. Compile a whole multi-op graph (two chained matmuls + requant) into
   ONE task-ISA stream with the program-level JIT, then rerun it on new
   data without re-scheduling — the paper's module-level JIT-cost
   amortization.
8. Run a *general* kh*kw>1 convolution (a ResNet C2-style 3x3) through
   the same stack: the direct-conv schedule's per-output-row GEMMs are
   coalesced into batched Pallas calls, so the layer takes ZERO eager
   fallback iterations — verified by the fast-path counters — and the
   lowering decision (direct vs im2col vs via_matmul) is inspectable on
   the compiled program.
9. Serve the compiled program: compile ONCE, call N times.  Dependent
   layers are joined by buffer-granular fences (only the consumer's
   loads of the produced buffer wait on the producer's final store —
   inspect the fence edges in describe()), weights are graph constants
   staged into DRAM at compile time, intermediates live in a recycled
   arena, and the encoded stream is pre-staged — so every repeat call
   performs ZERO DRAM allocation (asserted) and stages only the fresh
   activations.
10. Pool-serve it asynchronously: clone the staged device onto a
   DevicePool, submit() a burst of requests, wait() the futures out of
   order.  Requests parked at the same segment execute as one lockstep
   gang — every Pallas launch carries all gang members' tiles — and
   each slot keeps the zero-allocation serving contract independently
   (trimmed clones make a stray alloc an ERROR).  Per-slot stats show
   the sharding.
11. Decode a transformer through the same stack: a 2-block quantized
   decoder whose KV caches live in *persistent* DRAM buffers — the
   third liveness class next to constants and arena intermediates.
   One compiled program is one decode STEP; four pool sessions hold
   four independent dialogues, the scheduler swaps each session's KV
   bytes at stable addresses, and every step is bit-exact against the
   eager numpy reference with zero per-step DRAM allocation.
12. Continuous-batch a 2-program mix: co-stage two different graphs
   into ONE resident DRAM image (compile_multi — disjoint ranges, every
   baked address valid), serve both through one pool behind an
   admission window (core.sched): requests park up to window_us, same-
   program arrivals release together as full-width gangs, programs
   never mix in a gang, and backpressure is typed — then dump the whole
   control plane with describe().
13. Shrink the weights below a byte: the same linear layer at bits=4
   stores its weight constant int4-PACKED in the DRAM image (half the
   staged bytes — describe() shows it), both engines decode the packed
   stream bit-exactly, decode-shaped calls auto-route to the bit-plane
   LUT-GEMM kernel, and the int4 output tracks the int8 path's dequant
   reference within the coarser quantization step.
14. Kill a serving slot mid-dialogue and watch the pool heal itself:
   the slot respawns from the pristine staged image (max_respawns), the
   decode session transparently restores its KV bytes from the last
   checkpoint (checkpoint_every=1 — restored_from_step is visible,
   never silent), the dialogue continues bit-exact against the same
   eager reference, and describe() carries the death/respawn/restore
   accounting.
15. Autotune the deployment (paper §4): a seeded design-space search
   prices candidate template geometries + schedule knobs on the
   calibrated cycle oracle, measures and byte-validates only the top
   predictions, and writes the winner into the tuning cache — so
   recompiling the same op under the tuned spec is all cache HITS
   (describe() shows the hit/miss counters and the chosen conv
   lowering, which is itself picked by replayed cycles, not a rule).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import compile_cache
from repro.core import Program, hwspec, quantize as q
from repro.core.backend import CrossBackendChecker, assert_fast_path
from repro.core.conv import ConvShape, conv2d_reference
from repro.core.runtime import Runtime
from repro.core.scheduler import (Epilogue, matmul_reference,
                                  read_matmul_result, schedule_matmul)
from repro.core.simulator import TimingModel


def main() -> None:
    compile_cache.enable()
    spec = hwspec.pynq()
    print(f"VTA template: {spec.batch}x{spec.block_in}x{spec.block_out} "
          f"GEMM core @ {spec.freq_mhz:.0f} MHz "
          f"= {spec.peak_gops:.1f} GOPS peak")

    # --- 1. float workload -> int8 (post-training quantization, §5) ---
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 512)).astype(np.float32)
    w = rng.normal(size=(256, 512)).astype(np.float32) / np.sqrt(512)
    qx, qw = q.calibrate(x), q.calibrate(w)
    qy = q.calibrate(x @ w.T)
    shift = q.choose_requant_shift(qx.scale, qw.scale, qy.scale)
    xq, wq = q.quantize(x, qx), q.quantize(w, qw)

    # --- 2-4. schedule, JIT, simulate, verify ---
    rt = Runtime(spec)
    plan = schedule_matmul(rt, xq, wq, epilogue=Epilogue(shift=shift),
                           virtual_threads=2)
    stats = rt.synchronize()
    got = read_matmul_result(rt, plan)
    want = matmul_reference(xq, wq, epilogue=Epilogue(shift=shift))
    assert np.array_equal(got, want), "simulator diverged from oracle!"
    print(f"exact int8 result ok; {stats.gemm_macs / 1e6:.1f} M MACs, "
          f"{stats.dram_rd_bytes / 1e3:.0f} kB read")

    # --- 5. latency hiding (Fig. 4 / Fig. 15) ---
    for vt in (1, 2):
        rt = Runtime(spec)
        schedule_matmul(rt, xq, wq, virtual_threads=vt)
        s = rt.synchronize(timing=TimingModel(spec))
        print(f"virtual_threads={vt}: {s.total_cycles:,} cycles, "
              f"compute utilization {s.compute_utilization:.1%}, "
              f"{s.gops(spec.freq_mhz):.1f} GOPS")

    # --- 6. heterogeneous execution: one stream, two engines (§3) ---
    rt = Runtime(spec)
    plan = schedule_matmul(rt, xq, wq, epilogue=Epilogue(shift=shift),
                           virtual_threads=2)
    report = CrossBackendChecker().check_runtime(rt)
    got = read_matmul_result(rt, plan)
    assert report.matches, "engines diverged!"
    assert np.array_equal(got, want), "adopted image diverged from oracle!"
    print("cross-backend check ok: "
          + ", ".join(f"{r.backend} {r.stats.wall_time_s * 1e3:.0f} ms"
                      for r in report.runs)
          + "  (pallas time includes one-time jit compile; the chip's "
            "steady state is bench/run.py's to measure)")

    # --- 7. program-level JIT: a whole graph in ONE stream ---
    w2 = rng.normal(size=(128, 256)).astype(np.float32) / np.sqrt(256)
    w2q = q.quantize(w2, q.calibrate(w2))
    ep1 = Epilogue(shift=shift, relu=True)
    ep2 = Epilogue(shift=6)
    prog = Program(spec)
    h = prog.matmul(prog.input("x", xq.shape), prog.input("w1", wq.shape),
                    epilogue=ep1)
    prog.matmul(h, prog.input("w2", w2q.shape), epilogue=ep2)
    compiled = prog.compile()
    print(f"program: {compiled.describe()}")
    want2 = matmul_reference(matmul_reference(xq, wq, ep1), w2q, ep2)
    for backend in ("simulator", "pallas"):
        out = compiled(backend=backend, x=xq, w1=wq, w2=w2q)
        assert np.array_equal(out, want2), f"{backend} diverged!"
    # rerun with fresh activations: rebinds DRAM, no re-scheduling
    from repro.core import program as program_mod
    builds = program_mod.STREAM_BUILDS
    x2 = q.quantize(rng.normal(size=xq.shape).astype(np.float32), qx)
    out = compiled(x=x2, w1=wq, w2=w2q)
    assert program_mod.STREAM_BUILDS == builds
    assert np.array_equal(
        out, matmul_reference(matmul_reference(x2, wq, ep1), w2q, ep2))
    print("program JIT ok: 2-op graph, one stream, both engines exact; "
          "second call hit the stream cache")

    # --- 8. general conv2d on the Pallas fast path (kh*kw > 1) ---
    shape = ConvShape(n=1, h=14, w=14, ic=32, oc=32, kh=3, kw=3,
                      stride=1, pad=1)                  # C2-style 3x3
    xq3 = rng.integers(-64, 64, size=(1, 32, 14, 14), dtype=np.int8)
    k3 = rng.integers(-16, 16, size=(32, 32, 3, 3), dtype=np.int8)
    ep3 = Epilogue(shift=5, relu=True)
    cprog = Program(spec)
    cprog.conv2d(cprog.input("x", xq3.shape), cprog.input("k", k3.shape),
                 shape, epilogue=ep3, name="c2")
    cc = cprog.compile()
    print(f"conv program: {cc.describe()}")            # shows c2:direct
    want3 = conv2d_reference(xq3, k3, shape, epilogue=ep3)
    for backend in ("simulator", "pallas"):
        out3 = cc(backend=backend, x=xq3, k=k3)
        assert np.array_equal(out3, want3), f"{backend} conv diverged!"
    assert_fast_path(cc.last_stats)                    # zero eager GEMMs
    eager = sum(s.eager_gemm_insns for s in cc.last_stats)
    coal = sum(s.coalesced_gemm_insns for s in cc.last_stats)
    print(f"3x3 conv ok on the fast path: {coal} GEMM insns coalesced "
          f"into batched Pallas calls, {eager} eager fallbacks")

    # --- 9. serve it: compile once, call N times, zero per-call DRAM ---
    import time
    sprog = Program(spec)
    t = sprog.conv2d(sprog.input("x", xq3.shape),
                     sprog.constant("k1", k3),      # weight staged ONCE
                     shape, epilogue=ep3, name="s1")
    sprog.conv2d(t, sprog.constant("k2",
                                   rng.integers(-16, 16, size=(32, 32, 1, 1),
                                                dtype=np.int8)),
                 ConvShape(n=1, h=14, w=14, ic=32, oc=32, kh=1, kw=1,
                           stride=1, pad=0),
                 epilogue=ep3, name="s2")
    served = sprog.compile()
    print(f"serving program: {served.describe()}")    # fence edge + arena
    served(backend="pallas", x=xq3)                   # warm jit caches
    n_calls = 16
    dram_mark = served.device.dram._next
    t0 = time.perf_counter()
    for _ in range(n_calls):
        out9 = served(backend="pallas", x=xq3)
    dt = time.perf_counter() - t0
    assert served.device.dram._next == dram_mark, \
        "serving loop grew the DRAM image!"
    stats9 = served.last_stats[0]
    print(f"served {n_calls} calls at {n_calls / dt:.1f} calls/s: "
          f"{stats9.n_buffer_fences} fence / {stats9.n_join_barriers} "
          f"barriers per stream, {served.last_staging_bytes} B staged per "
          f"call (activations only), DRAM image constant, "
          f"{sum(s.tiles_resolved for s in served.last_stats)} tiles in "
          f"{sum(s.tile_batches for s in served.last_stats)} batched "
          f"launches")

    # --- 10. pool-serve it: async submit/wait over cloned devices ---
    from repro.core.serve import DevicePool
    with DevicePool(served, size=2, backend="pallas",
                    policy="least_loaded") as pool:
        xs = [rng.integers(-64, 64, size=xq3.shape, dtype=np.int8)
              for _ in range(8)]
        futs = [pool.submit(x=xi) for xi in xs]        # async burst
        marks = [s.device.dram._next for s in pool.slots]
        for fut, xi in reversed(list(zip(futs, xs))):  # wait out of order
            got = fut.wait(timeout=600)
            want = served(x=xi)                        # serial oracle
            assert np.array_equal(got, want), "pooled result diverged!"
        assert [s.device.dram._next for s in pool.slots] == marks, \
            "a pool slot grew its DRAM image!"
        gangs = sum(s.ganged_steps for s in pool.slot_stats())
        print(f"pool-served {len(xs)} async requests on "
              f"{len(pool)} slots ({gangs} ganged segments, byte-exact "
              f"vs serial, per-slot DRAM constant):")
        print("\n".join(pool.describe().splitlines()[1:]))  # per-slot

    # --- 11. persistent state: KV-cache decode through the pool ---
    from repro.models.vta_decoder import QuantDecoder
    dec = QuantDecoder()                       # 2 blocks, d=64, numpy attn
    cdec = dec.compile()
    print(f"decoder program: {cdec.describe().splitlines()[0]}")
    n_steps = 8
    with DevicePool(cdec, size=2, backend="pallas") as dpool:
        sess = [dpool.session() for _ in range(4)]   # 4 dialogues
        refs = [dec.reference() for _ in range(4)]
        for t in range(n_steps):                     # lockstep decode
            xs = [dec.token(1000 * i + t) for i in range(4)]
            futs = [s.submit(x=xi) for s, xi in zip(sess, xs)]
            for fut, ref, xi in zip(futs, refs, xs):
                assert np.array_equal(fut.wait(300), ref.step(xi)), \
                    "pooled decode diverged from the eager reference!"
        # each session's KV cache really holds ITS dialogue, in place
        for i, s in enumerate(sess):
            assert np.array_equal(s.state("k0"), refs[i].K[0])
            assert int(s.state("pos0")[0]) == n_steps
        print(f"decoded {n_steps} steps x {len(sess)} sessions "
              f"({cdec.persistent_bytes} persistent B/session at stable "
              f"addresses), bit-exact vs eager numpy; per-slot state:")
        print("\n".join(dpool.describe().splitlines()[1:]))

    # --- 12. continuous batching: 2-program mix behind an admission
    #         window ---
    from repro.core.program import compile_multi
    from repro.core.sched import SchedConfig, Scheduler

    ws = rng.integers(-64, 64, size=(64, 64), dtype=np.int8)
    pa = Program(spec)
    ta = pa.input("x", (16, 64))
    pa.output(pa.matmul(ta, pa.constant("wa", ws), epilogue=ep2))
    pb = Program(spec)
    tb = pb.input("x", (16, 64))
    tb = pb.matmul(tb, pb.constant("wb", ws), epilogue=ep2)
    pb.output(pb.matmul(tb, pb.constant("wb2", ws.T.copy()),
                        epilogue=ep2))
    ca, cb = compile_multi([pa, pb])     # ONE image, disjoint ranges
    assert not ca.image_range.overlaps(cb.image_range)
    with DevicePool([ca, cb], size=4, backend="pallas") as mpool:
        sched = Scheduler(mpool, SchedConfig(window_us=1500.0))
        feeds = [rng.integers(-64, 64, size=(16, 64), dtype=np.int8)
                 for _ in range(8)]
        futs = [sched.submit(program=i % 2, x=f)
                for i, f in enumerate(feeds)]
        for i, (fut, xf) in enumerate(zip(futs, feeds)):
            want = matmul_reference(xf, ws, ep2)
            if i % 2:
                want = matmul_reference(want, ws.T.copy(), ep2)
            assert np.array_equal(fut.wait(timeout=600), want), \
                "windowed result diverged from serial!"
        sa, sb = sched.stats()
        print(f"continuous-batched {sa.completed}+{sb.completed} "
              f"requests of 2 co-staged programs "
              f"({sa.releases + sb.releases} releases, max gang "
              f"{max(sa.max_gang, sb.max_gang)}, programs never mixed "
              f"in a gang); control plane:")
        print(sched.describe())
        sched.close()

    # --- 13. sub-byte weights: int4 packed storage + LUT-GEMM decode ---
    from repro.core.backend import PallasBackend, SimulatorBackend
    from repro.models.quantized import VtaLinear

    wf = rng.normal(size=(96, 64)).astype(np.float32) * 0.1
    xf = rng.normal(size=(2, 96)).astype(np.float32)   # decode-shaped
    lin8, lin4 = VtaLinear(wf, bits=8), VtaLinear(wf, bits=4)
    y8, y4 = lin8(xf), lin4(xf)
    # the packed program is bit-exact across both engines...
    assert np.array_equal(lin4(xf, backend=PallasBackend()),
                          lin4(xf, backend=SimulatorBackend()))
    c8 = next(iter(lin8._programs.values()))
    c4 = next(iter(lin4._programs.values()))
    assert c4.const_bytes * 2 == c8.const_bytes       # int4 = half the bytes
    # ...and decode-shaped calls route through the LUT-GEMM kernel
    lin4(xf, backend=PallasBackend())
    luts = sum(s.lut_launches for s in c4.last_stats)
    # int4 output tracks the int8 path within the coarser quant step
    q_step = float(np.abs(y4 - xf @ wf).max())
    print(f"int4 VtaLinear: {c4.describe().splitlines()[0]}")
    print(f"  const {c4.const_bytes}B packed vs {c8.const_bytes}B int8, "
          f"{luts} LUT-GEMM launches, |y4 - x@W|max {q_step:.3f} "
          f"(int8 path {np.abs(y8 - xf @ wf).max():.3f})")

    # --- 14. self-healing: kill a slot mid-dialogue, respawn + restore ---
    with DevicePool(cdec, size=2, backend="pallas", max_respawns=2,
                    checkpoint_every=1) as hpool:
        hsess = hpool.session(slot=0)
        href = dec.reference()
        for t in range(4):
            xi = dec.token(t)
            assert np.array_equal(hsess.submit(x=xi).wait(300),
                                  href.step(xi)), "decode diverged!"
        hpool.kill_slot(0)                   # chaos: the slot dies NOW
        st = hpool.slot_stats()[0]
        assert st.deaths == 1 and st.respawns == 1, \
            "slot did not respawn from the pristine image!"
        assert hsess.stats.restored_from_step == 4, \
            "session did not restore from its checkpoint!"
        for t in range(4, 6):                # the dialogue just continues
            xi = dec.token(t)
            assert np.array_equal(hsess.submit(x=xi).wait(300),
                                  href.step(xi)), \
                "restored decode diverged from the eager reference!"
        print(f"self-healed mid-dialogue: slot 0 died and respawned, "
              f"session restored from step "
              f"{hsess.stats.restored_from_step} (checkpoint_every=1), "
              f"decode continued bit-exact; recovery accounting:")
        print("\n".join(hpool.describe().splitlines()[1:]))

    # --- 15. autotune the deployment, then compile out of the cache ---
    from repro.core import autotune

    wl = autotune.conv_workload(
        ConvShape(n=1, h=14, w=14, ic=32, oc=32, kh=3, kw=3,
                  stride=1, pad=1), seed=0)
    res = autotune.search(wl, seed=0, n_candidates=8, top_n=2, repeats=1)
    assert res.winner is not None and res.winner.validated
    # rebuild the workload under the winning spec: every accel op now
    # resolves from the tuning record the search just wrote
    tuned_prog, feeds, refs = wl.build(res.winner.candidate.spec,
                                       res.winner.candidate.virtual_threads,
                                       res.winner.candidate.lowering)
    tuned = tuned_prog.compile(use_cache=False)
    assert tuned.tune_hits >= 1 and tuned.tune_misses == 0, \
        "recompile under the tuned spec must be all cache hits!"
    assert np.array_equal(tuned(backend="simulator", **feeds), refs["y"])
    lowering = next(n.lowering for n in tuned.nodes if n.op == "conv2d")
    print(f"autotuned {wl.name}: winner {res.winner.candidate.label()} "
          f"({res.speedup_measured:.2f}x measured over the default), "
          f"conv lowering '{lowering}' picked by replayed cycles")
    print(f"  recompile: {tuned.describe().splitlines()[-1]}")


if __name__ == "__main__":
    main()
