"""End-to-end driver #1 (paper §5, Fig. 16): ResNet-18 conv offload onto VTA.

Part 1 — per-layer study (unchanged semantics): quantize one ResNet conv
layer end to end, lower it with the direct-conv scheduler (2D padded DMA,
no host im2col), execute on the simulator, check the result against the
integer oracle, and report cycle-level timing.

Part 2 — heterogeneous execution, *executed* rather than modelled: a
C1-style `cpu_only` stem, the anchor conv layer, and a 1x1 pointwise conv
are compiled by the program-level JIT into host steps + ONE task-ISA
stream, then run end to end on BOTH execution backends (simulator oracle
and the Pallas fast path) and checked bit-exact against the chained
reference — the Fig. 16 CPU/accelerator split as a real program.  The
chain is channel-scaled (<=128) so the simulator side stays quick.

Run:  PYTHONPATH=src python examples/resnet18_offload.py [layer]
"""
import sys
import time

import numpy as np

from repro import compile_cache
from repro.core import Program, hwspec, quantize as q
from repro.core.backend import assert_fast_path
from repro.core.conv import ConvShape, conv2d_reference, read_conv_result, \
    schedule_conv2d
from repro.core.runtime import Runtime
from repro.core.scheduler import Epilogue
from repro.core.simulator import TimingModel
from repro.core.workloads import layer_by_name


def per_layer_study(name: str) -> None:
    layer = layer_by_name(name)
    shape = layer.shape
    spec = hwspec.pynq()
    print(f"{name}: {shape.ic}->{shape.oc} ch, {shape.h}x{shape.w}, "
          f"k={shape.kh} s={shape.stride}  ({shape.gops:.2f} GOP)")

    rng = np.random.default_rng(0)
    x_f = rng.normal(size=(shape.n, shape.ic, shape.h, shape.w)) \
        .astype(np.float32)
    w_f = (rng.normal(size=(shape.oc, shape.ic, shape.kh, shape.kw))
           / np.sqrt(shape.ic * shape.kh * shape.kw)).astype(np.float32)

    qx, qw = q.calibrate(x_f), q.calibrate(w_f)
    xq, wq = q.quantize(x_f, qx), q.quantize(w_f, qw)

    rt = Runtime(spec)
    ep = Epilogue(shift=0, relu=False)
    plan = schedule_conv2d(rt, xq, wq, shape, epilogue=ep, virtual_threads=2)
    stats = rt.synchronize(timing=TimingModel(spec))
    got = read_conv_result(rt, plan)
    want = conv2d_reference(xq, wq, shape, epilogue=ep)
    assert np.array_equal(got, want), "simulator diverged!"

    secs = stats.total_cycles / (spec.freq_mhz * 1e6)
    print(f"exact on VTA; {stats.total_cycles:,} cycles = {secs * 1e3:.1f} ms "
          f"@ {spec.freq_mhz:.0f} MHz")
    print(f"achieved {stats.gops(spec.freq_mhz):.1f} / {spec.peak_gops:.1f} "
          f"GOPS  (utilization {stats.compute_utilization:.1%})")
    print(f"DRAM traffic: {stats.dram_rd_bytes / 1e6:.1f} MB read, "
          f"{stats.dram_wr_bytes / 1e6:.1f} MB written "
          f"(intensity {stats.arithmetic_intensity:.1f} ops/B)")


def heterogeneous_chain(name: str) -> None:
    """cpu stem -> anchor conv -> 1x1 conv, one Program, two engines."""
    anchor = layer_by_name(name).shape
    spec = hwspec.pynq()
    # channel-scale the chain so the behavioral simulator stays quick
    ic = min(anchor.ic, 128)
    oc = min(anchor.oc, 128)
    h = anchor.h
    stem = ConvShape(n=1, h=2 * h, w=2 * h, ic=3, oc=ic,
                     kh=7, kw=7, stride=2, pad=3)          # C1-style, CPU
    body = ConvShape(n=1, h=h, w=h, ic=ic, oc=oc, kh=anchor.kh,
                     kw=anchor.kw, stride=1, pad=anchor.kh // 2)
    point = ConvShape(n=1, h=body.oh, w=body.ow, ic=oc, oc=oc,
                      kh=1, kw=1, stride=1, pad=0)         # C3-style, GEMM
    ep = Epilogue(shift=5, relu=True)

    rng = np.random.default_rng(1)
    x = rng.integers(-64, 64, size=(1, 3, stem.h, stem.w), dtype=np.int8)
    k1 = rng.integers(-8, 8, size=(stem.oc, 3, 7, 7), dtype=np.int8)
    k2 = rng.integers(-8, 8, size=(body.oc, body.ic, body.kh, body.kw),
                      dtype=np.int8)
    k3 = rng.integers(-8, 8, size=(point.oc, point.ic, 1, 1), dtype=np.int8)

    prog = Program(spec)
    t = prog.conv2d(prog.input("x", x.shape), prog.input("k1", k1.shape),
                    stem, epilogue=ep, cpu_only=True)
    t = prog.conv2d(t, prog.input("k2", k2.shape), body, epilogue=ep)
    prog.conv2d(t, prog.input("k3", k3.shape), point, epilogue=ep)
    t0 = time.perf_counter()
    compiled = prog.compile()
    print(f"\nheterogeneous chain ({name}-scaled): {compiled.describe()}")
    print(f"compiled in {(time.perf_counter() - t0) * 1e3:.0f} ms; "
          f"{len(compiled.cpu_steps)} cpu step(s) + "
          f"{len(compiled.accel_steps)} accelerator stream(s), "
          f"{compiled.insn_count} instructions")

    ref = conv2d_reference(x, k1, stem, epilogue=ep)
    ref = conv2d_reference(ref, k2, body, epilogue=ep)
    ref = conv2d_reference(ref, k3, point, epilogue=ep)

    for backend in ("simulator", "pallas"):
        t0 = time.perf_counter()
        got = compiled(backend=backend, x=x, k1=k1, k2=k2, k3=k3)
        dt = time.perf_counter() - t0
        assert np.array_equal(got, ref), f"{backend} diverged!"
        print(f"  {backend}: exact end-to-end in {dt * 1e3:.0f} ms")
        if backend == "pallas":
            # every conv — including the kh*kw>1 body — must stay on the
            # coalesced vta_gemm fast path (describe() shows the modes)
            assert_fast_path(compiled.last_stats)
            coal = sum(s.coalesced_gemm_insns for s in compiled.last_stats)
            eager = sum(s.eager_gemm_insns for s in compiled.last_stats)
            print(f"    fast path: {coal} GEMM insns coalesced, "
                  f"{eager} eager fallbacks")
    # second invocation: rebinds DRAM inputs, no re-scheduling
    x2 = rng.integers(-64, 64, size=x.shape, dtype=np.int8)
    t0 = time.perf_counter()
    compiled(x=x2, k1=k1, k2=k2, k3=k3)
    print(f"  rerun with new data (stream cache hit): "
          f"{(time.perf_counter() - t0) * 1e3:.0f} ms")


def main() -> None:
    compile_cache.enable()
    name = sys.argv[1] if len(sys.argv) > 1 else "C9"
    per_layer_study(name)
    heterogeneous_chain(name)


if __name__ == "__main__":
    main()
