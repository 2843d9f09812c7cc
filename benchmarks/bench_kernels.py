"""Kernel microbenchmarks (interpret-mode wall time is NOT TPU time — the
value here is the oracle check + the derived-from-spec static analysis of
each kernel's VMEM working set and arithmetic intensity), plus the
execution-backend comparison: the same encoded task-ISA stream through
the cycle-capable simulator vs the Pallas engine."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hwspec
from repro.core.runtime import Runtime
from repro.core.scheduler import (matmul_reference, read_matmul_result,
                                  schedule_matmul)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.vta_gemm import vta_gemm, vta_gemm_ref


def _time(fn, *args, reps=3):
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def run(quiet: bool = False):
    rows = []
    rng = np.random.default_rng(0)
    # vta_gemm: VMEM working set at (128,128,128) int8 blocks
    a = jnp.asarray(rng.integers(-128, 128, (256, 256)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (256, 256)), jnp.int8)
    us_ref = _time(lambda: vta_gemm(a, w, use_pallas=False))
    us_pl = _time(lambda: vta_gemm(a, w, use_pallas=True, interpret=True))
    ok = bool(jnp.array_equal(vta_gemm(a, w, use_pallas=True, interpret=True),
                              vta_gemm_ref(a, w)))
    vmem_kib = (128 * 128 + 128 * 128 + 128 * 128 * 4 + 128 * 128 * 4) / 1024
    rows.append({"kernel": "vta_gemm_256", "us_ref": round(us_ref, 1),
                 "us_interpret": round(us_pl, 1), "exact": ok,
                 "vmem_working_set_kib": vmem_kib,
                 "intensity_flops_per_byte": round(
                     2 * 256 ** 3 / (3 * 256 * 256), 1)})
    # flash attention block analysis
    q = jnp.asarray(rng.normal(size=(1, 512, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    us_f = _time(lambda: flash_attention(q, k, k, use_pallas=True,
                                         interpret=True, bq=128, bk=128))
    close = bool(jnp.allclose(
        flash_attention(q, k, k, use_pallas=True, interpret=True,
                        bq=128, bk=128),
        flash_attention(q, k, k, use_pallas=False), atol=2e-5))
    rows.append({"kernel": "flash_attn_512", "us_ref": "-",
                 "us_interpret": round(us_f, 1), "exact": close,
                 "vmem_working_set_kib": (128 * 64 * 4 * 3 + 128 * 128 * 4) / 1024,
                 "intensity_flops_per_byte": round(
                     4 * 512 * 512 * 64 / (3 * 512 * 64 * 4), 1)})
    if not quiet:
        print(",".join(str(k) for k in rows[0].keys()))
        for r in rows:
            print(",".join(str(v) for v in r.values()))
    return rows


def run_backends(size: int = 512, reps: int = 3, quiet: bool = False) -> dict:
    """Execution-backend comparison on one schedule_matmul stream: the
    decoded-stream Pallas engine must beat the per-uop numpy simulator by
    >= 10x on the size^3 workload while staying bit-exact.  Best-of-reps
    wall-clock per engine (first pallas rep additionally pays the one-time
    jit compile and is excluded by the warm-up call)."""
    spec = hwspec.pynq()
    rng = np.random.default_rng(0)
    a = rng.integers(-128, 128, size=(size, size), dtype=np.int8)
    w = rng.integers(-128, 128, size=(size, size), dtype=np.int8)

    def one(backend):
        rt = Runtime(spec)
        plan = schedule_matmul(rt, a, w, virtual_threads=2)
        stats = rt.synchronize(backend=backend)
        return stats, read_matmul_result(rt, plan)

    one("pallas")                       # warm the jit caches once
    runs = {b: [one(b) for _ in range(reps)]
            for b in ("pallas", "simulator")}
    pal_s = min(s.wall_time_s for s, _ in runs["pallas"])
    sim_s = min(s.wall_time_s for s, _ in runs["simulator"])
    ref = matmul_reference(a, w)
    exact = all(np.array_equal(out, ref)
                for outs in runs.values() for _, out in outs)
    row = {"workload": f"matmul_{size}x{size}x{size}",
           "simulator_s": round(sim_s, 3),
           "pallas_s": round(pal_s, 3),
           "speedup_x": round(sim_s / max(pal_s, 1e-9), 1),
           "exact": exact}
    if not quiet:
        print(",".join(str(k) for k in row.keys()))
        print(",".join(str(v) for v in row.values()))
    return row


def fit_timing_constants(spec=None, quiet: bool = False) -> dict:
    """Calibrate TimingModel DMA/compute constants against MEASURED Pallas
    kernel times on this host, so ``RunStats.total_cycles`` predicts
    wall-clock on the Pallas engine (the ROADMAP calibration item).

    Model being fitted (see ``TimingModel``):
      * GEMM insn latency = #matrix-multiplies cycles, i.e. the spec's
        ``macs_per_cycle`` per cycle -> fit ``freq_mhz`` from the measured
        vta_gemm MAC rate (one warmed ``vta_gemm_pallas`` at 512^3);
      * DMA latency = ``dram_latency_cycles`` + bytes / ``bytes_per_cycle``
        -> fit bandwidth and fixed setup cost from a two-point host-memcpy
        measurement through the simulated DRAM (a 4 KiB and a 16 MiB
        write), converted to cycles at the fitted frequency.

    Returns the kwargs for ``hwspec.calibrated`` /
    ``HardwareSpec.replace``.  The constants fitted on the dev container
    are recorded as ``hwspec.HOST_FIT``.
    """
    from repro.core.driver import Dram
    from repro.kernels._platform import resolve_interpret
    from repro.kernels.vta_gemm.kernel import vta_gemm_pallas

    spec = spec or hwspec.pynq()
    rng = np.random.default_rng(0)
    n = 512
    a = jnp.asarray(rng.integers(-128, 128, (n, n)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (n, n)), jnp.int8)
    # auto-select like PallasBackend: native on real TPU (the ROADMAP
    # recalibration path), interpreter on CPU CI
    interpret = resolve_interpret(None)

    def gemm():
        return vta_gemm_pallas(a, w, epilogue="requant", shift=7,
                               interpret=interpret)

    us = _time(gemm)                       # warmed best-effort microseconds
    mac_rate = n ** 3 / (us / 1e6)         # MACs / second
    freq_hz = mac_rate / spec.macs_per_cycle
    freq_mhz = freq_hz / 1e6

    dram = Dram(1 << 25)
    small = np.zeros(4 * 1024, np.uint8)
    big = np.zeros(16 * 1024 * 1024, np.uint8)
    a0, a1 = dram.alloc(small.nbytes), dram.alloc(big.nbytes)

    def t_write(addr, arr, reps=5):
        dram.write(addr, arr)
        t0 = time.perf_counter()
        for _ in range(reps):
            dram.write(addr, arr)
        return (time.perf_counter() - t0) / reps

    ts, tb = t_write(a0, small), t_write(a1, big)
    bw = (big.nbytes - small.nbytes) / max(tb - ts, 1e-12)
    lat_s = max(ts - small.nbytes / bw, 0.0)
    fit = dict(freq_mhz=round(freq_mhz, 4),
               dram_rd_bytes_per_cycle=round(bw / freq_hz, 2),
               dram_wr_bytes_per_cycle=round(bw / freq_hz, 2),
               dram_latency_cycles=max(1, int(round(lat_s * freq_hz))))
    if not quiet:
        print(f"fitted: {mac_rate / 1e6:.1f} MMAC/s "
              f"-> freq {freq_mhz:.3f} MHz; "
              f"DMA {bw / 1e9:.2f} GB/s "
              f"-> {fit['dram_rd_bytes_per_cycle']} B/cycle, "
              f"latency {fit['dram_latency_cycles']} cycles")
        print("hwspec.calibrated() kwargs:", fit)
    return fit


def run_fit_check(quiet: bool = False) -> dict:
    """Sanity row: cycles from the calibrated TimingModel on the Pallas
    engine vs its measured wall-clock for one schedule_matmul stream —
    the two should agree within a small factor (the calibration's whole
    point; interpret-mode timings are host-dependent, so the gate is
    loose)."""
    from repro.core.simulator import TimingModel

    fit = fit_timing_constants(quiet=True)
    spec = hwspec.pynq().replace(**fit)
    rng = np.random.default_rng(0)
    a = rng.integers(-128, 128, size=(256, 256), dtype=np.int8)
    w = rng.integers(-128, 128, size=(256, 256), dtype=np.int8)
    rt = Runtime(spec)
    schedule_matmul(rt, a, w, virtual_threads=2)
    rt.synchronize(backend="pallas", keep_stream=True)   # warm jit
    rt.reset_stream()
    rt2 = Runtime(spec)
    schedule_matmul(rt2, a, w, virtual_threads=2)
    stats = rt2.synchronize(backend="pallas", timing=TimingModel(spec))
    predicted_s = stats.total_cycles / (spec.freq_mhz * 1e6)
    row = {"fit": fit, "total_cycles": stats.total_cycles,
           "predicted_s": round(predicted_s, 4),
           "wall_s": round(stats.wall_time_s, 4),
           "ratio": round(stats.wall_time_s / max(predicted_s, 1e-12), 2)}
    if not quiet:
        print(f"calibration check: predicted {row['predicted_s']}s vs "
              f"wall {row['wall_s']}s (ratio {row['ratio']}x)")
    return row


def main() -> None:
    run()
    run_backends()
    run_fit_check()


if __name__ == "__main__":
    main()
