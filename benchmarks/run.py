"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV summary lines plus each
benchmark's own table.  The times are the host clock of whatever
platform JAX runs on (the Pallas interpreter on a CPU), not chip
measurements.
"""
from __future__ import annotations

import time


def _section(title):
    print(f"\n==== {title} " + "=" * max(0, 60 - len(title)))


def main() -> None:
    from benchmarks import (bench_fig15_roofline, bench_fig16_e2e,
                            bench_kernels, bench_program,
                            bench_sec26_bandwidth)
    from repro import compile_cache

    compile_cache.enable()

    summary = []

    _section("Paper Fig. 15: ResNet-18 roofline + latency hiding")
    t0 = time.perf_counter()
    rows, u1, u2 = bench_fig15_roofline.run()
    summary.append(("fig15_latency_hiding",
                    (time.perf_counter() - t0) * 1e6,
                    f"util {u1:.2f}->{u2:.2f} (paper 0.70->0.88)"))

    _section("Paper Fig. 16: end-to-end ResNet-18 offload")
    t0 = time.perf_counter()
    _, cpu_s, off_s, speedup = bench_fig16_e2e.run()
    summary.append(("fig16_e2e_offload", (time.perf_counter() - t0) * 1e6,
                    f"{cpu_s:.2f}s->{off_s:.2f}s conv x{speedup:.0f}"))

    _section("Paper Sec 2.6: GEMM-core SRAM bandwidth")
    t0 = time.perf_counter()
    bench_sec26_bandwidth.run()
    summary.append(("sec26_bandwidth", (time.perf_counter() - t0) * 1e6,
                    "derivation check"))

    _section("Kernel microbench (interpret mode + oracle check)")
    t0 = time.perf_counter()
    bench_kernels.run()
    summary.append(("kernels", (time.perf_counter() - t0) * 1e6, "oracle ok"))

    _section("Execution backends: simulator vs Pallas, one task-ISA stream")
    t0 = time.perf_counter()
    row = bench_kernels.run_backends()
    summary.append(("backends", (time.perf_counter() - t0) * 1e6,
                    f"x{row['speedup_x']} exact={row['exact']}"))

    _section("Program-level JIT: one stream vs per-op synchronize")
    t0 = time.perf_counter()
    prow = bench_program.run()
    summary.append(("program_jit", (time.perf_counter() - t0) * 1e6,
                    f"{prow['insns']} insns, "
                    f"x{prow['rows'][0]['speedup_x']} on sim"))

    _section("Pool serving: async device pool, gang dispatch (1/2/4 slots)")
    t0 = time.perf_counter()
    prow = bench_program.run_pool()
    summary.append(("pool_serving", (time.perf_counter() - t0) * 1e6,
                    f"x{prow['speedup_4v1_x']} pool4 vs pool1"))

    _section("Decode serving: persistent-KV decoder, 4 sessions, pool 1 vs 4")
    t0 = time.perf_counter()
    drow = bench_program.run_decode()
    summary.append(("decode_serving", (time.perf_counter() - t0) * 1e6,
                    f"x{drow['speedup_4v1_x']} pool4 vs pool1, "
                    f"p99 {drow['pools']['4']['p99_step_ms']}ms"))

    _section("Sub-byte weights: packed int4/int2 constants + LUT-GEMM")
    t0 = time.perf_counter()
    lrow = bench_program.run_lowbit()
    summary.append(("lowbit_weights", (time.perf_counter() - t0) * 1e6,
                    f"x{lrow['bits']['4']['shrink_x']} const shrink at int4, "
                    f"exact={lrow['bits']['4']['exact_both_engines']}"))

    _section("General conv2d fast path: coalesced vs eager (measured C2)")
    t0 = time.perf_counter()
    _, conv_speedup = bench_fig16_e2e.run_measured()
    summary.append(("conv_fast_path", (time.perf_counter() - t0) * 1e6,
                    f"x{conv_speedup:.1f} vs pre-PR eager path"))

    _section("Traffic smoke: continuous batching, open-loop arrivals")
    t0 = time.perf_counter()
    from benchmarks import loadgen
    trow = loadgen.run_traffic(smoke=True)
    mcell = next(iter(trow["matmul"]["traces"].values()))
    summary.append(("traffic_smoke", (time.perf_counter() - t0) * 1e6,
                    f"exact={mcell['modes']['windowed']['exact']} "
                    "(full: python -m benchmarks.loadgen)"))

    _section("Chaos smoke: self-healing pool under seeded fault injection")
    t0 = time.perf_counter()
    from benchmarks import bench_chaos
    crow = bench_chaos.run(smoke=True)
    summary.append(("chaos_smoke", (time.perf_counter() - t0) * 1e6,
                    f"exact={crow['exact']} ratio={crow['goodput_ratio']} "
                    "(full: python -m benchmarks.bench_chaos)"))

    _section("Autotune smoke: seeded DSE on the calibrated cycle oracle")
    t0 = time.perf_counter()
    arow = bench_program.run_autotune(candidates=12, top=4)
    summary.append(("autotune_smoke", (time.perf_counter() - t0) * 1e6,
                    " ".join(f"x{w['speedup_measured']:.2f}"
                             for w in arow["workloads"]) +
                    " (deep: python -m benchmarks.bench_program)"))

    _section("summary CSV")
    print("name,us_per_call,derived")
    for name, us, derived in summary:
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    main()
