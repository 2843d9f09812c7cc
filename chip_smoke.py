"""On-chip smoke test: the compiled VTA path, natively on one TPU.

Drives the normal path once per phase, in one process, through the
entry points a user calls:

    Program -> CompiledProgram -> DevicePool / Scheduler -> PallasBackend
            -> Mosaic-compiled vta_gemm / tensor_alu / lut_gemm /
               decode_attention kernels

and checks every output bit for bit against the repository's numpy
integer oracles.

Phases:

* ``resnet``: ResNet-18 Table-1 convs C2-C12 at their published shapes
  (batch 1) on the ``pynq()`` and ``tpu_like()`` template instances.
  Each layer is one ``Program.conv2d`` with constant seeded weights and a
  requant+relu epilogue, served by a 2-slot ``DevicePool`` behind a
  ``Scheduler`` for 4 seeded requests, each compared with
  ``conv.conv2d_reference``.
* ``decode``: ``QuantDecoder`` with kernel attention, 4 sessions x 8
  greedy steps through a 2-slot pool; the tokens must equal the eager
  ``DecoderReference``'s.
* ``lowbit``: a decode-shaped ``Program.matmul`` (16 rows) with a constant
  int4 weight on ``hwspec.lowbit(4)`` — what ``VtaLinear(bits=4)``
  compiles — routed to the ``lut_gemm`` kernel, compared with
  ``scheduler.matmul_reference``.

The script exits non-zero and prints no result when JAX finds no TPU,
when the engine would run the Pallas interpreter, when the tuning cache
is not empty (its result must depend on committed files only), when any
phase leaves the kernel fast path (an eager GEMM or ALU instruction), or
when any output differs.  Per program it prints host-clock wall time,
``Program.compile`` time and tuning-cache hits/misses: smoke timings,
not benchmark numbers.  The last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run:  python3 chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

REQUESTS = 4            # seeded requests per conv / matmul program
POOL_SLOTS = 2
DECODE_SESSIONS = 4
DECODE_STEPS = 8
WAIT_S = 900.0          # per-future bound: a wedged pool fails, never hangs


class SmokeFailure(RuntimeError):
    pass


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _check_stats(stats, what: str) -> None:
    """Every accelerator segment ran on the kernels: zero eager GEMM and
    zero eager ALU instructions, and at least one coalesced GEMM."""
    from repro.core.backend import assert_fast_path

    _require(len(stats) > 0, f"{what}: no accelerator segment ran")
    assert_fast_path(stats, allow_eager_alu=False)
    _require(sum(s.coalesced_gemm_insns for s in stats) > 0,
             f"{what}: no GEMM instruction reached a kernel")


def _line(phase: str, name: str, compile_s: float, wall_s: float,
          compiled, extra: str = "") -> None:
    print(f"[{phase}] {name}: compile {compile_s:.3f} s, serve "
          f"{wall_s:.3f} s (host clock, first call compiles kernels), "
          f"tune hits {compiled.tune_hits} misses {compiled.tune_misses}"
          f"{extra}", flush=True)


def _serve_scheduled(engine, compiled, feeds):
    """Serve `feeds` through a 2-slot pool behind a Scheduler; returns
    (outputs, per-segment RunStats, wall seconds)."""
    from repro.core.sched import SchedConfig, Scheduler
    from repro.core.serve import DevicePool

    t0 = time.perf_counter()
    with DevicePool(compiled, size=POOL_SLOTS, backend=engine) as pool, \
            Scheduler(pool, SchedConfig(gang_width=POOL_SLOTS)) as sched:
        futs = [sched.submit(**f) for f in feeds]
        outs = [f.wait(timeout=WAIT_S) for f in futs]
    wall = time.perf_counter() - t0
    stats = [s for f in futs for s in f.pool_future.stats]
    return outs, stats, wall


def phase_resnet(engine, spec_name: str) -> None:
    """ResNet-18 Table-1 C2-C12, one program per layer, on one template
    instance."""
    from repro.core import hwspec
    from repro.core.conv import conv2d_reference
    from repro.core.program import Program
    from repro.core.scheduler import Epilogue
    from repro.core.workloads import resnet18_table1

    spec = getattr(hwspec, spec_name)()
    ep = Epilogue(shift=6, relu=True)
    for layer in resnet18_table1():
        if layer.cpu_only:
            continue
        s = layer.shape
        rng = np.random.default_rng(int(layer.name[1:]))
        w = rng.integers(-8, 8, size=(s.oc, s.ic, s.kh, s.kw), dtype=np.int8)
        xs = [rng.integers(-32, 32, size=(s.n, s.ic, s.h, s.w),
                           dtype=np.int8) for _ in range(REQUESTS)]
        p = Program(spec)
        x = p.input("x", (s.n, s.ic, s.h, s.w))
        p.output(p.conv2d(x, p.constant("w", w), s, epilogue=ep,
                          name=layer.name))
        t0 = time.perf_counter()
        compiled = p.compile()
        compile_s = time.perf_counter() - t0
        outs, stats, wall = _serve_scheduled(engine, compiled,
                                             [{"x": xi} for xi in xs])
        what = f"{layer.name}@{spec_name}"
        _check_stats(stats, what)
        for i, (xi, got) in enumerate(zip(xs, outs)):
            want = conv2d_reference(xi, w, s, epilogue=ep)
            _require(got.shape == want.shape and got.dtype == want.dtype,
                     f"{what} request {i}: {got.shape}/{got.dtype} vs "
                     f"{want.shape}/{want.dtype}")
            diff = int(np.count_nonzero(got != want))
            _require(diff == 0, f"{what} request {i}: {diff} of "
                                f"{want.size} outputs differ from "
                                f"conv2d_reference")
            _require(0 < np.count_nonzero(want) < want.size,
                     f"{what} request {i}: degenerate reference output")
        lowering = compiled.nodes[-1].lowering
        _line("resnet", what, compile_s, wall, compiled,
              f", {lowering}, {len(stats)} segments, "
              f"{sum(st.tile_batches for st in stats)} GEMM launches")


def phase_decode(engine) -> None:
    """Pooled greedy decode with kernel attention vs the eager oracle."""
    from repro.core.serve import DevicePool
    from repro.models.vta_decoder import DecoderConfig, QuantDecoder

    dec = QuantDecoder(DecoderConfig(attention="kernel"))
    t0 = time.perf_counter()
    compiled = dec.compile()
    compile_s = time.perf_counter() - t0
    prompts = [7 * i + 3 for i in range(DECODE_SESSIONS)]
    want = []
    for tok in prompts:
        ref, seq = dec.reference(), []
        for _ in range(DECODE_STEPS):
            tok = int(np.argmax(ref.step(dec.token(tok))))
            seq.append(tok)
        want.append(seq)

    t0 = time.perf_counter()
    stats = []
    with DevicePool(compiled, size=POOL_SLOTS, backend=engine) as pool:
        sess = [pool.session() for _ in prompts]
        toks = list(prompts)
        got = [[] for _ in prompts]
        for _ in range(DECODE_STEPS):
            futs = [s.submit(x=dec.token(t)) for s, t in zip(sess, toks)]
            for i, fut in enumerate(futs):
                toks[i] = int(np.argmax(fut.wait(timeout=WAIT_S)))
                got[i].append(toks[i])
                stats.extend(fut.stats)
    wall = time.perf_counter() - t0
    _check_stats(stats, "decode")
    for i, (g, w) in enumerate(zip(got, want)):
        _require(g == w, f"decode session {i}: tokens {g} differ from the "
                         f"eager reference's {w}")
    _line("decode", f"{dec.cfg.n_blocks}-block d{dec.cfg.d_model} "
          f"{DECODE_SESSIONS}x{DECODE_STEPS} steps", compile_s, wall,
          compiled, f", tokens {got}")


def phase_lowbit(engine) -> None:
    """Decode-shaped int4-weight matmul on the LUT kernel."""
    from repro.core import hwspec
    from repro.core.program import Program
    from repro.core.scheduler import Epilogue, matmul_reference

    m, k, n = 16, 1024, 1024
    spec = hwspec.lowbit(4)
    rng = np.random.default_rng(4)
    w = rng.integers(-8, 8, size=(n, k), dtype=np.int8)
    xs = [rng.integers(-64, 64, size=(m, k), dtype=np.int8)
          for _ in range(REQUESTS)]
    ep = Epilogue(shift=8)
    p = Program(spec)
    x = p.input("x", (m, k))
    p.output(p.matmul(x, p.constant("w", w), epilogue=ep, name="y"))
    t0 = time.perf_counter()
    compiled = p.compile()
    compile_s = time.perf_counter() - t0
    outs, stats, wall = _serve_scheduled(engine, compiled,
                                         [{"x": xi} for xi in xs])
    _check_stats(stats, "lowbit")
    lut = sum(s.lut_launches for s in stats)
    _require(lut > 0, "lowbit: no launch went to the lut_gemm kernel")
    for i, (xi, got) in enumerate(zip(xs, outs)):
        want = matmul_reference(xi, w, ep)
        diff = int(np.count_nonzero(got != want))
        _require(diff == 0, f"lowbit request {i}: {diff} of {want.size} "
                            f"outputs differ from matmul_reference")
        _require(np.count_nonzero(want) > 0,
                 f"lowbit request {i}: degenerate reference output")
    _line("lowbit", f"int4 {m}x{k}x{n}", compile_s, wall, compiled,
          f", {lut} of {sum(s.tile_batches for s in stats)} GEMM launches "
          f"on lut_gemm")


PHASES = (("resnet/pynq", lambda e: phase_resnet(e, "pynq")),
          ("resnet/tpu_like", lambda e: phase_resnet(e, "tpu_like")),
          ("decode", phase_decode),
          ("lowbit", phase_lowbit))


def run_phases(engine) -> list:
    """Run every phase on `engine`; returns the names of those that
    failed (each failure's traceback goes to stderr)."""
    failed = []
    for name, fn in PHASES:
        t0 = time.perf_counter()
        try:
            fn(engine)
        except Exception:
            traceback.print_exc()
            print(f"phase {name}: FAILED", file=sys.stderr, flush=True)
            failed.append(name)
            continue
        print(f"phase {name}: ok in {time.perf_counter() - t0:.1f} s "
              "(host clock)", flush=True)
    return failed


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is "
              f"{dev.platform!r}); this check runs on the chip only",
              file=sys.stderr)
        return 2
    if os.environ.get("REPRO_TUNE_CACHE"):
        print("chip_smoke: REPRO_TUNE_CACHE is set; the smoke must depend "
              "on committed files only", file=sys.stderr)
        return 2

    from repro import compile_cache
    from repro.core.autotune import global_cache
    from repro.core.backend import PallasBackend

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {compile_cache.enable()}",
          flush=True)
    if len(global_cache()):
        print(f"chip_smoke: the tuning cache holds {len(global_cache())} "
              "records; it must be empty", file=sys.stderr)
        return 2
    engine = PallasBackend()
    if engine.resolved_interpret:
        print("chip_smoke: PallasBackend resolved interpret=True on a TPU",
              file=sys.stderr)
        return 2

    failed = run_phases(engine)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "src"))
    # libtpu otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
