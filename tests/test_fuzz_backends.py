"""Cross-backend differential fuzzer: random Program graphs, one encoded
stream, bit-exact DRAM images on both engines — in BOTH fence modes.

The flexibility the conv-lowering modes buy (direct / im2col / via_matmul,
batch-blocked specs, mixed epilogues) has to be paid for with systematic
cross-configuration testing: every random graph is compiled twice
(``fence_mode="buffer"`` and the ``"barrier"`` baseline), each
accelerator segment is executed by ``CrossBackendChecker`` on cloned
devices (SimulatorBackend as the oracle, PallasBackend as the fast path)
with host steps run in between for heterogeneous ``cpu_only`` splits, and
the resulting DRAM images must match byte for byte per mode.  The two
modes' outputs are then byte-diffed against each other and against a
pure-numpy graph evaluator, so a bug that corrupted both engines — or
both fence modes — identically would still be caught.

Determinism: the generator is seeded numpy (no external dependency), so
the CI run is reproducible — override with REPRO_FUZZ_SEED / bound the
work with REPRO_FUZZ_GRAPHS.  REPRO_FUZZ_SPEC=tpu_like switches every
graph onto the MXU-shaped template instance (the nightly job's
configuration; CI keeps the fast pynq-scale mix).  When hypothesis is
installed an additional property-based pass explores the same generator
space.
"""
import os
from unittest import mock

import numpy as np
import pytest

from repro.core import hwspec
from repro.core.backend import CrossBackendChecker
from repro.core.compiler import AccelStep, CpuStep
from repro.core.conv import (ConvShape, conv1x1_eligible,
                             conv_im2col_eligible, conv2d_reference)
from repro.core.isa import AluOp
from repro.core.program import Program
from repro.core.scheduler import Epilogue, matmul_reference

FUZZ_SEED = int(os.environ.get("REPRO_FUZZ_SEED", "20260802"))
# every graph now compiles+runs in BOTH fence modes (2 compile units per
# graph); the default keeps tier-1 wall time near the pre-fence baseline
# while the dedicated CI fuzz job pins REPRO_FUZZ_GRAPHS=56 (>= 50-graph
# acceptance criterion).  Keep each graph tiny so the eager simulator
# side stays fast.
FUZZ_GRAPHS = int(os.environ.get("REPRO_FUZZ_GRAPHS", "36"))
# "" = pynq-scale mix (CI); "tpu_like" = MXU-shaped template (nightly)
FUZZ_SPEC = os.environ.get("REPRO_FUZZ_SPEC", "")
# fuzz FLAVOR: "" = the cross-backend sweep below; "pool" = random
# graphs served through a DevicePool with randomized submit order and
# pool size, byte-diffed against serial execution; "persistent" = random
# STATEFUL graphs (Program.persistent buffers mutated by host ops)
# driven >=3 consecutive calls per engine and byte-diffed against a
# stateful numpy reference AND across engines, whole DRAM images
# included (the nightly job runs all three).  Small always-on pool and
# persistent sweeps keep tier-1 coverage.
FUZZ_FLAVOR = os.environ.get("REPRO_FUZZ_FLAVOR", "")
POOL_GRAPHS = int(os.environ.get("REPRO_FUZZ_POOL_GRAPHS",
                                 "24" if FUZZ_FLAVOR == "pool" else "6"))
PERSIST_GRAPHS = int(os.environ.get(
    "REPRO_FUZZ_PERSIST_GRAPHS",
    "24" if FUZZ_FLAVOR == "persistent" else "6"))
# "sched" = random graphs routed through the continuous-batching
# Scheduler (core.sched) with randomized admission window / gang width /
# queue cap / backpressure policy; survivors byte-diffed against serial,
# typed Shed outcomes accounted exactly (nightly flavor; a small
# always-on sweep keeps tier-1 coverage).
SCHED_GRAPHS = int(os.environ.get(
    "REPRO_FUZZ_SCHED_GRAPHS",
    "24" if FUZZ_FLAVOR == "sched" else "4"))
# "lowbit" = random graphs on packed sub-byte weight specs
# (hwspec.lowbit(4|2|1)): weights constrained to the b-bit range, the
# staged/packed DRAM bytes byte-diffed against the numpy packed
# reference (layout.pack_bits), both engines cross-checked, and the
# Pallas LUT-GEMM vs dense kernel A/B'd on the same stream.
LOWBIT_GRAPHS = int(os.environ.get(
    "REPRO_FUZZ_LOWBIT_GRAPHS",
    "24" if FUZZ_FLAVOR == "lowbit" else "6"))
# "chaos" = random graphs served through a self-healing DevicePool while
# a seeded FaultPlan injects slot kills, DRAM bit flips, and gang delays:
# survivors must be byte-identical to fault-free serial execution, every
# loss must surface a typed error (SlotDied after retry exhaustion /
# PoolClosed), and the pool's fault log must account for every fired
# fault (nightly flavor; a small always-on sweep keeps tier-1 coverage).
CHAOS_GRAPHS = int(os.environ.get(
    "REPRO_FUZZ_CHAOS_GRAPHS",
    "24" if FUZZ_FLAVOR == "chaos" else "4"))

_VEC_OPS = (AluOp.ADD, AluOp.MIN, AluOp.MAX, AluOp.MUL)


# ----------------------------------------------------------------------
# random graph generation
# ----------------------------------------------------------------------
def _rand_epilogue(rng, n_out, spec):
    """Mixed epilogues: requant shifts, relu, clip/no-clip (int8 wrap),
    per-channel bias."""
    kind = rng.integers(0, 5)
    kw = {}
    if kind == 1:
        kw = dict(shift=int(rng.integers(1, 7)))
    elif kind == 2:
        kw = dict(shift=int(rng.integers(0, 7)), relu=True)
    elif kind == 3:
        kw = dict(clip_lo=None, clip_hi=None)          # wraparound store
    elif kind == 4:
        nb = -(-n_out // spec.block_out)
        bias = rng.integers(-1000, 1000, size=nb * spec.block_out,
                            dtype=np.int32)
        blocked = np.repeat(bias.reshape(nb, 1, spec.block_out),
                            spec.batch, axis=1)
        kw = dict(bias_blocked=blocked, shift=int(rng.integers(0, 6)),
                  relu=bool(rng.integers(0, 2)))
    return Epilogue(**kw)


def _rand_conv_shape(rng, spec, n=None, ic=None, h=None, w=None):
    kh = int(rng.integers(1, 4))
    kw = int(rng.integers(1, 4))
    stride = int(rng.integers(1, 3))
    pad = int(rng.integers(0, 2))
    if h is None:
        h = int(rng.integers(max(3, kh), 9))
    if w is None:
        w = h
    # keep the output non-empty
    kh = min(kh, h + 2 * pad)
    kw = min(kw, w + 2 * pad)
    return ConvShape(
        n=n if n is not None else int(rng.integers(1, 2 * spec.batch + 1)),
        h=h, w=w,
        ic=ic if ic is not None else int(rng.integers(1, 34)),
        oc=int(rng.integers(1, 34)), kh=kh, kw=kw, stride=stride, pad=pad)


def _rand_lowering(rng, shape, spec):
    modes = ["direct", None]
    if conv_im2col_eligible(shape):
        modes.append("im2col")
    if conv1x1_eligible(shape, spec):
        modes.append("via_matmul")
    return modes[int(rng.integers(0, len(modes)))]


def _rand_spec(rng):
    if FUZZ_SPEC == "tpu_like":
        return hwspec.tpu_like()
    return hwspec.pynq() if rng.integers(0, 4) else \
        hwspec.HardwareSpec(batch=2)


def build_random_program(rng):
    """One random graph + its input feeds (flavors: dependent matmul
    chains, dependent conv chains with mixed lowerings, independent op
    triples, single convs, heterogeneous cpu_only splits)."""
    spec = _rand_spec(rng)
    vt = int(rng.integers(1, 3))
    p = Program(spec, virtual_threads=vt)
    feeds = {}

    def feed(name, shape, dtype=np.int8, lo=-64, hi=64):
        feeds[name] = rng.integers(lo, hi, size=shape, dtype=dtype)
        return p.input(name, shape, dtype="int8" if dtype == np.int8
                       else "int32")

    flavor = rng.integers(0, 5)
    if flavor == 0:                      # matmul chain (join barriers)
        depth = int(rng.integers(1, 4))
        m = int(rng.integers(1, 41))
        k = int(rng.integers(1, 41))
        t = feed("x", (m, k))
        for i in range(depth):
            n = int(rng.integers(1, 41))
            w = feed(f"w{i}", (n, k))
            t = p.matmul(t, w, epilogue=_rand_epilogue(rng, n, spec),
                         name=f"mm{i}")
            k = n
    elif flavor == 1:                    # conv chain, mixed lowerings
        depth = int(rng.integers(1, 3))
        s = _rand_conv_shape(rng, spec)
        t = feed("x", (s.n, s.ic, s.h, s.w))
        for i in range(depth):
            w = feed(f"k{i}", (s.oc, s.ic, s.kh, s.kw), lo=-16, hi=16)
            t = p.conv2d(t, w, s, epilogue=_rand_epilogue(rng, s.oc, spec),
                         lowering=_rand_lowering(rng, s, spec),
                         name=f"cv{i}")
            if i + 1 < depth:
                s = _rand_conv_shape(rng, spec, n=s.n, ic=s.oc,
                                     h=s.oh, w=s.ow)
    elif flavor == 2:                    # independent ops (SRAM liveness)
        m, k, n = (int(rng.integers(1, 33)) for _ in range(3))
        mm = p.matmul(feed("a", (m, k)), feed("w", (n, k)),
                      epilogue=_rand_epilogue(rng, n, spec), name="mm")
        s = _rand_conv_shape(rng, spec)
        cv = p.conv2d(feed("x", (s.n, s.ic, s.h, s.w)),
                      feed("kc", (s.oc, s.ic, s.kh, s.kw), lo=-16, hi=16),
                      s, epilogue=_rand_epilogue(rng, s.oc, spec),
                      lowering=_rand_lowering(rng, s, spec), name="cv")
        ln = int(rng.integers(1, 300))
        vec = p.vector_binop(
            feed("va", (ln,), np.int32, -2 ** 20, 2 ** 20),
            feed("vb", (ln,), np.int32, -2 ** 20, 2 ** 20),
            op=_VEC_OPS[int(rng.integers(0, len(_VEC_OPS)))], name="vec")
        for r in (mm, cv, vec):
            p.output(r)
    elif flavor == 3:                    # single conv, any shape/mode
        s = _rand_conv_shape(rng, spec)
        p.conv2d(feed("x", (s.n, s.ic, s.h, s.w)),
                 feed("k", (s.oc, s.ic, s.kh, s.kw), lo=-16, hi=16),
                 s, epilogue=_rand_epilogue(rng, s.oc, spec),
                 lowering=_rand_lowering(rng, s, spec), name="cv")
    else:                                # heterogeneous cpu_only split
        depth = 3
        cpu_pos = int(rng.integers(0, depth))
        s = _rand_conv_shape(rng, spec)
        t = feed("x", (s.n, s.ic, s.h, s.w))
        for i in range(depth):
            w = feed(f"k{i}", (s.oc, s.ic, s.kh, s.kw), lo=-16, hi=16)
            cpu = i == cpu_pos
            t = p.conv2d(t, w, s, epilogue=_rand_epilogue(rng, s.oc, spec),
                         cpu_only=cpu,
                         lowering=None if cpu
                         else _rand_lowering(rng, s, spec),
                         name=f"hc{i}")
            if i + 1 < depth:
                s = _rand_conv_shape(rng, spec, n=s.n, ic=s.oc,
                                     h=s.oh, w=s.ow)
    return p, feeds


# ----------------------------------------------------------------------
# numpy graph evaluator (independent of both engines)
# ----------------------------------------------------------------------
def evaluate_reference(p: Program, feeds):
    vals = {}
    for n in p.nodes:
        if n.op == "input":
            vals[n.idx] = feeds[n.name]
        elif n.op == "cpu":
            vals[n.idx] = n.fn(*(vals[i] for i in n.inputs))
        elif n.op == "matmul":
            a, w = (vals[i] for i in n.inputs)
            vals[n.idx] = matmul_reference(a, w, epilogue=n.epilogue,
                                           spec=p.spec)
        elif n.op == "conv2d":
            x, w = (vals[i] for i in n.inputs)
            vals[n.idx] = conv2d_reference(x, w, n.conv, epilogue=n.epilogue)
        elif n.op == "vbinop":
            a, b = (vals[i].astype(np.int64) for i in n.inputs)
            r = {AluOp.ADD: a + b, AluOp.MIN: np.minimum(a, b),
                 AluOp.MAX: np.maximum(a, b), AluOp.MUL: a * b}[n.alu_op]
            vals[n.idx] = r.astype(np.int32).astype(np.int8)
        else:
            raise ValueError(n.op)
    return vals


def cross_check(compiled, feeds):
    """Run every accelerator segment through CrossBackendChecker (cloned
    devices, byte-diffed DRAM), executing host steps in between
    (heterogeneous cpu_only splits), and return the output tensors read
    from the adopted simulator image."""
    for name, arr in feeds.items():
        compiled._write(compiled.input_ids[name], arr)
    checker = CrossBackendChecker()
    for step in compiled.steps:
        if isinstance(step, CpuStep):
            node = compiled.nodes[step.node_id]
            args = [compiled._read(i) for i in node.inputs]
            compiled._write(step.node_id, node.fn(*args))
            continue
        assert isinstance(step, AccelStep)
        report = checker.run(compiled.spec, compiled.device, step.stream)
        assert report.matches, (
            f"{report.mismatched_bytes} DRAM bytes differ between "
            f"simulator and pallas")
        compiled.device.copy_from(report.device_for("simulator"))
    return {compiled.nodes[i].name: compiled._read(i)
            for i in compiled.output_ids}


def _run_one(seed: int) -> None:
    rng = np.random.default_rng(seed)
    p, feeds = build_random_program(rng)
    refs = evaluate_reference(p, feeds)
    outs = {}
    for fence_mode in ("buffer", "barrier"):
        compiled = p.compile(use_cache=False, fence_mode=fence_mode)
        outs[fence_mode] = cross_check(compiled, feeds)
        for i in compiled.output_ids:
            name = p.nodes[i].name
            np.testing.assert_array_equal(
                outs[fence_mode][name], refs[i],
                err_msg=f"seed={seed} fence_mode={fence_mode} node={name} "
                        f"({compiled.describe()})")
    for name in outs["buffer"]:
        np.testing.assert_array_equal(
            outs["buffer"][name], outs["barrier"][name],
            err_msg=f"seed={seed} node={name}: fenced stream diverged "
                    f"from the barrier baseline")


# ----------------------------------------------------------------------
# pool flavor: random graphs served concurrently through a DevicePool,
# byte-diffed against serial single-device execution
# ----------------------------------------------------------------------
def _run_one_pool(seed: int) -> None:
    from repro.core.serve import DevicePool

    rng = np.random.default_rng(seed)
    p, feeds = build_random_program(rng)
    fence_mode = ("buffer", "barrier")[int(rng.integers(0, 2))]
    compiled = p.compile(use_cache=False, fence_mode=fence_mode)
    backend = ("simulator", "pallas")[int(rng.integers(0, 2))]
    pool_size = int(rng.integers(1, 5))
    policy = ("round_robin", "least_loaded")[int(rng.integers(0, 2))]
    n_requests = int(rng.integers(2, 3 + 2 * pool_size))

    # fresh per-request feeds with the same shapes/dtypes (permuted
    # content keeps ranges valid for every node kind)
    def permute(feed):
        return {k: rng.permutation(v.ravel()).reshape(v.shape)
                for k, v in feed.items()}
    requests = [permute(feeds) for _ in range(n_requests)]
    serial = [compiled(backend=backend, **r) for r in requests]
    refs = [evaluate_reference(p, r) for r in requests]

    ctx = (f"seed={seed} fence_mode={fence_mode} backend={backend} "
           f"pool={pool_size}/{policy} ({compiled.describe()})")
    with DevicePool(compiled, size=pool_size, backend=backend,
                    policy=policy) as pool:
        order = rng.permutation(n_requests)              # submit order
        futs = {int(i): pool.submit(**requests[i]) for i in order}
        for i in rng.permutation(n_requests):            # wait order
            got = futs[int(i)].wait(timeout=600)
            want = serial[int(i)]
            if not isinstance(got, dict):
                got = {"out": got}
                want = {"out": want}
            for name in got:
                np.testing.assert_array_equal(
                    got[name], want[name],
                    err_msg=f"{ctx} req={i} node={name}: pooled "
                            "execution diverged from serial")
        for i, ref in enumerate(refs):
            got = futs[i].wait()
            outs = got if isinstance(got, dict) else \
                {p.nodes[compiled.output_ids[0]].name: got}
            for nid in compiled.output_ids:
                np.testing.assert_array_equal(
                    outs[p.nodes[nid].name], ref[nid],
                    err_msg=f"{ctx} req={i}: pooled execution diverged "
                            "from the numpy reference")


# ----------------------------------------------------------------------
# sched flavor: random graphs through the continuous-batching scheduler
# under randomized admission/backpressure configs; every survivor is
# byte-diffed against serial execution and every loss is a typed Shed
# ----------------------------------------------------------------------
def _run_one_sched(seed: int) -> None:
    from repro.core.program import compile_multi
    from repro.core.sched import QueueFull, SchedConfig, Scheduler, Shed
    from repro.core.serve import DevicePool

    rng = np.random.default_rng(seed)
    p, feeds = build_random_program(rng)
    backend = ("simulator", "pallas")[int(rng.integers(0, 2))]
    pool_size = int(rng.integers(1, 5))
    multi = bool(rng.integers(0, 3) == 0)   # 1/3: two co-staged programs
    if multi:
        p2, feeds2 = build_random_program(rng)
        progs = compile_multi([p, p2])
        graphs = [(p, feeds), (p2, feeds2)]
    else:
        progs = [p.compile(use_cache=False)]
        graphs = [(p, feeds)]
    n_requests = int(rng.integers(2, 4 + 2 * pool_size))
    cfg = SchedConfig(
        window_us=float(rng.choice([200.0, 2000.0, 50000.0])),
        gang_width=(None if rng.integers(0, 2)
                    else int(rng.integers(1, pool_size + 1))),
        queue_cap=int(rng.integers(1, n_requests + 2)),
        policy=("reject", "shed_oldest")[int(rng.integers(0, 2))],
        pipeline_depth=int(rng.integers(1, 3)))

    def permute(feed):
        return {k: rng.permutation(v.ravel()).reshape(v.shape)
                for k, v in feed.items()}

    picks = [int(rng.integers(0, len(progs))) for _ in range(n_requests)]
    requests = [permute(graphs[pi][1]) for pi in picks]
    serial = [progs[pi](backend=backend, **r)
              for pi, r in zip(picks, requests)]

    ctx = (f"seed={seed} backend={backend} pool={pool_size} "
           f"multi={multi} cfg={cfg}")
    with DevicePool(progs, size=pool_size, backend=backend) as pool:
        sched = Scheduler(pool, cfg)
        futs = []
        for i in range(n_requests):
            try:
                futs.append((i, sched.submit(program=picks[i],
                                             **requests[i])))
            except QueueFull:
                assert cfg.policy == "reject", \
                    f"{ctx}: QueueFull under policy={cfg.policy}"
        assert futs, f"{ctx}: every submit rejected (cap >= 1)"
        survivors, shed = 0, 0
        for i, f in futs:
            try:
                got = f.wait(timeout=600)
            except Shed:
                shed += 1
                assert cfg.policy == "shed_oldest", \
                    f"{ctx}: Shed under policy={cfg.policy}"
                continue
            survivors += 1
            want = serial[i]
            if not isinstance(got, dict):
                got, want = {"out": got}, {"out": want}
            for name in got:
                np.testing.assert_array_equal(
                    got[name], want[name],
                    err_msg=f"{ctx} req={i} node={name}: windowed "
                            "execution diverged from serial")
        assert survivors >= 1, f"{ctx}: no request survived"
        stats = sched.stats()
        assert sum(s.completed for s in stats) == survivors, ctx
        assert sum(s.shed for s in stats) == shed, ctx
        assert sum(s.failed for s in stats) == 0, ctx
        sched.close()


# ----------------------------------------------------------------------
# chaos flavor: random graphs through a self-healing DevicePool under a
# seeded FaultPlan (kills / bit flips / delays); every survivor is
# byte-diffed against fault-free serial execution, every loss is typed,
# and the fault log must reconcile with the plan's fired entries
# ----------------------------------------------------------------------
def _run_one_chaos(seed: int) -> None:
    from repro.core.chaos import FaultPlan
    from repro.core.serve import DevicePool, SlotDied, PoolClosed

    rng = np.random.default_rng(seed)
    p, feeds = build_random_program(rng)
    compiled = p.compile(use_cache=False)
    backend = ("simulator", "pallas")[int(rng.integers(0, 2))]
    pool_size = int(rng.integers(2, 5))
    n_requests = int(rng.integers(4, 5 + 2 * pool_size))

    def permute(feed):
        return {k: rng.permutation(v.ravel()).reshape(v.shape)
                for k, v in feed.items()}
    requests = [permute(feeds) for _ in range(n_requests)]
    serial = [compiled(backend=backend, **r) for r in requests]

    plan = FaultPlan.random(
        seed=seed, n_gangs=4 * n_requests, slots=pool_size,
        rate=float(rng.choice([0.1, 0.2, 0.3])), max_delay_s=0.01)
    ctx = (f"seed={seed} backend={backend} pool={pool_size} "
           f"{plan.describe()} ({compiled.describe()})")
    survivors, losses = 0, 0
    with DevicePool(compiled, size=pool_size, backend=backend,
                    max_respawns=8, retries=3, retry_backoff_s=0.01,
                    integrity=True, fault_plan=plan) as pool:
        futs = [pool.submit(**r) for r in requests]
        for i, f in enumerate(futs):
            try:
                got = f.wait(timeout=600)   # a hang here is a bug
            except (SlotDied, PoolClosed) as e:
                losses += 1                 # typed, accounted loss
                assert getattr(e, "attempts", 1) >= 1, ctx
                continue
            survivors += 1
            want = serial[i]
            if not isinstance(got, dict):
                got, want = {"out": got}, {"out": want}
            for name in got:
                np.testing.assert_array_equal(
                    got[name], want[name],
                    err_msg=f"{ctx} req={i} node={name}: execution under "
                            "fault injection diverged from fault-free "
                            "serial")
        assert survivors + losses == n_requests, ctx
        assert len(pool.fault_log) == len(plan.fired), \
            f"{ctx}: fault log ({len(pool.fault_log)}) does not " \
            f"reconcile with fired faults ({len(plan.fired)})"
        # respawn math: every death is either respawned or leaves the
        # slot dead (respawn cap), never silent
        for s in pool.slots:
            assert s.stats.respawns <= s.stats.deaths, ctx
            assert s.dead == (s.stats.deaths > s.stats.respawns), ctx


# ----------------------------------------------------------------------
# lowbit flavor: random graphs on packed sub-byte weight specs; the
# packed DRAM image is byte-diffed against the numpy packed reference
# and the LUT-GEMM kernel is A/B'd against the dense kernel per graph
# ----------------------------------------------------------------------
def build_random_lowbit_program(rng):
    """Random graph on an int4/int2/int1-weight template: every weight
    tensor (matmul and conv, constant and per-call input) carries values
    in the b-bit two's-complement range; activations stay full int8."""
    bits = int(rng.choice([4, 4, 2, 1]))
    base = hwspec.pynq() if rng.integers(0, 4) else \
        hwspec.HardwareSpec(batch=2)
    spec = hwspec.lowbit(bits, base)
    qmin, qmax = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    p = Program(spec, virtual_threads=int(rng.integers(1, 3)))
    feeds = {}
    consts = {}

    def feed(name, shape, lo=-64, hi=64):
        feeds[name] = rng.integers(lo, hi, size=shape, dtype=np.int8)
        return p.input(name, shape)

    def wfeed(name, shape):
        w = rng.integers(qmin, qmax + 1, size=shape, dtype=np.int8)
        if rng.integers(0, 2):          # constant: staged packed at compile
            consts[name] = w
            return p.constant(name, w)
        feeds[name] = w                 # input: staged packed per call
        return p.input(name, shape)

    flavor = rng.integers(0, 3)
    if flavor == 0:                      # matmul chain
        depth = int(rng.integers(1, 4))
        m = int(rng.integers(1, 41))
        k = int(rng.integers(1, 41))
        t = feed("x", (m, k))
        for i in range(depth):
            n = int(rng.integers(1, 41))
            t = p.matmul(t, wfeed(f"w{i}", (n, k)),
                         epilogue=_rand_epilogue(rng, n, spec),
                         name=f"mm{i}")
            k = n
    elif flavor == 1:                    # single conv, any lowering
        s = _rand_conv_shape(rng, spec)
        p.conv2d(feed("x", (s.n, s.ic, s.h, s.w)),
                 wfeed("k", (s.oc, s.ic, s.kh, s.kw)),
                 s, epilogue=_rand_epilogue(rng, s.oc, spec),
                 lowering=_rand_lowering(rng, s, spec), name="cv")
    else:                                # independent matmul + conv
        m, k, n = (int(rng.integers(1, 33)) for _ in range(3))
        mm = p.matmul(feed("a", (m, k)), wfeed("w", (n, k)),
                      epilogue=_rand_epilogue(rng, n, spec), name="mm")
        s = _rand_conv_shape(rng, spec)
        cv = p.conv2d(feed("x", (s.n, s.ic, s.h, s.w)),
                      wfeed("kc", (s.oc, s.ic, s.kh, s.kw)),
                      s, epilogue=_rand_epilogue(rng, s.oc, spec),
                      lowering=_rand_lowering(rng, s, spec), name="cv")
        for r in (mm, cv):
            p.output(r)
    return p, feeds, consts


def _check_packed_image(compiled, weights):
    """Byte-diff every sub-byte weight buffer in DRAM against the numpy
    packed reference (TensorMeta.pack -> layout.pack_bits)."""
    from repro.core import layout as _layout  # noqa: F401  (reference path)
    for name, w in weights.items():
        nid = compiled.input_ids[name]
        meta = compiled.nodes[nid].meta
        if meta.kind not in ("wgt", "cwgt"):
            continue
        raw = compiled.device.dram.read(compiled.addrs[nid],
                                        meta.nbytes(compiled.spec))
        want = meta.pack(w, compiled.spec)
        assert want.dtype == np.uint8, "sub-byte weights must store packed"
        np.testing.assert_array_equal(
            raw, want.reshape(-1),
            err_msg=f"{name}: packed DRAM bytes diverge from the numpy "
                    "packed reference")


def _run_one_lowbit(seed: int) -> None:
    from repro.core.backend import PallasBackend

    rng = np.random.default_rng(seed)
    p, feeds, consts = build_random_lowbit_program(rng)
    refs = evaluate_reference(p, {**feeds, **consts})
    outs = {}
    for fence_mode in ("buffer", "barrier"):
        compiled = p.compile(use_cache=False, fence_mode=fence_mode)
        outs[fence_mode] = cross_check(compiled, feeds)
        _check_packed_image(compiled, {**feeds, **consts})
        for i in compiled.output_ids:
            name = p.nodes[i].name
            np.testing.assert_array_equal(
                outs[fence_mode][name], refs[i],
                err_msg=f"seed={seed} fence_mode={fence_mode} node={name} "
                        f"({compiled.describe()})")
    for name in outs["buffer"]:
        np.testing.assert_array_equal(
            outs["buffer"][name], outs["barrier"][name],
            err_msg=f"seed={seed} node={name}: fenced stream diverged "
                    f"from the barrier baseline")
    # both GEMM kernels on the Pallas engine: with a row bound above
    # every launch (all LUT-GEMM) and of 0 (all dense MXU) the result
    # must reproduce the numpy reference bit-exactly
    compiled = p.compile(use_cache=False)
    for lut_rows in (1 << 30, 0):
        with mock.patch.object(PallasBackend, "LUT_MAX_ROWS", lut_rows):
            got = compiled(backend=PallasBackend(), **feeds)
        if not isinstance(got, dict):
            got = {p.nodes[compiled.output_ids[0]].name: got}
        for i in compiled.output_ids:
            name = p.nodes[i].name
            np.testing.assert_array_equal(
                got[name], refs[i],
                err_msg=f"seed={seed} LUT_MAX_ROWS={lut_rows} node={name}: "
                        "GEMM kernel diverged from the numpy reference")


# ----------------------------------------------------------------------
# persistent flavor: random stateful graphs run >=3 consecutive calls,
# byte-diffed against a stateful numpy reference and across engines
# ----------------------------------------------------------------------
def _state_variant(rng):
    """One of three in-place state mutations (accumulate / roll-in /
    decay-accumulate) — all pure, deterministic numpy."""
    kind = int(rng.integers(0, 3))

    def accum(h, s):
        ns = np.clip(s.astype(np.int32) + h.astype(np.int32),
                     -128, 127).astype(np.int8)
        return ns, ns

    def roll(h, s):
        ns = np.roll(s, 1, axis=0)
        ns = ns.copy()
        ns[0] = h[0]
        out = np.clip(ns.astype(np.int32) + h.astype(np.int32),
                      -128, 127).astype(np.int8)
        return out, ns

    def decay(h, s):
        ns = np.clip((s.astype(np.int32) >> 1) + h.astype(np.int32),
                     -128, 127).astype(np.int8)
        return ns, ns

    fn = (accum, roll, decay)[kind]
    return fn, f"fuzz.state.{fn.__name__}"


def build_random_persistent_program(rng):
    """Random stateful graph: accel matmul feeds a host op that mutates a
    persistent state buffer in place; optionally a second matmul consumes
    the host output (accelerator reads data derived from cross-call
    state).  Returns (program, make_feeds)."""
    spec = _rand_spec(rng)
    p = Program(spec, virtual_threads=int(rng.integers(1, 3)))
    m = int(rng.integers(1, 2 * spec.batch + 1))
    k = int(rng.integers(1, 33))
    n = int(rng.integers(1, 33))
    shapes = {"x": (m, k), "w0": (n, k)}
    x = p.input("x", (m, k))
    w0 = p.input("w0", (n, k))
    h = p.matmul(x, w0, epilogue=Epilogue(shift=int(rng.integers(1, 6))),
                 name="h")
    s_init = rng.integers(-64, 64, size=(m, n), dtype=np.int8)
    s = p.persistent("state", (m, n), init=s_init)
    fn, key = _state_variant(rng)
    t = p.host(fn, h, s, shape=(m, n), kind="mat", key=key,
               updates=(s,), name="mut")
    if rng.integers(0, 2):
        n2 = int(rng.integers(1, 33))
        shapes["w1"] = (n2, n)
        t = p.matmul(t, p.input("w1", (n2, n)),
                     epilogue=_rand_epilogue(rng, n2, spec), name="mm1")
    p.output(t)

    def make_feeds():
        return {name: rng.integers(-64, 64, size=shp, dtype=np.int8)
                for name, shp in shapes.items()}
    return p, make_feeds


def evaluate_reference_stateful(p: Program, calls):
    """Numpy oracle over a sequence of calls: persistent buffers carry
    across calls, host updates are applied in graph order.  Returns
    (per-call output dicts, final persistent state by node id)."""
    state = {nx.idx: np.array(nx.const) for nx in p.nodes if nx.persistent}
    outs = []
    for feeds in calls:
        vals = {}
        for nd in p.nodes:
            if nd.op == "input":
                vals[nd.idx] = state[nd.idx] if nd.persistent \
                    else feeds[nd.name]
            elif nd.op == "cpu":
                res = nd.fn(*(vals[i] for i in nd.inputs))
                if nd.updates:
                    out, *upd = res
                    for nid, arr in zip(nd.updates, upd):
                        state[nid] = arr
                else:
                    out = res
                vals[nd.idx] = out
            elif nd.op == "matmul":
                a, w = (vals[i] for i in nd.inputs)
                vals[nd.idx] = matmul_reference(a, w, epilogue=nd.epilogue,
                                                spec=p.spec)
            else:
                raise ValueError(nd.op)
        outs.append({i: vals[i] for i in p._outputs})
    return outs, state


def _run_one_persistent(seed: int) -> None:
    rng = np.random.default_rng(seed)
    p, make_feeds = build_random_persistent_program(rng)
    n_calls = int(rng.integers(3, 6))
    calls = [make_feeds() for _ in range(n_calls)]
    refs, ref_state = evaluate_reference_stateful(p, calls)
    for fence_mode in ("buffer", "barrier"):
        compiled = p.compile(use_cache=False, fence_mode=fence_mode)
        ctx = f"seed={seed} fence_mode={fence_mode}"
        devs = {eng: compiled.device.clone(trim=True)
                for eng in ("simulator", "pallas")}
        for eng, dev in devs.items():
            for ci, feeds in enumerate(calls):
                res = compiled.run_on(dev, backend=eng, inputs=feeds)
                outs = res.outputs if isinstance(res.outputs, dict) else \
                    {p.nodes[compiled.output_ids[0]].name: res.outputs}
                for nid in compiled.output_ids:
                    np.testing.assert_array_equal(
                        outs[p.nodes[nid].name], refs[ci][nid],
                        err_msg=f"{ctx} eng={eng} call={ci}: stateful "
                                "output diverged from numpy reference")
            for nid in compiled.persistent_ids:
                np.testing.assert_array_equal(
                    compiled._read(nid, device=dev), ref_state[nid],
                    err_msg=f"{ctx} eng={eng}: final persistent state "
                            "diverged from numpy reference")
        # byte-identical WHOLE DRAM images after the same call sequence:
        # stream staging, constants, arena recycling, persistent state
        np.testing.assert_array_equal(
            devs["simulator"].dram.mem, devs["pallas"].dram.mem,
            err_msg=f"{ctx}: engines diverged somewhere in the DRAM "
                    "image after the stateful call sequence")


# ----------------------------------------------------------------------
# the deterministic CI sweep (>= 50 graphs, fixed seed)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("idx", range(FUZZ_GRAPHS))
def test_fuzz_cross_backend(idx):
    if FUZZ_FLAVOR == "pool":
        _run_one_pool(FUZZ_SEED + idx)
    elif FUZZ_FLAVOR == "persistent":
        _run_one_persistent(FUZZ_SEED + idx)
    elif FUZZ_FLAVOR == "sched":
        _run_one_sched(FUZZ_SEED + idx)
    elif FUZZ_FLAVOR == "lowbit":
        _run_one_lowbit(FUZZ_SEED + idx)
    elif FUZZ_FLAVOR == "chaos":
        _run_one_chaos(FUZZ_SEED + idx)
    else:
        _run_one(FUZZ_SEED + idx)


@pytest.mark.parametrize("idx", range(POOL_GRAPHS))
def test_fuzz_pool(idx):
    """Always-on pooled sweep (smaller than the main grid); the nightly
    REPRO_FUZZ_FLAVOR=pool job widens it and flips the main grid over to
    the pool flavor too."""
    _run_one_pool(FUZZ_SEED + 7919 + idx)


@pytest.mark.parametrize("idx", range(PERSIST_GRAPHS))
def test_fuzz_persistent(idx):
    """Always-on stateful sweep; the nightly REPRO_FUZZ_FLAVOR=persistent
    job widens it and flips the main grid over too."""
    _run_one_persistent(FUZZ_SEED + 104729 + idx)


@pytest.mark.parametrize("idx", range(LOWBIT_GRAPHS))
def test_fuzz_lowbit(idx):
    """Always-on sub-byte weight sweep (packed DRAM bytes byte-diffed
    against the numpy packed reference; LUT vs dense kernel A/B); the
    nightly REPRO_FUZZ_FLAVOR=lowbit job widens it and flips the main
    grid over too."""
    _run_one_lowbit(FUZZ_SEED + 15485863 + idx)


@pytest.mark.parametrize("idx", range(CHAOS_GRAPHS))
def test_fuzz_chaos(idx):
    """Always-on self-healing sweep (seeded fault injection; survivors
    byte-diffed against fault-free serial, losses typed); the nightly
    REPRO_FUZZ_FLAVOR=chaos job widens it and flips the main grid over
    too."""
    _run_one_chaos(FUZZ_SEED + 2750159 + idx)


@pytest.mark.parametrize("idx", range(SCHED_GRAPHS))
def test_fuzz_sched(idx):
    """Always-on continuous-batching sweep; the nightly
    REPRO_FUZZ_FLAVOR=sched job widens it and flips the main grid over
    too."""
    _run_one_sched(FUZZ_SEED + 1299709 + idx)


# optional hypothesis pass over the same generator space
try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=20, deadline=None, derandomize=True,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(min_value=0, max_value=2 ** 31 - 1))
    def test_fuzz_cross_backend_hypothesis(seed):
        _run_one(seed)
except ImportError:                                        # pragma: no cover
    pass
