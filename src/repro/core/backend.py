"""Pluggable execution backends: one task-ISA stream, two engines (§3).

The paper's runtime supports *heterogeneous execution*: the identical
binary instruction stream runs on a behavioral simulator or on the FPGA,
and the simulator doubles as the differential-testing oracle for the fast
path.  This module reproduces that split for the jax_pallas port:

  * ``SimulatorBackend`` — the cycle-capable numpy engine
    (``simulator.run_program``), bit-exact oracle semantics;
  * ``PallasBackend``   — interprets the *decoded* task-ISA stream,
    coalescing each virtual-thread tile's LOAD/GEMM/ALU/STORE groups into
    calls to the TPU-native Pallas kernels (``kernels.vta_gemm`` and
    ``kernels.tensor_alu``), honoring the same dependence-token protocol;
  * ``CrossBackendChecker`` — runs one encoded stream on every backend
    against cloned devices and diffs the resulting DRAM images, turning
    the simulator into the oracle for the fast path exactly the way the
    paper checks the FPGA against simulation.

Both engines consume the stream *after* ``IsaLayout.encode_stream`` —
there is no side channel: whatever the scheduler lowered is what runs.

Why sequential interpretation is sound: the runtime emits ``dep_push``
flags on instructions that are already in the stream and attaches each
``dep_pop`` to the next instruction it emits, so every token's producer
precedes its consumer in program order.  Program order also preserves
each module's queue order, hence it is one of the legal executions the
token protocol admits (§2.3) — the PallasBackend verifies this once per
stream, before it runs, and raises ``DeadlockError`` on streams that
violate it.

jax / Pallas imports are deferred to PallasBackend execution so that
importing :mod:`repro.core` stays numpy-only.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Protocol, Sequence, \
    Tuple, Union, runtime_checkable

import numpy as np

from .driver import Device
from .hwspec import HardwareSpec
from .isa import (AluInsn, AluOp, DEP_IN_EDGES, DEP_OUT_EDGES, FinishInsn,
                  GemmInsn, Insn, IsaLayout, LoadStoreInsn, MemId, Opcode,
                  route_queue, LOAD_Q, COMPUTE_Q, STORE_Q)
from .simulator import (DeadlockError, ModuleStats, RunStats, Simulator,
                        TimingModel, replay_timing, run_program,
                        _MODULE_NAMES)
from .spans import span, tags


# ----------------------------------------------------------------------
# the backend contract
# ----------------------------------------------------------------------
@runtime_checkable
class ExecutionBackend(Protocol):
    """Anything that can run an encoded VTA instruction stream against a
    device and report RunStats.  ``staged_addr`` (when >= 0 / not None)
    names a pre-staged DRAM copy of the same stream: the engine kicks the
    fetch registers at it instead of re-staging — the serving fast path's
    zero-allocation repeat call."""

    name: str

    def execute(self, spec: HardwareSpec, device: Device, stream: np.ndarray,
                timing: Optional[TimingModel] = None,
                staged_addr: Optional[int] = None) -> RunStats:
        ...


class SimulatorBackend:
    """The paper's behavioral/cycle-level engine (default)."""

    name = "simulator"

    def __init__(self, timing: Optional[TimingModel] = None):
        self.timing = timing

    def execute(self, spec: HardwareSpec, device: Device, stream: np.ndarray,
                timing: Optional[TimingModel] = None,
                staged_addr: Optional[int] = None) -> RunStats:
        t0 = time.perf_counter()
        stats = run_program(spec, device, stream,
                            timing=timing or self.timing,
                            staged_addr=staged_addr)
        stats.wall_time_s = time.perf_counter() - t0
        stats.backend = self.name
        return stats


# ----------------------------------------------------------------------
# PallasBackend: decoded-stream interpreter over the Pallas kernels
# ----------------------------------------------------------------------
_ALU_NAMES = {AluOp.MIN: "min", AluOp.MAX: "max", AluOp.ADD: "add",
              AluOp.SHR: "shr", AluOp.MUL: "mul"}

# token FIFO name + dep flag consumed per queue / produced per queue
# (shared with the runtime's static validator)
_IN_EDGES = DEP_IN_EDGES
_OUT_EDGES = DEP_OUT_EDGES

# content-addressed decoded-stream cache (see PallasBackend._decode_cached).
# Shared across backend instances AND serving threads: the pool scheduler
# may decode concurrently with a foreground call, so every access holds
# _DECODE_LOCK (pop+reinsert is not atomic under concurrent eviction).
# Each entry also holds the stream's interpretation plans, which live
# and are evicted with it (see PallasBackend._run_gang).
_DECODE_CACHE: Dict[tuple, "_Decoded"] = {}
_DECODE_LOCK = threading.Lock()
# LRU bound on the shared cache: generous by default (a long-lived
# multi-program server holds a handful of streams per program, and an
# expert layer one per held expert and row count it has seen), but
# configurable so it can never grow without limit.  Evictions are
# counted — cumulatively here, per run in RunStats.decode_evictions.
_DECODE_CACHE_CAP = 1024
_DECODE_EVICTIONS = 0


def set_decode_cache_cap(cap: int) -> int:
    """Re-bound the process-wide decoded-stream LRU cache at `cap`
    entries (0 disables retention entirely), trimming least-recently-hit
    entries immediately if it is over the new bound.  Returns the number
    of entries trimmed by this call."""
    global _DECODE_CACHE_CAP, _DECODE_EVICTIONS
    if cap < 0:
        raise ValueError(f"decode cache cap must be >= 0, got {cap}")
    trimmed = 0
    with _DECODE_LOCK:
        _DECODE_CACHE_CAP = cap
        while len(_DECODE_CACHE) > cap:
            _DECODE_CACHE.pop(next(iter(_DECODE_CACHE)))
            trimmed += 1
        _DECODE_EVICTIONS += trimmed
    return trimmed


def decode_cache_info() -> Dict[str, int]:
    """Live size / bound / lifetime eviction count of the shared
    decoded-stream cache (ops introspection)."""
    with _DECODE_LOCK:
        return {"size": len(_DECODE_CACHE), "cap": _DECODE_CACHE_CAP,
                "evictions": _DECODE_EVICTIONS}


@dataclass
class _Decoded:
    """One decoded stream as the cache holds it: its instructions and,
    per setting of the switches that shape the engine's analysis, the
    :class:`_StreamPlan` recorded from its first run.  ``plans`` is None
    where the engine keeps no plan (``cache_decode=False`` or a cache
    cap of 0); it is read and written under _DECODE_LOCK."""
    insns: List[Insn]
    plans: Optional[Dict[tuple, "_StreamPlan"]] = None


class _Step(NamedTuple):
    """One instruction's entry in a stream plan (``PallasBackend._analyze``):
    the method that does its work, called as ``(states, statss, insn,
    *args)``, and the uop SRAM bytes and pending-tile keys it was derived
    from (None where it read neither), which a replay checks first."""
    apply: str
    uops: Optional[bytes] = None
    pending: Optional[Tuple[int, ...]] = None
    args: tuple = ()


@dataclass(frozen=True)
class _StreamPlan:
    """What ``PallasBackend._run_gang`` derives from one stream and the
    uop SRAM, recorded on the stream's first run and replayed by later
    ones: one step per instruction, the per-module instruction counts,
    and the dependence tokens pushed.  Never changed once published."""
    steps: Tuple[_Step, ...]
    counts: Tuple[Tuple[str, int], ...]
    tokens_pushed: int


@dataclass(frozen=True, eq=False)
class _TileShape:
    """The stream-derived half of a pending tile's resolution plan: its
    GEMM chunks grouped by grid (each group concatenates along K), the
    fused requant shift, the structural part of the plan key (``head``;
    per group ``gsig``: the weight shape and the grid's relative ids and
    operand shape), and, unless the one group is the tile's own grid,
    each group's scatter positions in the tile."""
    n_chunks: int
    n_alu: int
    groups: Tuple[Tuple[np.ndarray, Tuple[int, ...]], ...]
    shift: Optional[int]
    head: tuple
    gsig: Tuple[tuple, ...]
    pos: Optional[Tuple[np.ndarray, ...]]


class _TileLayout:
    """A reset step's slot for its tile's :class:`_TileShape`: filled
    once, when the run that records the stream's plan first plans the
    tile, and only read by replays (which plan exactly the tiles the
    recording run planned)."""
    __slots__ = ("shape",)

    def __init__(self):
        self.shape: Optional[_TileShape] = None


@dataclass
class _TilePlan:
    """One tile's resolution plan for one run (``_plan_tile``): GEMM
    stages ``wgroups = [(W, [(grid, A, group), ...]), ...]``, one per
    distinct weight tile, and which grid groups each stage holds."""
    wgroups: list
    shape: _TileShape
    partition: Tuple[Tuple[int, ...], ...]


def _frozen(a: np.ndarray) -> np.ndarray:
    """A read-only copy of an index array a plan step keeps (a copy, so
    that a view does not keep its whole base array alive)."""
    a = np.array(a)
    a.setflags(write=False)
    return a


@dataclass
class _GemmChunk:
    """One coalesced GEMM instruction: the acc-element grid it wrote and a
    snapshot of its operands.  ``grid`` may equal the owning tile's full
    (reset) grid — the blocked-matmul case — or cover a sub-region of it,
    which is the direct-conv structure: one instruction per output row
    ``oh``, each accumulating kh*kw*cbt uops into its row of the tile."""
    grid: np.ndarray                    # (iter_out, iter_in) acc element ids
    a: np.ndarray                       # (io*batch, U*block_in) int8
    w: np.ndarray                       # (ii*block_out, U*block_in) int8


@dataclass
class _PendingTile:
    """A lazily-evaluated accumulator tile: the coalesced record of one
    virtual-thread context's reset + GEMM chunks + ALU epilogue, resolved
    with batched ``vta_gemm`` Pallas calls (plus fused ALU chains) when
    the tile is stored or otherwise observed."""
    grid: np.ndarray                    # canonical (reset) grid of acc ids
    indices: np.ndarray                 # sorted unique ids (overlap queries)
    chunks: List[_GemmChunk] = field(default_factory=list)
    # epilogue: ("imm", op, imm) | ("tensor", op, (R, C) int32 matrix)
    alu_chain: List[tuple] = field(default_factory=list)
    # the reset step's shape slot (None: plan the tile from scratch)
    layout: Optional[_TileLayout] = None


class _Clock:
    """Host seconds one gang spends in each engine phase: ``stage``
    (building and uploading kernel operands), ``launch`` (the kernel
    call until it returns) and ``sync`` (waiting for the result and
    reading it back).  Each phase is also a ``vta.engine.<phase>`` span;
    phases are timed per launch, never per instruction."""

    def __init__(self):
        self.s = {"stage": 0.0, "launch": 0.0, "sync": 0.0}

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        try:
            with span("engine." + name):
                yield
        finally:
            self.s[name] += time.perf_counter() - t


@dataclass
class _RunState:
    """Per-execute() interpreter state, passed explicitly so one
    PallasBackend instance can be shared (and re-entered) safely."""
    sim: Simulator                          # SRAM state + eager semantics
    clock: _Clock                           # shared by the whole gang
    pending: Dict[int, _PendingTile] = field(default_factory=dict)
    # GEMM weight operands gathered in this run, by slot: chunks that
    # gather the same weight rows with no WGT load between them share one
    # gather.  The analysis (state 0) assigns the slots in `wslots`, by
    # the rows' ids since the last WGT load; a slot is the position of
    # the first instruction that gathered them
    wsnap: Dict[int, np.ndarray] = field(default_factory=dict)
    wslots: Dict[tuple, int] = field(default_factory=dict)


class PallasBackend:
    """Executes a decoded task-ISA stream through the Pallas kernels.

    LOADs update numpy SRAM state eagerly (DMA semantics are reused from
    the Simulator).  GEMM/ALU instructions whose micro-coded affine index
    pattern matches the blocked-matmul / direct-conv / tile-epilogue
    structure are *coalesced* per accumulator tile and resolved by
    ``vta_gemm`` / ``tensor_alu`` when the tile is stored; anything else
    falls back to the simulator's eager per-instruction semantics, so
    arbitrary valid streams still execute correctly — just without the
    fast path.  ``RunStats.coalesced_*`` / ``eager_*`` count which route
    each compute instruction took (see :func:`assert_fast_path`).

    ``coalesce_subgrids=False`` restricts coalescing to instructions whose
    grid equals the tile's reset grid exactly (the pre-generalization
    behavior, which sent direct-conv schedules to the eager loop) — kept
    as an A/B switch for benchmarks and debugging.  ``batch_tiles=False``
    likewise disables the batched tile dispatch (one kernel launch per
    pending tile, the pre-serving-path behavior).
    """

    name = "pallas"

    #: auto LUT selection: per-tile activation rows at or below this are
    #: "decode-shaped" and route to the LUT-GEMM (bit-plane) kernel when
    #: weights are sub-byte.  Fitted to the interpreter; on the MXU that
    #: kernel costs one int8 pass per weight bit, so the value awaits a
    #: chip measurement
    LUT_MAX_ROWS = 16

    def __init__(self, interpret: Optional[bool] = None,
                 check_tokens: bool = True,
                 coalesce_subgrids: bool = True,
                 batch_tiles: bool = True,
                 cache_decode: bool = True,
                 use_lut: Optional[bool] = None):
        # interpret=None follows the platform (see resolved_interpret)
        self.interpret = interpret
        self.check_tokens = check_tokens
        self.coalesce_subgrids = coalesce_subgrids
        self.batch_tiles = batch_tiles
        self.cache_decode = cache_decode
        # use_lut: None -> auto (sub-byte weights AND decode-shaped tiles);
        # True forces the LUT kernel for every sub-byte GEMM; False pins
        # the dense kernel (A/B baseline).  int8 specs never use it.
        self.use_lut = use_lut

    @property
    def resolved_interpret(self) -> bool:
        """Whether the kernels this engine launches run in the Pallas
        interpreter (True) or Mosaic-compiled on the TPU (False): the
        constructor's `interpret`, with None resolved from the platform."""
        from ..kernels._platform import resolve_interpret
        return resolve_interpret(self.interpret)

    def _lut_select(self, spec: HardwareSpec, rows: int) -> bool:
        """Per-shape kernel choice for one GEMM launch group: LUT-GEMM
        (one MXU pass per weight bit plane) vs dense MXU GEMM.  Both are
        bit-exact; this is purely a roofline call, so the fuzzer sweeps it
        freely."""
        if not spec.wgt_packed or self.use_lut is False:
            return False
        return bool(self.use_lut) or rows <= self.LUT_MAX_ROWS

    # ------------------------------------------------------------------
    def execute(self, spec: HardwareSpec, device: Device, stream: np.ndarray,
                timing: Optional[TimingModel] = None,
                staged_addr: Optional[int] = None) -> RunStats:
        """Same control handshake as the hardware path: the stream is
        DMA'd to DRAM (or a pre-staged copy at `staged_addr` is kicked —
        zero per-call allocation), the fetch registers are set, and the
        engine runs to FINISH.  With `timing`, the same TimingModel
        cycle-accounting the simulator performs is replayed over the
        decoded stream, so RunStats.total_cycles is meaningful on both
        engines (wall_time_s stays this engine's real clock).

        A single-device execute is a gang of one — every launch-batching
        decision below is shared with :meth:`execute_gang`, so the whole
        test suite exercises the same code path the device pool serves
        through."""
        return self.execute_gang(spec, [device], stream, timing=timing,
                                 staged_addr=staged_addr)[0]

    def execute_gang(self, spec: HardwareSpec, devices: Sequence[Device],
                     stream: np.ndarray,
                     timing: Optional[TimingModel] = None,
                     staged_addr: Optional[int] = None) -> List[RunStats]:
        """Run ONE encoded stream on N devices in lockstep (SPMD over a
        device pool): the stream — hence every scheduling, coalescing and
        materialization decision — is identical across devices; only the
        DRAM data differs.  Each kernel launch therefore batches the
        peer tiles of ALL gang members along the existing vmapped tile
        axis, paying the per-launch dispatch cost once for the pool —
        the sharded batch dispatch that makes pooled serving throughput
        scale with pool size.  Returns one RunStats per device
        (``gang_size`` records the gang width; ``wall_time_s`` is the
        shared gang window, not a per-device slice, and so are the
        phase seconds ``stage_s``, ``launch_s`` and ``sync_s``).  The
        window is the span ``vta.engine.gang``, tagged with the gang
        width and the ids of an enclosing :func:`spans.tagged`."""
        t0 = time.perf_counter()
        clock = _Clock()
        with span("engine.gang", width=len(devices), **tags()):
            isa = IsaLayout(spec)
            if staged_addr is None:
                # per-device staging may land at different addresses;
                # the staged CONTENT is identical, so decode from the
                # first
                addr = [d.stage_stream(stream) for d in devices][0]
            else:
                addr = staged_addr
                for d in devices:
                    d.kick_stream(addr, stream.shape[0])
            raw = devices[0].dram.read(
                addr, stream.shape[0] * isa.insn_bytes,
                dtype=np.uint64, shape=(stream.shape[0], isa.insn_words))
            dec, evicted = self._decode_cached(spec, isa, raw)
            statss = self._run_gang(spec, devices, dec, clock)
            wall = time.perf_counter() - t0
        rep = None
        if timing is not None:
            # cycle replay happens OUTSIDE the wall-clock window: the
            # pure-python scheduler pass prices the stream, it is not
            # part of this engine's execution time
            rep = replay_timing(spec, dec.insns, timing)
        for d, stats in zip(devices, statss):
            d.regs.set_done()
            stats.backend = self.name
            stats.wall_time_s = wall
            stats.stage_s = clock.s["stage"]
            stats.launch_s = clock.s["launch"]
            stats.sync_s = clock.s["sync"]
            stats.gang_size = len(devices)
            stats.decode_evictions = evicted
            if rep is not None:
                stats.total_cycles = rep.total_cycles
                for nm, ms in rep.modules.items():
                    stats.modules[nm].busy_cycles = ms.busy_cycles
                    stats.modules[nm].stall_on_token = ms.stall_on_token
        return statss

    def _decode_cached(self, spec: HardwareSpec, isa: IsaLayout,
                       raw: np.ndarray) -> Tuple[_Decoded, int]:
        """Decode the raw stream words, memoized by content digest: a
        serving loop re-running one pre-staged stream pays the (pure
        python) decode exactly once.  Keyed on the bytes actually read
        from DRAM, so there is still no side channel.  The entry also
        holds the stream's interpretation plans (:meth:`_run_gang`), so a
        plan is keyed by the same digest and evicted with its stream;
        with ``cache_decode=False``, or a cap of 0, neither is kept.
        Returns ``(decoded, evicted)`` where `evicted` counts LRU entries
        this call pushed out of the bounded cache
        (set_decode_cache_cap)."""
        import hashlib
        global _DECODE_EVICTIONS
        if not self.cache_decode:
            return _Decoded(isa.decode_stream(raw)), 0
        key = (spec, hashlib.sha1(raw.tobytes()).hexdigest())
        with _DECODE_LOCK:
            hit = _DECODE_CACHE.pop(key, None)
            if hit is not None:
                _DECODE_CACHE[key] = hit   # re-insert: LRU order by last hit
                return hit, 0
        dec = _Decoded(isa.decode_stream(raw))
        evicted = 0
        with _DECODE_LOCK:
            while len(_DECODE_CACHE) >= max(1, _DECODE_CACHE_CAP):
                # evict the least-recently-used entry; hot streams survive
                _DECODE_CACHE.pop(next(iter(_DECODE_CACHE)))
                evicted += 1
            if _DECODE_CACHE_CAP > 0:
                dec.plans = {}
                _DECODE_CACHE[key] = dec
            _DECODE_EVICTIONS += evicted
        return dec, evicted

    # ------------------------------------------------------------------
    def _run_gang(self, spec: HardwareSpec, devices: Sequence[Device],
                  dec: _Decoded, clock: _Clock) -> List[RunStats]:
        """Interpret one decoded stream against N per-device states in
        lockstep.  Control flow (structure detection, tile bookkeeping,
        materialization triggers) is data-independent — it derives from
        the stream and the uop SRAM, which are identical across the gang
        — so every decision is taken once on state 0 and applied to all;
        only the operand data differs per state.  Invariant: the states'
        ``pending`` dicts stay key-synchronized throughout.

        Each instruction is analysed into a step (:meth:`_analyze`) that
        the gang then applies.  The first run of a stream, per setting of
        ``check_tokens``, ``coalesce_subgrids`` and ``batch_tiles``,
        records its steps, the token check and the per-module counts as a
        :class:`_StreamPlan` in the stream's decode-cache entry
        (:meth:`_decode_cached`), built outside _DECODE_LOCK and
        published under it.  Later runs, at any gang width, replay the
        steps and do only the data work: DMA, operand gathers (one per
        weight slot, see ``_RunState.wsnap``), weight-byte grouping,
        kernel launches and write-back.  Before a
        replayed step is applied, the uop SRAM bytes and the pending-tile
        keys it was derived from are checked against state 0; on a
        mismatch that instruction and the rest of the stream are analysed
        afresh, so a replay takes no decision the analysis would not.
        ``RunStats.plan_hit`` is 1 when the whole stream replayed."""
        states = [_RunState(sim=Simulator(spec, d), clock=clock)
                  for d in devices]
        statss = [RunStats(modules={n: ModuleStats()
                                    for n in _MODULE_NAMES.values()})
                  for _ in devices]
        insns = dec.insns
        switches = (self.check_tokens, self.coalesce_subgrids,
                    self.batch_tiles)
        plan = None
        if dec.plans is not None:
            with _DECODE_LOCK:
                plan = dec.plans.get(switches)
        if plan is None:
            counts, pushed = self._check_stream(insns)
            done, steps = 0, [] if dec.plans is not None else None
        else:
            counts, pushed = plan.counts, plan.tokens_pushed
            done, steps = self._replay(plan.steps, insns, states, statss), None
            if done < len(insns):
                # the analysis takes over: plan what is pending afresh
                # and gather weights anew
                for st in states:
                    st.wsnap.clear()
                    for t in st.pending.values():
                        t.layout = None
        for i in range(done, len(insns)):
            step = self._analyze(i, insns[i], states[0])
            if steps is not None:
                steps.append(step)
            getattr(self, step.apply)(states, statss, insns[i], *step.args)

        # a well-formed stream leaves nothing pending, but flush anyway so
        # partial streams (no FINISH/store) still leave coherent SRAM
        if states[0].pending:
            self._materialize_group(states, list(states[0].pending), statss,
                                    None)
        if steps is not None:
            made = _StreamPlan(tuple(steps), counts, pushed)
            with _DECODE_LOCK:
                dec.plans.setdefault(switches, made)
        hit = int(plan is not None and done == len(insns))
        for stats in statss:
            for nm, n in counts:
                stats.modules[nm].insn_count = n
            stats.tokens_pushed = pushed
            stats.plan_hit = hit
        return statss

    def _check_stream(self, insns: Sequence[Insn]
                      ) -> Tuple[Tuple[Tuple[str, int], ...], int]:
        """Per-module instruction counts and the dependence tokens pushed
        (counted under ``check_tokens``, as the token protocol is checked).
        The protocol is stream-determined, so it is checked once per
        stream, before any instruction runs: a pop from an empty FIFO
        raises DeadlockError."""
        counts = {n: 0 for n in _MODULE_NAMES.values()}
        tokens = {"l2c": 0, "c2l": 0, "c2s": 0, "s2c": 0}
        pushed = 0
        for insn in insns:
            q = route_queue(insn)
            counts[_MODULE_NAMES[q]] += 1
            if not self.check_tokens:
                continue
            for fifo, flag in _IN_EDGES[q]:
                if getattr(insn.dep, flag):
                    if tokens[fifo] == 0:
                        raise DeadlockError(
                            f"{type(insn).__name__} pops empty dependence"
                            f" FIFO {fifo}: stream is not a legal "
                            f"program-order execution")
                    tokens[fifo] -= 1
            for fifo, flag in _OUT_EDGES[q]:
                if getattr(insn.dep, flag):
                    tokens[fifo] += 1
                    pushed += 1
        return tuple(counts.items()), pushed

    def _replay(self, steps: Sequence[_Step], insns: Sequence[Insn],
                states: Sequence[_RunState],
                statss: Sequence[RunStats]) -> int:
        """Apply recorded steps while the uop bytes and pending-tile keys
        each was derived from still hold; returns how many were applied."""
        st0 = states[0]
        uop_sram = st0.sim.uop_sram
        for i, (insn, (apply, uops, pending, args)) in enumerate(
                zip(insns, steps)):
            if uops is not None and \
                    uop_sram[insn.uop_bgn:insn.uop_end].tobytes() != uops:
                return i
            if pending is not None and tuple(st0.pending) != pending:
                return i
            getattr(self, apply)(states, statss, insn, *args)
        return len(steps)

    # ------------------------------------------------------------------
    # analysis: what an instruction does, from the stream, the uop SRAM
    # and the pending tiles of state 0
    # ------------------------------------------------------------------
    def _analyze(self, i: int, insn: Insn, st0: _RunState) -> _Step:
        """The step of instruction `i`, changing no machine state."""
        if isinstance(insn, FinishInsn):
            return _Step("_ap_nop")
        if isinstance(insn, LoadStoreInsn):
            if insn.opcode == Opcode.STORE:
                lo = insn.sram_base
                hi = insn.sram_base + insn.y_size * insn.x_size
            elif insn.memory_type in (MemId.ACC, MemId.OUT):
                # both land in tile-owned state: ACC loads overwrite
                # accumulators, OUT loads overwrite the write-through
                # mirror a later STORE reads
                width = insn.x_pad_0 + insn.x_size + insn.x_pad_1
                rows = insn.y_pad_0 + insn.y_size + insn.y_pad_1
                lo, hi = insn.sram_base, insn.sram_base + rows * width
            else:
                if insn.memory_type == MemId.WGT:
                    st0.wslots.clear()      # weight rows change: new slots
                return _Step("_ap_commit")
            return _Step("_ap_commit", None, tuple(st0.pending),
                         self._range_need(st0, lo, hi))
        if not isinstance(insn, (GemmInsn, AluInsn)):
            raise TypeError(type(insn))
        sim0 = st0.sim
        words = sim0.uop_sram[insn.uop_bgn:insn.uop_end]
        uops = sim0.uop_layout.decode_kernel(words)
        if not uops or insn.iter_out == 0 or insn.iter_in == 0:
            return _Step("_ap_nop", words.tobytes())
        analyze = self._analyze_gemm if isinstance(insn, GemmInsn) \
            else self._analyze_alu
        apply, args = analyze(i, st0, insn, uops)
        return _Step(apply, words.tobytes(), tuple(st0.pending), args)

    def _range_need(self, st0: _RunState, lo: int, hi: int) -> tuple:
        """``(keys, peers)`` of a store or ACC/OUT load over SRAM
        [lo, hi): the pending tiles it forces, and their peer candidates
        (:meth:`_peer_candidates`); () when it forces none."""
        need = tuple(
            base for base, t in st0.pending.items()
            if t.indices[0] < hi and lo <= t.indices[-1]
            and np.any((t.indices >= lo) & (t.indices < hi)))
        if not need:
            return ()
        # store / ACC-load trigger: peer virtual-thread tiles of the
        # same op are complete here (their epilogues precede the
        # group's first store in program order) — batch them along
        return need, self._peer_candidates(st0, need)

    def _peer_candidates(self, st0: _RunState, keys: Sequence[int]
                         ) -> Optional[Tuple[int, ...]]:
        """Pending tiles structurally like a forced one (``_pre_key``),
        which may resolve in the same launches; whether they do is the
        plan-key match, which reads weight bytes and is made in every
        run (:meth:`_materialize_group`).  None: no peer sweep."""
        if not self.batch_tiles:
            return None
        pre = {self._pre_key(st0.pending[k]) for k in keys
               if st0.pending[k].chunks}
        if not pre:
            return ()
        return tuple(base for base, t in st0.pending.items()
                     if base not in keys and t.chunks
                     and self._pre_key(t) in pre)

    @staticmethod
    def _tiles_holding(st0: _RunState, idx: np.ndarray) -> Tuple[int, ...]:
        """The keys of the pending tiles holding any of the acc ids
        `idx`."""
        idx = np.unique(idx)
        return tuple(base for base, t in st0.pending.items()
                     if np.isin(idx, t.indices).any())

    @staticmethod
    def _decode_structure(insn, uops, dsts, srcs, wgts):
        """Detect the 2-level-affine blocked-matmul index structure:
        dst = f(i0, i1), src = g(i0, u), wgt = h(i1, u) with all dsts
        distinct.  Returns (dst_grid, src_idx, wgt_idx) or None."""
        io, ii, U = insn.iter_out, insn.iter_in, len(uops)
        D = dsts.reshape(io, ii, U)
        S = srcs.reshape(io, ii, U)
        W = wgts.reshape(io, ii, U)
        if not (D == D[:, :, :1]).all():
            return None
        grid = D[:, :, 0]
        if np.unique(grid).size != grid.size:
            return None
        if not (S == S[:, :1, :]).all():
            return None
        if not (W == W[:1, :, :]).all():
            return None
        return grid, S[:, 0, :], W[0, :, :]

    def _find_containing(self, st: _RunState, grid: np.ndarray
                         ) -> Optional[Tuple[int, _PendingTile]]:
        """The pending tile this GEMM accumulates into: an exact grid
        match (blocked matmul / im2col), or — with sub-grid coalescing —
        any tile whose reset region contains every dst id (the direct-conv
        per-output-row structure).  Returns (pending key, tile) so a gang
        caller can fetch the same tile in every peer state."""
        base = int(grid.min())
        tile = st.pending.get(base)
        if tile is not None and tile.grid.shape == grid.shape \
                and (tile.grid == grid).all():
            return base, tile
        if not self.coalesce_subgrids:
            return None
        ids = grid.ravel()
        lo, hi = int(ids.min()), int(ids.max())
        for k, t in st.pending.items():
            if lo >= t.indices[0] and hi <= t.indices[-1] \
                    and np.isin(ids, t.indices).all():
                return k, t
        return None

    def _analyze_gemm(self, i: int, st0: _RunState, insn: GemmInsn, uops
                      ) -> Tuple[str, tuple]:
        dsts, srcs, wgts = st0.sim._affine_indices(insn, uops)
        struct = self._decode_structure(insn, uops, dsts, srcs, wgts)
        if struct is None:
            return "_ap_commit", (self._tiles_holding(st0, dsts),)
        grid, src_idx, wgt_idx = struct
        if insn.reset:
            # reset opens a fresh accumulation tile; whatever overlapped
            # before is dead (never observed) for an exact-region match,
            # and must be resolved first otherwise
            base = int(grid.min())
            prev = st0.pending.get(base)
            drop = prev is not None and prev.grid.shape == grid.shape \
                and bool((prev.grid == grid).all())
            need = () if drop else self._tiles_holding(st0, grid)
            return "_ap_reset", (
                base, drop, need, _frozen(grid), _frozen(np.unique(grid)),
                _TileLayout())
        found = self._find_containing(st0, grid)
        if found is None or found[1].alu_chain:
            # accumulate-onto-existing-values, post-epilogue, or
            # partially-overlapping GEMM: resolve lazies, then run the
            # eager oracle semantics
            return "_ap_commit", (self._tiles_holding(st0, dsts),)
        s = st0.sim.spec
        macs = grid.size * src_idx.shape[1] * s.batch * s.block_in \
            * s.block_out
        wslot = st0.wslots.setdefault((wgt_idx.shape, wgt_idx.tobytes()), i)
        return "_ap_chunk", (
            found[0], _frozen(grid), _frozen(src_idx), _frozen(wgt_idx),
            wslot, macs)

    def _analyze_alu(self, i: int, st0: _RunState, insn: AluInsn, uops
                     ) -> Tuple[str, tuple]:
        s = st0.sim.spec
        dsts, srcs, _ = st0.sim._affine_indices(insn, uops)
        if len(uops) == 1:
            # tile-epilogue shape: one uop, each dst written exactly once;
            # src may be any affine function of the loop indices (the bias
            # add reads a per-column staging row, self ops read dst)
            grid = dsts.reshape(insn.iter_out, insn.iter_in)
            src_grid = srcs.reshape(insn.iter_out, insn.iter_in)
            base = int(grid.min())
            tile0 = st0.pending.get(base)
            distinct = np.unique(grid).size == grid.size
            ops = grid.size * s.batch * s.block_out
            if (tile0 is not None and distinct
                    and tile0.grid.shape == grid.shape
                    and (tile0.grid == grid).all()):
                op = _ALU_NAMES[insn.alu_opcode]
                if insn.use_imm:
                    return "_ap_alu_chain", (base, op, None, ops)
                # tensor-tensor: src must be readable now (eager region)
                if not self._tiles_holding(st0, src_grid):
                    return "_ap_alu_chain", (
                        base, op, _frozen(src_grid), ops)
            # vector-ALU fast path: a dense single-uop op over the *eager*
            # region (no pending lazy tile) — e.g. the chunked
            # schedule_vector_binop stream — resolves through one
            # tensor_alu Pallas call instead of the eager per-row loop
            if (distinct and not self._tiles_holding(st0, dsts)
                    and (insn.use_imm
                         or not self._tiles_holding(st0, srcs))):
                return "_alu_eager_region", (
                    _frozen(grid), _frozen(src_grid),
                    _frozen(np.unique(grid)), ops)
        # fallback: eager semantics on materialized state
        return "_ap_commit", (self._tiles_holding(
            st0, dsts if insn.use_imm else np.concatenate([dsts, srcs])),)

    # ------------------------------------------------------------------
    # steps: the work of one instruction on the gang's states
    # ------------------------------------------------------------------
    def _ap_nop(self, states: Sequence[_RunState],
                statss: Sequence[RunStats], insn: Insn) -> None:
        pass

    def _ap_commit(self, states: Sequence[_RunState],
                   statss: Sequence[RunStats], insn: Insn,
                   keys: Sequence[int] = (),
                   peers: Optional[Sequence[int]] = None) -> None:
        """Resolve the pending tiles at `keys` (and matching `peers`),
        then run the instruction with the simulator's semantics: a DMA,
        or a compute instruction the fast path does not take (eager)."""
        if keys:
            self._materialize_group(states, keys, statss, peers)
        gemm = int(isinstance(insn, GemmInsn))
        alu = int(isinstance(insn, AluInsn))
        for st, stats in zip(states, statss):
            st.sim._commit(insn, stats)
            stats.eager_gemm_insns += gemm
            stats.eager_alu_insns += alu

    def _ap_reset(self, states: Sequence[_RunState],
                  statss: Sequence[RunStats], insn: GemmInsn, base: int,
                  drop: bool, keys: Sequence[int], grid: np.ndarray,
                  indices: np.ndarray, layout: _TileLayout) -> None:
        """Open the tile at `base` in every state, first dropping a dead
        exact-match predecessor or resolving the tiles at `keys`."""
        if drop:
            for st in states:
                del st.pending[base]
        elif keys:
            self._materialize_group(states, keys, statss, None)
        for st in states:
            st.pending[base] = _PendingTile(grid=grid, indices=indices,
                                            layout=layout)

    def _ap_chunk(self, states: Sequence[_RunState],
                  statss: Sequence[RunStats], insn: GemmInsn, key: int,
                  grid: np.ndarray, src_idx: np.ndarray,
                  wgt_idx: np.ndarray, wslot: int, macs: int) -> None:
        """Add one coalesced GEMM chunk to the tile at `key` in every
        state; the weight operand is gathered once per slot `wslot`."""
        s = states[0].sim.spec
        U = src_idx.shape[1]
        for st, stats in zip(states, statss):
            sim = st.sim
            # snapshot operands NOW: virtual threading will overwrite
            # these SRAM contexts before the tile is stored
            A = sim.inp_sram[src_idx]        # (io, U, batch, block_in)
            A2 = np.ascontiguousarray(
                A.transpose(0, 2, 1, 3).reshape(grid.shape[0] * s.batch,
                                                U * s.block_in))
            W2 = st.wsnap.get(wslot)
            if W2 is None:
                Wm = sim.wgt_sram[wgt_idx]   # (ii, U, block_out, block_in)
                W2 = st.wsnap[wslot] = np.ascontiguousarray(
                    Wm.transpose(0, 2, 1, 3).reshape(
                        grid.shape[1] * s.block_out, U * s.block_in))
            st.pending[key].chunks.append(_GemmChunk(grid=grid, a=A2, w=W2))
            stats.coalesced_gemm_insns += 1
            stats.gemm_macs += macs

    def _ap_alu_chain(self, states: Sequence[_RunState],
                      statss: Sequence[RunStats], insn: AluInsn, base: int,
                      op: str, src_grid: Optional[np.ndarray],
                      ops: int) -> None:
        """Append one op to the epilogue of the tile at `base`: an
        immediate, or (with `src_grid`) a tensor operand read now."""
        s = states[0].sim.spec
        for st, stats in zip(states, statss):
            if src_grid is None:
                entry = ("imm", op, int(insn.imm))
            else:
                entry = ("tensor", op,
                         self._to_matrix(st.sim.acc_sram[src_grid], s))
            st.pending[base].alu_chain.append(entry)
            stats.alu_ops += ops
            stats.coalesced_alu_insns += 1

    def _alu_eager_region(self, states: Sequence[_RunState],
                          statss: Sequence[RunStats], insn: AluInsn,
                          grid: np.ndarray, src_grid: np.ndarray,
                          touched: np.ndarray, ops: int) -> None:
        """Run one dense ALU instruction over already-materialized
        accumulator state through the tensor_alu Pallas kernel, keeping the
        §2.5 write-through OUT mirror coherent.  Gang members row-stack
        into a single launch (the region shape is identical across the
        gang; only the data differs)."""
        import jax.numpy as jnp

        from ..kernels.tensor_alu import tensor_alu
        s = states[0].sim.spec
        clock = states[0].clock
        op = _ALU_NAMES[insn.alu_opcode]
        with clock.phase("stage"):
            dst_mats = [self._to_matrix(st.sim.acc_sram[grid], s)
                        for st in states]
            R = dst_mats[0].shape[0]
            big = dst_mats[0] if len(states) == 1 \
                else np.concatenate(dst_mats, axis=0)
            args = [jnp.asarray(big)]
            if not insn.use_imm:
                src_mats = [self._to_matrix(st.sim.acc_sram[src_grid], s)
                            for st in states]
                big_src = src_mats[0] if len(states) == 1 \
                    else np.concatenate(src_mats, axis=0)
                args.append(jnp.asarray(big_src))
        chain = ((op, int(insn.imm)),) if insn.use_imm else ((op, None),)
        with clock.phase("launch"):
            out = tensor_alu(*args, chain=chain, use_pallas=True,
                             interpret=self.interpret)
        with clock.phase("sync"):
            out = np.asarray(out, dtype=np.int32)
        io, ii = grid.shape
        for i, (st, stats) in enumerate(zip(states, statss)):
            sim = st.sim
            sim.acc_sram[grid] = self._from_matrix(
                out[i * R:(i + 1) * R], io, ii, s)
            sim.out_sram[touched] = sim.acc_sram[touched].astype(np.int8)
            stats.alu_ops += ops
            stats.coalesced_alu_insns += 1

    # ------------------------------------------------------------------
    # pending-tile resolution
    # ------------------------------------------------------------------
    def _materialize_group(self, states: Sequence[_RunState],
                           keys: Sequence[int], statss: Sequence[RunStats],
                           peers: Optional[Sequence[int]]) -> None:
        """Resolve the pending tiles at `keys` in EVERY gang state —
        plus those of the candidate `peers` whose plan matches one of
        theirs — grouping same-plan tiles into ONE (vmapped) kernel
        launch per GEMM stage instead of one launch per tile.  With a
        gang of N the launch batches N× the tiles: the per-launch
        dispatch cost is paid once for the pool (sharded batch
        dispatch)."""
        st0 = states[0]
        plan0: Dict[int, _TilePlan] = {}     # state-0 plans, keyed by base
        if peers:
            # peer match decided on state 0; the chosen KEYS are popped
            # from every state so the pending dicts stay synchronized.  A
            # peer whose plan key diverges on another state (e.g.
            # coincidentally-equal weight bytes merged there) still
            # resolves correctly — it just lands in its own launch group
            # below.
            sigs = set()
            for k in keys:
                t = st0.pending[k]
                if t.chunks:
                    plan0[k] = self._plan_tile(t)
                    sigs.add(self._plan_key(plan0[k]))
            found = []
            for base in peers:
                plan = self._plan_tile(st0.pending[base])
                if self._plan_key(plan) in sigs:
                    found.append(base)
                    plan0[base] = plan
            keys = list(keys) + found
        entries: List[Tuple[int, int, _PendingTile]] = \
            [(si, k, st.pending.pop(k))
             for si, st in enumerate(states) for k in keys]
        if not self.batch_tiles:
            for si, _, t in entries:
                self._materialize(states[si], t, statss[si])
            return
        groups: Dict[tuple, List[Tuple[int, _PendingTile, _TilePlan]]] = {}
        for si, k, t in entries:
            if t.chunks:
                plan = plan0[k] if si == 0 and k in plan0 \
                    else self._plan_tile(t)
                groups.setdefault(self._plan_key(plan), []).append(
                    (si, t, plan))
            else:
                self._materialize(states[si], t, statss[si])  # reset/ALU-only
        for grp in groups.values():
            tiles_g = [t for _, t, _ in grp]
            plans_g = [p for _, _, p in grp]
            stats_g = [statss[si] for si, _, _ in grp]
            accs = self._resolve_tiles(tiles_g, plans_g, stats_g,
                                       st0.sim.spec, st0.clock)
            for (si, tile, _), acc in zip(grp, accs):
                self._writeback(states[si], tile, acc, statss[si])

    # ------------------------------------------------------------------
    # tile resolution through the Pallas kernels
    # ------------------------------------------------------------------
    @staticmethod
    def _to_matrix(blocked: np.ndarray, spec: HardwareSpec) -> np.ndarray:
        """(io, ii, batch, block_out) -> (io*batch, ii*block_out)."""
        io, ii = blocked.shape[0], blocked.shape[1]
        return np.ascontiguousarray(
            blocked.transpose(0, 2, 1, 3).reshape(io * spec.batch,
                                                  ii * spec.block_out))

    @staticmethod
    def _from_matrix(mat: np.ndarray, io: int, ii: int,
                     spec: HardwareSpec) -> np.ndarray:
        """(io*batch, ii*block_out) -> (io, ii, batch, block_out)."""
        return (mat.reshape(io, spec.batch, ii, spec.block_out)
                .transpose(0, 2, 1, 3))

    def _materialize(self, st: _RunState, tile: _PendingTile,
                     stats: RunStats) -> None:
        s = st.sim.spec
        io, ii = tile.grid.shape
        R, C = io * s.batch, ii * s.block_out
        if tile.chunks:
            plan = self._plan_tile(tile)
            acc = self._resolve_tiles([tile], [plan], [stats], s,
                                      st.clock)[0]
        elif tile.alu_chain:
            acc = self._alu_chain(np.zeros((R, C), np.int32), tile.alu_chain,
                                  st.clock)
        else:
            acc = np.zeros((R, C), np.int32)
        self._writeback(st, tile, acc, stats)

    def _writeback(self, st: _RunState, tile: _PendingTile, acc: np.ndarray,
                   stats: RunStats) -> None:
        sim = st.sim
        s = sim.spec
        io, ii = tile.grid.shape
        sim.acc_sram[tile.grid] = self._from_matrix(acc, io, ii, s)
        # §2.5 write-through mirror: OUT narrows with a truncating cast
        sim.out_sram[tile.indices] = \
            sim.acc_sram[tile.indices].astype(np.int8)
        stats.tiles_resolved += 1

    @staticmethod
    def _requant_shift(chain: Sequence[tuple]) -> Optional[int]:
        """If the epilogue is exactly [SHR s >= 0,] MAX -128, MIN 127 it is
        the kernel's fused requant epilogue; returns s (0 when no shift)."""
        ops = list(chain)
        shift = 0
        if ops and ops[0][:2] == ("imm", "shr") and ops[0][2] >= 0:
            shift = ops[0][2]
            ops = ops[1:]
        if [o[:3] for o in ops] == [("imm", "max", -128), ("imm", "min", 127)]:
            return shift
        return None

    def _tile_shape(self, tile: _PendingTile) -> _TileShape:
        """The stream-derived half of `tile`'s plan: read from its reset
        step's layout when that holds it for these chunks and epilogue,
        else worked out (and kept there, if the slot is empty)."""
        lay = tile.layout
        sh = lay.shape if lay is not None else None
        if sh is not None and sh.n_chunks == len(tile.chunks) \
                and sh.n_alu == len(tile.alu_chain):
            return sh
        groups: List[Tuple[np.ndarray, List[int]]] = []
        index: Dict[tuple, int] = {}
        for i, c in enumerate(tile.chunks):
            key = (c.grid.shape, c.grid.tobytes())
            if key in index:
                groups[index[key]][1].append(i)
            else:
                index[key] = len(groups)
                groups.append((c.grid, [i]))
        ids = np.concatenate([g.ravel() for g, _ in groups])
        disjoint = np.unique(ids).size == ids.size
        shift = self._requant_shift(tile.alu_chain) if disjoint else None
        base = int(tile.indices[0])
        alu_sig = tuple((k, op, x) if k == "imm" else (k, op, x.shape)
                        for k, op, x in tile.alu_chain)
        gsig = []
        for g, idx in groups:
            cs = [tile.chunks[i] for i in idx]
            w_shape = (cs[0].w.shape[0], sum(c.w.shape[1] for c in cs))
            a_shape = (cs[0].a.shape[0], sum(c.a.shape[1] for c in cs))
            gsig.append((w_shape, (g.shape, (g - base).tobytes(), a_shape)))
        g0 = groups[0][0]
        pos = None
        if not (len(groups) == 1 and g0.shape == tile.grid.shape
                and (g0 == tile.grid).all()):
            flat = tile.grid.ravel()
            order = np.argsort(flat)
            pos = tuple(order[np.searchsorted(flat, g.ravel(), sorter=order)]
                        for g, _ in groups)
        sh = _TileShape(n_chunks=len(tile.chunks), n_alu=len(tile.alu_chain),
                        groups=tuple((g, tuple(idx)) for g, idx in groups),
                        shift=shift,
                        head=(shift, tile.grid.shape,
                              (tile.grid - base).tobytes(), alu_sig),
                        gsig=tuple(gsig), pos=pos)
        if lay is not None and lay.shape is None:
            lay.shape = sh
        return sh

    def _plan_tile(self, tile: _PendingTile) -> _TilePlan:
        """Stage 1+2 of tile resolution (pure bookkeeping, no kernels):
        chunks that accumulated onto the *same* grid (the reduction loop)
        concatenate along K; grids that multiplied the *same* weight tile
        — the direct-conv structure, one instruction per output row —
        row-stack into one GEMM per distinct weight tile.  The grouping
        by grid is the tile's :class:`_TileShape`; the grouping by weight
        bytes is made in every run.  The shape's ``shift`` is the requant
        shift when the ALU chain fuses into the kernel epilogue (chunk
        grids pairwise disjoint + canonical shr/clip chain), else None."""
        sh = self._tile_shape(tile)
        ch = tile.chunks
        wgroups: List[Tuple[np.ndarray,
                            List[Tuple[np.ndarray, np.ndarray, int]]]] = []
        parts: List[List[int]] = []
        # a stage is found by weight bytes; groups built from the very
        # same weight arrays (a shared gather) are found without them
        windex: Dict[tuple, int] = {}
        same: Dict[tuple, int] = {}
        for j, (g, idx) in enumerate(sh.groups):
            A = ch[idx[0]].a if len(idx) == 1 else \
                np.concatenate([ch[i].a for i in idx], axis=1)
            ids = tuple(id(ch[i].w) for i in idx)
            w = same.get(ids)
            if w is None:
                W = ch[idx[0]].w if len(idx) == 1 else \
                    np.concatenate([ch[i].w for i in idx], axis=1)
                w = windex.setdefault((W.shape, W.tobytes()), len(wgroups))
                same[ids] = w
                if w == len(wgroups):
                    wgroups.append((W, []))
                    parts.append([])
            wgroups[w][1].append((g, A, j))
            parts[w].append(j)
        return _TilePlan(wgroups, sh, tuple(tuple(p) for p in parts))

    @staticmethod
    def _pre_key(tile: _PendingTile) -> tuple:
        """O(#chunks) structural fingerprint (no data copies) used to
        pre-filter batch-peer candidates before the full plan is built."""
        base = int(tile.indices[0])
        return (tile.grid.shape, (tile.grid - base).tobytes(),
                tuple((c.grid.shape, c.a.shape, c.w.shape)
                      for c in tile.chunks),
                tuple((k, op, x) if k == "imm" else (k, op, x.shape)
                      for k, op, x in tile.alu_chain))

    @staticmethod
    def _plan_key(plan: _TilePlan) -> tuple:
        """Structural signature of a tile's resolution plan.  Tiles with
        equal keys (peer virtual-thread contexts of one op) run the same
        kernel shapes over the same relative index structure and can be
        resolved by ONE vmapped launch per GEMM stage."""
        sh = plan.shape
        return sh.head + (tuple(
            (sh.gsig[p[0]][0], tuple(sh.gsig[j][1] for j in p))
            for p in plan.partition),)

    def _resolve_tiles(self, tiles: Sequence[_PendingTile],
                       plans: Sequence[_TilePlan],
                       statss: Sequence[RunStats],
                       spec: HardwareSpec, clock: _Clock
                       ) -> List[np.ndarray]:
        """Execute structurally-identical tile plans: per GEMM stage the
        tiles' padded operands stack along a leading tile axis and run as
        ONE ``vta_gemm`` launch (``jax.vmap`` over the tile axis; plain
        call when there is a single tile) — cutting per-tile dispatch
        overhead; requant fuses into the kernel epilogue exactly as in
        the per-tile path.  Non-fused ALU chains apply to the row-stacked
        tile batch in one ``tensor_alu`` pass per chain step.  Returns
        one assembled (R, C) int32 accumulator matrix per tile.

        ``statss`` is parallel to ``tiles`` (gang members contribute
        tiles with their own RunStats); each distinct stats object counts
        every launch it participated in exactly once.  Each launch is
        one stage, launch and sync phase of `clock`."""
        import functools

        import jax
        import jax.numpy as jnp

        from ..kernels.lut_gemm.kernel import lut_gemm_pallas
        from ..kernels.vta_gemm.kernel import vta_gemm_pallas
        interpret = self.resolved_interpret

        T = len(tiles)
        wgroups0, shift = plans[0].wgroups, plans[0].shape.shift
        results_per_tile: List[List[Tuple[int, np.ndarray]]] = \
            [[] for _ in range(T)]

        def rows_of(t: int, wi: int) -> np.ndarray:
            """Tile t's operand rows of GEMM stage wi."""
            parts = plans[t].wgroups[wi][1]
            return parts[0][1] if len(parts) == 1 else \
                np.concatenate([A for _, A, _ in parts], axis=0)

        for wi in range(len(wgroups0)):
            bm = bn = bk = 128
            Ws = [plan.wgroups[wi][0] for plan in plans]
            Rg = sum(A.shape[0] for _, A, _ in wgroups0[wi][1])
            K = wgroups0[wi][1][0][1].shape[1]
            Cg = Ws[0].shape[0]
            Rp = -(-Rg // bm) * bm
            Cp = -(-Cg // bn) * bn
            Kp = -(-K // bk) * bk
            kw = dict(interpret=interpret)
            if shift is not None:
                kw.update(epilogue="requant", shift=shift)
            # per-shape kernel choice: sub-byte weights on decode-shaped
            # tiles go through the LUT-GEMM kernel (same operands, same
            # epilogue contract, bit-identical output)
            use_lut = self._lut_select(spec, Rg)

            def gemm_call(Ap, Wp):
                if use_lut:
                    return lut_gemm_pallas(Ap, Wp, bits=spec.wgt_bits, **kw)
                return vta_gemm_pallas(Ap, Wp, **kw)
            # tiles whose weight DATA is identical (gang members serving
            # the same constant weights) can row-concat into one taller
            # GEMM instead of spending a padded vmap lane each — the
            # gang's requests fill the bm-row tile the padding would have
            # wasted.  Choose by padded-row cost; ~64 rows approximates
            # the fixed per-launch dispatch cost of an extra call.
            subgroups: Dict[bytes, List[int]] = {}
            for t, W in enumerate(Ws):
                subgroups.setdefault(W.tobytes(), []).append(t)
            cost_vmap = T * Rp
            cost_concat = sum(-(-(len(g) * Rg) // bm) * bm
                              for g in subgroups.values()) \
                + 64 * (len(subgroups) - 1)
            mats: List[Optional[np.ndarray]] = [None] * T
            if len(subgroups) < T and cost_concat < cost_vmap:
                for g in subgroups.values():
                    with clock.phase("stage"):
                        Rp2 = -(-(len(g) * Rg) // bm) * bm
                        Ap = np.zeros((Rp2, Kp), np.int8)
                        for j, t in enumerate(g):
                            Ap[j * Rg:(j + 1) * Rg, :K] = rows_of(t, wi)
                        Wp = np.zeros((Kp, Cp), np.int8)
                        Wp[:K, :Cg] = Ws[g[0]].T
                        args = (jnp.asarray(Ap), jnp.asarray(Wp))
                    with clock.phase("launch"):
                        out = gemm_call(*args)
                    with clock.phase("sync"):
                        out = np.asarray(out)
                    for s_ in {id(statss[t]): statss[t] for t in g}.values():
                        s_.tile_batches += 1
                        s_.lut_launches += int(use_lut)
                    for j, t in enumerate(g):
                        mats[t] = out[j * Rg:(j + 1) * Rg,
                                      :Cg].astype(np.int32)
            else:
                with clock.phase("stage"):
                    Aps, Wps = [], []
                    for t in range(T):
                        Ap = np.zeros((Rp, Kp), np.int8)
                        Ap[:Rg, :K] = rows_of(t, wi)
                        Wp = np.zeros((Kp, Cp), np.int8)
                        Wp[:K, :Cg] = Ws[t].T
                        Aps.append(Ap)
                        Wps.append(Wp)
                    if T == 1:
                        args = (jnp.asarray(Aps[0]), jnp.asarray(Wps[0]))
                    else:
                        args = (jnp.asarray(np.stack(Aps)),
                                jnp.asarray(np.stack(Wps)))
                with clock.phase("launch"):
                    if T == 1:
                        outs = [gemm_call(*args)]
                    elif use_lut:
                        outs = jax.vmap(functools.partial(
                            lut_gemm_pallas, bits=spec.wgt_bits, **kw))(*args)
                    else:
                        outs = jax.vmap(functools.partial(vta_gemm_pallas,
                                                          **kw))(*args)
                for s_ in {id(s_): s_ for s_ in statss}.values():
                    s_.tile_batches += 1
                    s_.lut_launches += int(use_lut)
                with clock.phase("sync"):
                    outs = np.asarray(outs)
                for t in range(T):
                    mats[t] = outs[t][:Rg, :Cg].astype(np.int32)
            for t in range(T):
                mat = mats[t]
                off = 0
                for _, A, j in plans[t].wgroups[wi][1]:
                    rows = A.shape[0]
                    results_per_tile[t].append((j, mat[off:off + rows]))
                    off += rows

        accs: List[np.ndarray] = []
        for t, tile in enumerate(tiles):
            results = results_per_tile[t]
            sh = plans[t].shape
            accs.append(results[0][1] if sh.pos is None
                        else self._scatter(results, tile.grid, sh, spec))
        if shift is None and tiles[0].alu_chain:
            accs = self._alu_chain_batch(accs, [t.alu_chain for t in tiles],
                                         clock)
        return accs

    def _alu_chain_batch(self, accs: List[np.ndarray],
                         chains: Sequence[Sequence[tuple]], clock: _Clock
                         ) -> List[np.ndarray]:
        """Apply structurally-identical per-tile ALU chains to the whole
        tile batch in one pass: accumulators row-stack into a single
        matrix, tensor operands (bias rows) stack the same way, and each
        chain step becomes ONE tensor_alu launch for all tiles."""
        T = len(accs)
        if T == 1:
            return [self._alu_chain(accs[0], chains[0], clock)]
        R = accs[0].shape[0]
        with clock.phase("stage"):
            x = np.concatenate(accs, axis=0)
            chain: List[tuple] = []
            for i, entry in enumerate(chains[0]):
                if entry[0] == "imm":
                    chain.append(entry)
                else:
                    chain.append(("tensor", entry[1],
                                  np.concatenate([c[i][2] for c in chains],
                                                 axis=0)))
        out = self._alu_chain(x, chain, clock)
        return [out[t * R:(t + 1) * R] for t in range(T)]

    def _scatter(self, results: Sequence[Tuple[int, np.ndarray]],
                 grid: np.ndarray, shape: _TileShape,
                 spec: HardwareSpec) -> np.ndarray:
        """Accumulate per-group sub-grid results, ``(group, matrix)``,
        into a matrix in `grid`'s orientation at the shape's scatter
        positions (uncovered reset-region elements stay zero)."""
        io, ii = grid.shape
        acc = np.zeros((grid.size, spec.batch, spec.block_out), np.int32)
        for j, mat in results:
            g = shape.groups[j][0]
            blocked = self._from_matrix(mat, g.shape[0], g.shape[1], spec) \
                .reshape(-1, spec.batch, spec.block_out)
            np.add.at(acc, shape.pos[j], blocked)
        return self._to_matrix(
            acc.reshape(io, ii, spec.batch, spec.block_out), spec)

    def _alu_chain(self, acc, chain: Sequence[tuple],
                   clock: _Clock) -> "np.ndarray":
        """Apply the recorded epilogue; consecutive immediate ops fuse into
        one tensor_alu pass (the §2.5 resource-balance trade).  `acc` may
        be a numpy or on-device array; returns the same shape."""
        import jax.numpy as jnp

        from ..kernels.tensor_alu import tensor_alu
        with clock.phase("stage"):
            x = jnp.asarray(acc)
        i = 0
        while i < len(chain):
            if chain[i][0] == "imm":
                j = i
                ops = []
                while j < len(chain) and chain[j][0] == "imm":
                    ops.append((chain[j][1], chain[j][2]))
                    j += 1
                with clock.phase("launch"):
                    x = tensor_alu(x, chain=tuple(ops), use_pallas=True,
                                   interpret=self.interpret)
                i = j
            else:
                _, op, src = chain[i]
                with clock.phase("stage"):
                    y = jnp.asarray(src)
                with clock.phase("launch"):
                    x = tensor_alu(x, y, chain=((op, None),),
                                   use_pallas=True, interpret=self.interpret)
                i += 1
        with clock.phase("sync"):
            return np.asarray(x, dtype=np.int32)


def assert_fast_path(stats: Union[RunStats, Sequence[RunStats]],
                     allow_eager_alu: bool = False) -> None:
    """Assert that a PallasBackend run took zero eager-loop iterations.

    The eager per-uop numpy loop is the correctness net, not the product:
    schedules that are supposed to be on the kernel fast path (matmul,
    direct conv, im2col conv, 1x1-via-GEMM, dense vector ALU) must never
    hit it.  Accepts one RunStats or a sequence (e.g.
    ``CompiledProgram.last_stats``)."""
    all_stats = [stats] if isinstance(stats, RunStats) else list(stats)
    for s in all_stats:
        if s.backend != "pallas":
            continue
        if s.eager_gemm_insns:
            raise AssertionError(
                f"{s.eager_gemm_insns} GEMM instruction(s) fell back to "
                f"the eager loop ({s.coalesced_gemm_insns} coalesced)")
        if s.eager_alu_insns and not allow_eager_alu:
            raise AssertionError(
                f"{s.eager_alu_insns} ALU instruction(s) fell back to "
                f"the eager loop ({s.coalesced_alu_insns} coalesced)")


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------
_REGISTRY = {"simulator": SimulatorBackend, "pallas": PallasBackend}

BackendLike = Union[None, str, ExecutionBackend]


def resolve_backend(backend: BackendLike = None) -> ExecutionBackend:
    """None -> SimulatorBackend; a name -> registry lookup; an instance
    passes through unchanged."""
    if backend is None:
        return SimulatorBackend()
    if isinstance(backend, str):
        try:
            return _REGISTRY[backend]()
        except KeyError:
            raise ValueError(f"unknown execution backend {backend!r}; "
                             f"known: {sorted(_REGISTRY)}") from None
    return backend


# ----------------------------------------------------------------------
# differential testing across engines
# ----------------------------------------------------------------------
@dataclass
class BackendRun:
    backend: str
    stats: RunStats
    device: Device


@dataclass
class CrossBackendReport:
    runs: List[BackendRun]
    matches: bool
    mismatched_bytes: int

    def run_for(self, name: str) -> BackendRun:
        for r in self.runs:
            if r.backend == name:
                return r
        raise KeyError(name)

    def device_for(self, name: str) -> Device:
        return self.run_for(name).device

    def stats_for(self, name: str) -> RunStats:
        return self.run_for(name).stats


class CrossBackendChecker:
    """Run one encoded task-ISA stream on several backends against cloned
    devices and diff the resulting DRAM images byte-for-byte — the
    simulator-vs-hardware differential flow of the paper, with the
    simulator as the oracle for the Pallas fast path."""

    def __init__(self, backends: Sequence[BackendLike] = ("simulator",
                                                          "pallas")):
        self.backends = [resolve_backend(b) for b in backends]
        if len(self.backends) < 2:
            raise ValueError("need at least two backends to cross-check")

    def run(self, spec: HardwareSpec, device: Device, stream: np.ndarray,
            timing: Optional[TimingModel] = None) -> CrossBackendReport:
        runs = []
        for b in self.backends:
            dev = device.clone()
            runs.append(BackendRun(b.name, b.execute(spec, dev, stream,
                                                     timing=timing), dev))
        ref = runs[0].device.dram.mem
        mismatched = 0
        for r in runs[1:]:
            mismatched += int(np.count_nonzero(ref != r.device.dram.mem))
        return CrossBackendReport(runs=runs, matches=mismatched == 0,
                                  mismatched_bytes=mismatched)

    def check_runtime(self, rt, timing: Optional[TimingModel] = None,
                      adopt: str = "simulator") -> CrossBackendReport:
        """Finalize `rt`'s pending stream, run it on every backend, then
        adopt the named backend's memory image into rt.device so scheduled
        results remain readable through the usual read_* helpers."""
        stream = rt.finalize_stream()
        report = self.run(rt.spec, rt.device, stream, timing=timing)
        rt.device.copy_from(report.device_for(adopt))
        rt.stats_history.extend(r.stats for r in report.runs)
        rt.reset_stream()
        return report
