"""From a profiler trace to the numbers the per-layer metrics read.

The traced run records one ``jax.profiler`` session around its window
and reduces it here, in memory (nothing is written to disk):

* the device's busy time: the union of the intervals in which an
  operation ran on each chip (the "XLA Ops" line of every
  ``/device:TPU:<n>`` plane), clipped to the window, averaged over the
  chips the cell uses;
* each kernel's summed device time, by name: an op belongs to a kernel
  when the kernel's name appears in the op's name or in the name of the
  XLA module it runs in (``jit_vta_gemm_pallas`` and the like);
* the idle gaps between device ops, each labelled with the benchmark's
  own host spans (``bench.*`` TraceAnnotations) that cover its middle.

The window itself is the ``bench.window`` host span.  Times are in ns on
the trace's clock.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the engine's Pallas kernels (repro/kernels/*)
KERNELS = ("vta_gemm", "tensor_alu", "lut_gemm")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10


@dataclass(frozen=True)
class Op:
    name: str
    start: float
    end: float
    module: str = ""


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float                       # mean over chips
    n_ops: int
    kernel_ns: Dict[str, float] = field(default_factory=dict)
    op_ns: Dict[str, float] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)  # (label, s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def breakdown(self) -> Dict[str, list]:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals as sorted, disjoint (start, end) pairs."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(merged: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi] outside the merged busy intervals."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def kernel_of(op: Op) -> Optional[str]:
    for k in KERNELS:
        if k in op.name or k in op.module:
            return k
    return None


def label(gap: Tuple[float, float], spans: Sequence[Span]) -> str:
    """The distinct benchmark spans covering the gap's middle."""
    mid = 0.5 * (gap[0] + gap[1])
    names = sorted({s.name for s in spans
                    if s.name != WINDOW_SPAN and s.start <= mid <= s.end})
    return "+".join(names) if names else "(no bench span)"


def op_key(op: Op) -> str:
    """Breakdown name of an op: its module (without the compile id), its
    op name (without the instance number) and, where the trace gives the
    op as HLO text (``%name.1 = s8[2,896,512]{...} custom-call(...)``),
    its result type."""
    mod = re.sub(r"\(\d+\)$", "", op.module)
    m = re.match(r"%?([\w.-]+?)(?:\.\d+)? = (\w+\[[\d,]*\])", op.name)
    name = (f"{m.group(1)} {m.group(2)}" if m
            else re.sub(r"\.\d+$", "", op.name))
    return f"{mod}/{name}" if mod else name


def summarize(ops_per_chip: Sequence[Sequence[Op]], spans: Sequence[Span],
              lo: float, hi: float) -> TraceSummary:
    busy, n_ops = [], 0
    kernel_ns: Dict[str, float] = defaultdict(float)
    op_ns: Dict[str, float] = defaultdict(float)
    all_gaps: List[Tuple[float, float]] = []
    for ops in ops_per_chip:
        clipped = [(max(o.start, lo), min(o.end, hi), o) for o in ops
                   if o.end > lo and o.start < hi]
        n_ops += len(clipped)
        for s, e, o in clipped:
            k = kernel_of(o)
            if k is not None:
                kernel_ns[k] += e - s
            op_ns[op_key(o)] += e - s
        merged = merge((s, e) for s, e, _ in clipped)
        busy.append(sum(e - s for s, e in merged))
        all_gaps.extend(gaps(merged, lo, hi))
    nchips = max(1, len(ops_per_chip))
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return TraceSummary(
        window_ns=hi - lo, busy_ns=sum(busy) / nchips, n_ops=n_ops,
        kernel_ns=dict(kernel_ns), op_ns=dict(op_ns),
        gaps=[(label(g, spans), (g[1] - g[0]) * 1e-9)
              for g in all_gaps[:TOP]])


# ----------------------------------------------------------------------
# reading jax.profiler's XSpace
# ----------------------------------------------------------------------
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def from_profile(pd, chips: int) -> Tuple[List[List[Op]], List[Span]]:
    """Device ops of the first `chips` TPU planes, and every
    ``bench.*`` host span, from a ``jax.profiler.ProfileData``."""
    planes = []
    spans: List[Span] = []
    for plane in pd.planes:
        m = _TPU_PLANE.match(plane.name)
        if m:
            planes.append((int(m.group(1)), plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns, ev.end_ns))
    ops_per_chip = []
    for _, plane in sorted(planes, key=lambda p: p[0])[:chips]:
        lines = {line.name: line for line in plane.lines}
        mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                      for ev in (lines["XLA Modules"].events
                                 if "XLA Modules" in lines else ()))
        ops = []
        if "XLA Ops" in lines:
            evs = sorted(((ev.start_ns, ev.end_ns, ev.name)
                          for ev in lines["XLA Ops"].events))
            mi = 0
            for s, e, name in evs:
                while mi < len(mods) and mods[mi][1] < s:
                    mi += 1
                mod = (mods[mi][2] if mi < len(mods) and mods[mi][0] <= s
                       else "")
                ops.append(Op(name, s, e, mod))
        ops_per_chip.append(ops)
    return ops_per_chip, spans


def window_of(spans: Sequence[Span]) -> Optional[Tuple[float, float]]:
    w = [s for s in spans if s.name == WINDOW_SPAN]
    return (w[0].start, w[0].end) if w else None
