"""The program's own host spans in a profiler trace.

The program opens ``vta.*`` spans at its layer boundaries
(``repro.core.spans``): ``vta.engine.gang`` around each engine segment
with ``vta.engine.stage``, ``launch`` and ``sync`` nested in it on the
same thread, ``vta.pool.stage_inputs``, and the waits ``vta.pool.idle``
(the pool's scheduler thread has nothing to run) and ``vta.sched.hold``
(a batch is ready but the pipeline throttle holds it).  They share the
clock of the device planes that :mod:`benchkit.trace` reads, so:

* per-name totals and a span's self time (its duration less what spans
  nested in it on its own thread cover), clipped to the window;
* the program span the host was in at each moment: the innermost
  working span (of those covering the moment, the one that started
  last), else the first wait span of ``WAITS`` that covers it, else
  ``NONE``;
* the device's idle time split by that span, and each idle gap labelled
  with it in front of the benchmark's own label.

A trace without program spans has empty totals and all its idle time
under ``NONE``.  Times are in ns on the trace's clock.
"""
from __future__ import annotations

import bisect
import heapq
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

from . import trace

PREFIX = "vta."
GANG = "vta.engine.gang"
#: spans in which a thread waits, in the order that labels a moment no
#: working span covers: a held batch says more than an idle pool
WAITS = ("vta.sched.hold", "vta.pool.idle")
NONE = "(no program span)"


@dataclass(frozen=True)
class HostSpan:
    name: str
    start: float
    end: float
    line: int                   # host thread line, numbered over planes


def from_profile(pd) -> List[HostSpan]:
    """Every ``vta.*`` event on the host planes of a
    ``jax.profiler.ProfileData``."""
    out: List[HostSpan] = []
    n = 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(HostSpan(ev.name, ev.start_ns, ev.end_ns, n))
            n += 1
    return out


def _clip(s: HostSpan, lo: float, hi: float) -> float:
    return max(0.0, min(s.end, hi) - max(s.start, lo))


def totals(spans: Sequence[HostSpan], lo: float, hi: float
           ) -> Dict[str, float]:
    """Summed duration of each span name inside [lo, hi]."""
    out: Dict[str, float] = defaultdict(float)
    for s in spans:
        if s.end > lo and s.start < hi:
            out[s.name] += _clip(s, lo, hi)
    return dict(out)


def self_ns(spans: Sequence[HostSpan], name: str, lo: float, hi: float
            ) -> float:
    """Summed self time of the spans named `name` inside [lo, hi]: each
    one's duration less the union of the spans nested in it on its
    line."""
    by_line: Dict[int, List[HostSpan]] = defaultdict(list)
    for s in spans:
        by_line[s.line].append(s)
    total = 0.0
    for line_spans in by_line.values():
        line_spans.sort(key=lambda s: (s.start, -s.end))
        starts = [s.start for s in line_spans]
        for i, s in enumerate(line_spans):
            if s.name != name or not (s.end > lo and s.start < hi):
                continue
            j = bisect.bisect_right(starts, s.end)
            inner = trace.merge(
                (max(c.start, lo), min(c.end, hi))
                for c in line_spans[i + 1:j] if c.end <= s.end)
            total += _clip(s, lo, hi) - sum(e - b for b, e in inner)
    return total


def segments(spans: Sequence[HostSpan], lo: float, hi: float
             ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into (start, end, label) pieces, the label being the
    program span the host was in (see the module's docstring)."""
    live = sorted((s for s in spans if s.end > lo and s.start < hi
                   and s.end > s.start), key=lambda s: s.start)
    points = sorted({lo, hi} | {min(max(t, lo), hi) for s in live
                                for t in (s.start, s.end)})
    working: List[Tuple[float, float, str]] = []    # (-start, end, name)
    waiting: Dict[str, List[float]] = {w: [] for w in WAITS}  # ends
    out: List[Tuple[float, float, str]] = []
    k = 0
    for a, b in zip(points, points[1:]):
        while k < len(live) and live[k].start <= a:
            s = live[k]
            if s.name in waiting:
                heapq.heappush(waiting[s.name], s.end)
            else:
                heapq.heappush(working, (-s.start, s.end, s.name))
            k += 1
        while working and working[0][1] <= a:
            heapq.heappop(working)
        label = working[0][2] if working else NONE
        if not working:
            for w in WAITS:
                ends = waiting[w]
                while ends and ends[0] <= a:
                    heapq.heappop(ends)
                if ends:
                    label = w
                    break
        if out and out[-1][2] == label and out[-1][1] == a:
            out[-1] = (out[-1][0], b, label)
        else:
            out.append((a, b, label))
    return out


def label_at(segs: Sequence[Tuple[float, float, str]], t: float) -> str:
    i = bisect.bisect_right([s[0] for s in segs], t) - 1
    return segs[i][2] if 0 <= i and t <= segs[i][1] else NONE


def idle_by_span(segs: Sequence[Tuple[float, float, str]],
                 idle: Sequence[Tuple[float, float]]) -> Dict[str, float]:
    """ns of the sorted, disjoint `idle` intervals under each label."""
    out: Dict[str, float] = defaultdict(float)
    i = 0
    for a, b in idle:
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            s0, s1, name = segs[j]
            out[name] += min(b, s1) - max(a, s0)
            j += 1
    return dict(out)


def idle_gaps(ops: Sequence[trace.Op], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The idle intervals of one chip inside [lo, hi]."""
    return trace.gaps(trace.merge((max(o.start, lo), min(o.end, hi))
                                  for o in ops
                                  if o.end > lo and o.start < hi), lo, hi)


def gap_labels(gaps: Sequence[Tuple[float, float]],
               segs: Sequence[Tuple[float, float, str]],
               bench_spans: Sequence[trace.Span], top: int = trace.TOP
               ) -> List[Tuple[str, float]]:
    """The `top` longest gaps as (label, s): the program span at the
    gap's middle, then the benchmark's own label."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return [(label_at(segs, 0.5 * (a + b)) + "+"
             + trace.label((a, b), bench_spans), (b - a) * 1e-9)
            for a, b in longest]
