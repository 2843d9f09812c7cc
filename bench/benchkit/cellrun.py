"""One run of one cell: set-up, the measured window, the check.

Set-up makes the data from the seed, compiles the configuration's
programs into one co-staged image (``compile_multi``), builds the
traffic mix's ``DevicePool`` and ``Scheduler``, and warms every gang
width the window can form.  The window then drives the mix for
``seconds`` through the scheduler's normal ``submit`` path; requests
still in flight at its close are finished (and checked) but not counted
in it.  With ``trace`` the window runs inside one profiler session,
reduced in memory by :mod:`benchkit.trace`.  After the window the pool
is closed and every finished request's outputs are compared with the
model's plain reference by :func:`compare`; a request that failed (a
typed loss or an error) makes the run not correct.
"""
from __future__ import annotations

import gc
import sys
import threading
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from . import layout, loadgen
from .trace import TraceSummary, WINDOW_SPAN, from_profile, summarize, \
    window_of

#: per-call bound: a wedged pool fails the request, never hangs the run
CALL_TIMEOUT_S = 120.0

#: the numbers compared in every run and their limits: the comparison
#: with the reference is exact, and no request may fail
LIMITS = {"wrong_values": 0, "max_abs_err": 0, "failed_images": 0}


@dataclass
class RunRecord:
    """Everything a metric reader may read about one run."""
    cell: str
    cfg: Dict
    mix: Dict
    seconds: float
    call_names: List[str]
    work: list                        # per call: model.Work
    requests: List[loadgen.Request]
    t_start: float
    t_end: float
    t_drained: float
    setup: Dict[str, float]
    peaks: Optional[Dict] = None
    trace: Optional[TraceSummary] = None
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    chips: int = 1
    checks: Dict[str, Dict[str, float]] = field(default_factory=dict)
    correct: bool = False

    @property
    def finished(self) -> List[loadgen.Request]:
        return [r for r in self.requests
                if r.error is None and r.done is not None]

    @property
    def in_window(self) -> List[loadgen.Request]:
        return [r for r in self.finished if r.done <= self.t_end]

    @property
    def failed(self) -> int:
        return sum(1 for r in self.requests if r.error is not None)

    @property
    def img_per_s(self) -> float:
        """Images' worth of calls finished inside the window, over its
        length: whole-call granularity, not whole-image."""
        calls = sum(1 for r in self.requests for t in r.call_done
                    if t <= self.t_end)
        return calls / len(self.call_names) / self.seconds


class _CompileCounter:
    """Counts, while armed, XLA compiles (``backend``), programs loaded
    from the persistent cache instead (``cache_loads``) and jaxpr traces
    (``traces``: Python-side tracing of a jitted or vmapped function,
    which the engine repeats for each vmapped launch); ``events`` counts
    every event JAX reports by name, and ``gc`` the collector's runs by
    generation."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.armed = False
        self.counts = {"backend": 0, "cache_loads": 0, "traces": 0}
        self.events: Counter = Counter()
        self._gc0 = [0, 0, 0]
        self.gc = [0, 0, 0]
        self._events = {dispatch.BACKEND_COMPILE_EVENT: "backend",
                        dispatch.JAXPR_TRACE_EVENT: "traces",
                        "/jax/compilation_cache/cache_retrieval_time_sec":
                            "cache_loads"}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def arm(self, on: bool) -> None:
        runs = [g["collections"] for g in gc.get_stats()]
        if on:
            self._gc0 = runs
        else:
            self.gc = [b - a for a, b in zip(self._gc0, runs)]
        self.armed = on

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed:
            self.events[event] += 1
            if event in self._events:
                self.counts[self._events[event]] += 1

    def _on_event(self, event: str, **kw) -> None:
        if self.armed:
            self.events[event] += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on_event)


class _StallWatch:
    """A thread that, inside its ``with`` block, wakes every 5 ms and
    keeps each wake-up that came more than 50 ms late: the whole process
    stood still then (a gc run, a call holding the GIL, or the host)."""

    def __init__(self, tick_s: float = 0.005, late_s: float = 0.05):
        self.tick_s, self.late_s = tick_s, late_s
        self.stalls: List[float] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch,
                                        name="bench-stall-watch",
                                        daemon=True)

    def _watch(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(self.tick_s):
            now = time.perf_counter()
            if now - last - self.tick_s > self.late_s:
                self.stalls.append(now - last - self.tick_s)
            last = now

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def summary(self) -> str:
        s = sorted(self.stalls, reverse=True)
        return (f"{len(s)} over {self.late_s * 1e3:.0f} ms, total "
                f"{sum(s) * 1e3:.1f} ms, longest "
                + (", ".join(f"{x * 1e3:.1f}" for x in s[:5]) or "-") + " ms")


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(cell: str, cfg: Dict, mix: Dict, seed: int, seconds: float,
        trace: bool, t0: float, peaks: Optional[Dict] = None,
        chips: int = 1, engine: Any = None,
        setup: Optional[Dict[str, float]] = None,
        bench_dir=layout.BENCH_DIR) -> RunRecord:
    """Set up, measure and check one cell.  `t0` is the process's start
    on the host clock (set-up runs from it to the window's start);
    `setup` holds the seconds of the phases the caller ran since then."""
    setup = dict(setup or {})
    t = time.perf_counter()
    from repro.core.backend import PallasBackend
    from repro.core.program import compile_multi
    from repro.core.sched import SchedConfig, Scheduler
    from repro.core.serve import DevicePool

    model = layout.model(cfg["model"], bench_dir)
    names = model.call_names(cfg)
    n_sets = int(mix["input_sets"])
    counter = _CompileCounter()
    setup["imports_s"] = time.perf_counter() - t

    def phase(name: str, fn: Callable[[], Any]) -> Any:
        t = time.perf_counter()
        with _annotate(f"bench.setup.{name}"):
            out = fn()
        setup[f"{name}_s"] = time.perf_counter() - t
        return out

    data = phase("data", lambda: model.make_data(cfg, seed, n_sets))
    progs = phase("build", lambda: model.build(cfg, data))
    compiled = phase("compile", lambda: compile_multi(progs))
    engine = engine if engine is not None else PallasBackend()
    pool = DevicePool(compiled, size=int(mix["pool_size"]), backend=engine)
    sched = None
    try:
        if mix.get("sched") is not None:
            sched = phase("sched", lambda: Scheduler(
                pool, SchedConfig(**mix["sched"])))
            widths = list(sched.gang_widths)
        else:
            widths = [min(len(pool), int(mix.get("clients", 1)))] * len(progs)
        sample = model.request(data, 0)
        phase("warm", lambda: _warm(pool, sample, widths))
        order = loadgen.set_order(n_sets, _rng(seed, 1))

        def serve(r: loadgen.Request) -> None:
            inputs = model.request(data, r.set_idx)
            r.start = time.perf_counter()
            try:
                for i, feed in enumerate(inputs):
                    with _annotate(f"bench.call.{names[i]}"):
                        if sched is not None:
                            f = sched.submit(program=i, **feed)
                            out = f.wait(timeout=CALL_TIMEOUT_S)
                            pf = f.pool_future
                        else:
                            pf = pool.submit_to(i, **feed)
                            out = pf.wait(timeout=CALL_TIMEOUT_S)
                    r.call_done.append(time.perf_counter())
                    r.outputs.append(out)
                    r.stats.append(list(pf.stats))
                r.done = r.call_done[-1]
            except Exception as e:     # a typed loss or an error fails it
                r.error = e

        session = _start_trace() if trace else None
        t_start = time.perf_counter()
        setup["other_s"] = t_start - t0 - sum(setup.values())
        setup["setup_s"] = t_start - t0
        t_end = t_start + seconds
        counter.arm(True)
        with _annotate(WINDOW_SPAN), _StallWatch() as watch:
            if mix["loop"] == "closed":
                reqs = loadgen.closed_loop(serve, int(mix["clients"]),
                                           t_start, t_end, order)
            else:
                offs = loadgen.arrivals(mix, seconds, _rng(seed, 2))
                reqs = loadgen.open_loop(serve, offs, t_start, order,
                                         int(mix.get("max_in_flight", 64)))
        t_drained = time.perf_counter()
        counter.arm(False)
        summary = _stop_trace(session, chips) if trace else None
        log(f"set-up {setup['setup_s']:.3f} s (" + ", ".join(
            f"{k} {v:.3f}" for k, v in setup.items() if k != "setup_s")
            + f"); gang widths {widths}")
        log("compiles in window: {backend} (persistent-cache loads "
            "{cache_loads}, jaxpr traces {traces})".format(**counter.counts))
        log("jax events in window: " + ", ".join(
            f"{k} {v}" for k, v in counter.events.most_common())
            + f"; gc runs by generation {counter.gc}")
        log(f"host stalls in window: {watch.summary()}")
        log("image latency in window, ms, by finish: " + " ".join(
            f"{(r.done - r.due) * 1e3:.0f}"
            for r in sorted(reqs, key=lambda r: r.done or 0.0)
            if r.done is not None and r.done <= t_end))
        log("calls done by 10 s of window: " + str(np.bincount(np.asarray(
            [(t - t_start) // 10 for r in reqs for t in r.call_done
             if t <= t_end], dtype=int),
            minlength=int(np.ceil(seconds / 10))).tolist()))
        if mix["loop"] == "open":
            log(f"generator lateness: {loadgen.lateness_s(reqs):.6f} s")
        errors = [r.error for r in reqs if r.error is not None]
        if errors:
            log(f"failed images: {len(errors)}; first: {errors[0]!r}")
        mem = _memory_peak(chips)
    finally:
        counter.close()
        if sched is not None:
            sched.close()
        pool.close()

    rec = RunRecord(cell=cell, cfg=cfg, mix=mix, seconds=seconds,
                    call_names=names, work=model.call_work(cfg),
                    requests=reqs, t_start=t_start, t_end=t_end,
                    t_drained=t_drained, setup=setup, peaks=peaks,
                    trace=summary,
                    compiles_in_window=counter.counts["backend"]
                    + counter.counts["cache_loads"],
                    memory_peak_bytes=mem, chips=chips)
    check(rec, model, data)
    return rec


def _rng(seed: int, stream: int) -> np.random.Generator:
    s = int(seed) % (1 << 64)
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, stream])


def _warm(pool, sample: List[Dict[str, np.ndarray]],
          widths: List[int]) -> None:
    """Run every program once at every gang width 1..its width: each
    width is its own set of kernel shapes.  ``submit_batch`` on an idle
    pool lands a batch on distinct slots as one gang."""
    for i, feed in enumerate(sample):
        for w in range(1, widths[i] + 1):
            for f in pool.submit_batch(i, [feed] * w):
                f.wait(timeout=CALL_TIMEOUT_S * 5)


def _start_trace():
    import jax
    from jax._src.lib import _profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host spans and JAX's own events
    opts.host_tracer_level = 2
    return _profiler.ProfilerSession(opts)


def _stop_trace(session, chips: int) -> Optional[TraceSummary]:
    import jax

    pd = jax.profiler.ProfileData.from_serialized_xspace(session.stop())
    ops, spans = from_profile(pd, chips)
    win = window_of(spans)
    if win is None:
        return None
    return summarize(ops, spans, *win)


def _memory_peak(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def compare(answers: Iterable[Tuple[List, List[np.ndarray]]],
            failed: int = 0) -> Dict[str, Dict[str, float]]:
    """The numbers a run is judged by, each beside its limit: over
    (outputs, reference outputs) pairs, the values that differ and the
    largest difference, and the requests that failed."""
    wrong = 0
    max_err = 0
    for outs, refs in answers:
        for got, want in zip(outs, refs):
            got = np.asarray(got)
            if got.shape != want.shape:
                wrong += want.size
                max_err = max(max_err, 255)
                continue
            d = np.abs(got.astype(np.int16) - want.astype(np.int16))
            wrong += int(np.count_nonzero(d))
            max_err = max(max_err, int(d.max()))
    values = {"wrong_values": wrong, "max_abs_err": max_err,
              "failed_images": failed}
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in values.items()}


def passes(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def check(rec: RunRecord, model, data: Dict) -> None:
    """Compare every finished request's outputs, as the serving plane
    returned them, with the plain reference; a run with no finished
    request, or with a failed one, is not correct."""
    fin = rec.finished
    refs = {s: model.reference(rec.cfg, data, s)
            for s in sorted({r.set_idx for r in fin})}
    log(f"images checked: {len(fin)} of {len(rec.requests)} started")
    rec.checks = compare(((r.outputs, refs[r.set_idx]) for r in fin),
                         rec.failed)
    rec.correct = bool(fin) and passes(rec.checks)


def metric_values(rec: RunRecord, entries: List[Dict],
                  bench_dir=layout.BENCH_DIR) -> Dict[str, Dict]:
    """Read each metric with its reader; a reader with nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in entries:
        v = layout.metric_reader(m["name"], bench_dir).read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
