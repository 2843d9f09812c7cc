"""The one traffic generator.  A mix is a data file,
``bench/traffic/<mix>.json``:

    {"loop": "closed", "clients": 4,      closed loop: each client sends
                                          its next request when the last
                                          one returned
     "pool_size": 4,                      DevicePool slots
     "sched": {},                         SchedConfig fields ({} = product
                                          defaults); null = no Scheduler,
                                          submit to the pool directly
     "input_sets": 8}                     distinct seeded requests, reused
                                          in a seeded order

    {"loop": "open", "arrival": "poisson" | "bursty",
     "rate_per_s": 1.5, "burst": 4,       open loop: requests arrive on a
     "max_in_flight": 64, ...}            seeded schedule, whether or not
                                          the system kept up

Latency of an open-loop request runs from its SCHEDULED arrival, so a
stall counts against every request it delays; how late the generator
itself ran is reported beside it.  Every seed gives the same set of
request sizes; the seed changes their order and, in an open loop, the
arrival times.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np


# ----------------------------------------------------------------------
# arrival traces (seeded, offsets in seconds from the window's start)
# ----------------------------------------------------------------------
def poisson_trace(rate_rps: float, n: int, rng: np.random.Generator
                  ) -> np.ndarray:
    """Memoryless arrivals: exponential inter-arrival gaps at
    `rate_rps` mean offered load."""
    return np.cumsum(rng.exponential(1.0 / rate_rps, size=n))


def bursty_trace(rate_rps: float, n: int, rng: np.random.Generator,
                 burst: int = 4) -> np.ndarray:
    """Same mean offered load, arriving in bursts of `burst`
    back-to-back requests separated by exponential gaps."""
    gaps = rng.exponential(burst / rate_rps,
                           size=(n + burst - 1) // burst)
    starts = np.cumsum(gaps)
    t = np.repeat(starts, burst)[:n]
    # 50us intra-burst spacing: near-simultaneous, not identical
    return t + np.tile(np.arange(burst) * 50e-6, (len(starts),))[:n]


TRACES = {"poisson": poisson_trace, "bursty": bursty_trace}


def arrivals(mix: Dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Offsets of the open-loop arrivals that fall inside the window."""
    rate = float(mix["rate_per_s"])
    n = int(rate * seconds * 2) + 16
    kw = {"burst": int(mix["burst"])} if mix["arrival"] == "bursty" else {}
    off = TRACES[mix["arrival"]](rate, n, rng, **kw)
    return off[off < seconds]


# ----------------------------------------------------------------------
# requests
# ----------------------------------------------------------------------
@dataclass
class Request:
    """One request: a sequence of calls, each submitted after the
    previous one returned.  Times are ``time.perf_counter`` seconds."""
    client: int
    seq: int
    set_idx: int
    due: float                       # scheduled arrival (closed: start)
    start: float = 0.0               # first call submitted
    done: Optional[float] = None     # last call returned
    call_done: List[float] = field(default_factory=list)
    stats: List[list] = field(default_factory=list)   # per call: RunStats
    outputs: List[Any] = field(default_factory=list)
    error: Optional[BaseException] = None

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.done is None else self.done - self.due


def set_order(n_sets: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(n_sets)


def closed_loop(serve: Callable[[Request], None], clients: int,
                t_start: float, t_end: float, order: np.ndarray
                ) -> List[Request]:
    """`clients` threads; each starts a new request whenever its last
    one finished, until `t_end`.  Client c's j-th request uses input
    set ``order[(j * clients + c) % len(order)]``."""
    out: List[List[Request]] = [[] for _ in range(clients)]

    def client(c: int) -> None:
        j = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            r = Request(client=c, seq=j, due=now,
                        set_idx=int(order[(j * clients + c) % len(order)]))
            out[c].append(r)
            serve(r)
            j += 1

    _wait_until(t_start)
    threads = [threading.Thread(target=client, args=(c,),
                                name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [r for rs in out for r in rs]


def open_loop(serve: Callable[[Request], None], offsets: np.ndarray,
              t_start: float, order: np.ndarray, max_in_flight: int
              ) -> List[Request]:
    """Replay the arrival offsets: each request is handed to a worker at
    its scheduled time; its latency counts from that time."""
    reqs = [Request(client=0, seq=i, due=t_start + float(off),
                    set_idx=int(order[i % len(order)]))
            for i, off in enumerate(offsets)]
    with ThreadPoolExecutor(max_workers=max_in_flight,
                            thread_name_prefix="bench-open") as ex:
        for r in reqs:
            _wait_until(r.due)
            ex.submit(serve, r)
    return reqs


def lateness_s(reqs: List[Request]) -> float:
    """How late the generator ran: the largest gap between a request's
    scheduled arrival and its first submit."""
    late = [r.start - r.due for r in reqs if r.start]
    return max(late) if late else 0.0


def _wait_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
