"""A CPU rehearsal of whole runs: the closed-loop clients, the open-loop
arrivals and the metric arithmetic, through the real DevicePool and
Scheduler with the Pallas kernels in interpret mode, on a tiny stand-in
conv.  The same runs with the timed path broken underneath must come out
not correct, and the control (the reference one precision lower) must
fail the comparison."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import run as run_cli  # noqa: E402
from benchkit import cellrun, layout, loadgen  # noqa: E402

BENCH = layout.load_benchmark()
CELL = BENCH["workloads"][0]
PEAKS = layout.peaks("TPU v5 lite")


def tiny_cfg() -> dict:
    cfg = layout.config(BENCH, CELL["config"])
    cfg["layers"] = {"T3": dict(h=8, ic=16, oc=16, k=3, stride=1, shift=9),
                     "T1": dict(h=8, ic=16, oc=32, k=1, stride=2, shift=8)}
    cfg["calls"] = [{"layer": "T3", "relu": True},
                    {"layer": "T1", "relu": False}]
    return cfg


MIX = {"loop": "closed", "clients": 2, "pool_size": 2,
       "sched": {"gang_width": 2}, "input_sets": 4}


class _Dev:
    platform, device_kind = "cpu", "cpu"


def _run(trace=False, mix=MIX, seconds=0.6, seed=3_000_000_019):
    return cellrun.run(CELL["name"], tiny_cfg(), dict(mix), seed, seconds,
                       trace, time.perf_counter(), peaks=PEAKS)


def test_closed_loop_traced_run_and_result_line():
    rec = _run(trace=True)
    assert rec.correct and rec.failed == 0 and rec.compiles_in_window == 0
    assert rec.checks["wrong_values"] == {"value": 0, "limit": 0}
    assert len(rec.finished) >= 2 and rec.img_per_s > 0
    line = run_cli.result_line(rec, BENCH, CELL, True, [_Dev()])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    json.loads(json.dumps(line))
    # no device trace on the CPU: device metrics are left out, not 0
    assert "device.idle" not in line["metrics"]
    assert "vta_kernels_roofline" not in line["metrics"]
    m = line["metrics"]
    assert m["serve.gang_mean"]["value"] > 1.0      # gangs of 2 formed
    assert m["engine.launches_per_img"]["value"] > 0
    assert m["jit.compile_s"]["unit"] == "s"
    untraced = run_cli.result_line(rec, BENCH, CELL, False, [_Dev()])
    assert list(untraced) == ["correct", "attempted", "failed", "metrics",
                              "device", "checks"]
    assert set(untraced["metrics"]) == {"img_per_s", "img_p75_ms",
                                        "setup_s"}


@pytest.mark.parametrize("mix", [
    dict(MIX, loop="open", arrival="bursty", rate_per_s=20.0, burst=2,
         max_in_flight=8),
    dict(MIX, loop="open", arrival="poisson", rate_per_s=20.0,
         max_in_flight=8, sched=None),
    dict(MIX, clients=1, pool_size=1, sched=None),
], ids=["open-bursty-sched", "open-poisson-pool", "closed-b1-pool"])
def test_other_loops_and_no_scheduler(mix):
    rec = _run(mix=mix)
    assert rec.correct and rec.requests and rec.failed == 0
    assert all(r.latency_s >= r.done - r.start for r in rec.finished)
    assert loadgen.lateness_s(rec.requests) >= 0


def test_arrival_traces_are_seeded_and_keep_their_rate():
    a = loadgen.arrivals({"arrival": "poisson", "rate_per_s": 50.0}, 20.0,
                         np.random.default_rng(1))
    b = loadgen.arrivals({"arrival": "poisson", "rate_per_s": 50.0}, 20.0,
                         np.random.default_rng(1))
    assert np.array_equal(a, b) and a.max() < 20.0
    assert abs(len(a) / 20.0 - 50.0) < 5.0
    c = loadgen.arrivals({"arrival": "bursty", "rate_per_s": 40.0,
                          "burst": 4}, 20.0, np.random.default_rng(2))
    assert abs(len(c) / 20.0 - 40.0) < 8.0
    assert np.all(np.diff(c[:4]) < 1e-3)          # one burst


def test_metric_arithmetic_on_known_stats():
    from repro.core.simulator import RunStats

    def st(gang, batches, wall):
        return RunStats(gang_size=gang, tile_batches=batches,
                        wall_time_s=wall)
    cfg = tiny_cfg()
    model = layout.model(cfg["model"])
    work = model.call_work(cfg)
    r1 = loadgen.Request(client=0, seq=0, set_idx=0, due=0.0, start=0.0,
                         done=1.0, call_done=[0.5, 1.0],
                         stats=[[st(2, 4, 0.2)], [st(2, 6, 0.4)]])
    r2 = loadgen.Request(client=1, seq=0, set_idx=1, due=0.0, start=0.0,
                         done=3.0, call_done=[2.0, 3.0],
                         stats=[[st(1, 3, 0.1)], [st(1, 1, 0.1)]])
    from benchkit.trace import TraceSummary
    rec = cellrun.RunRecord(
        cell="x", cfg=cfg, mix=MIX, seconds=2.0,
        call_names=model.call_names(cfg), work=work, requests=[r1, r2],
        t_start=0.0, t_end=2.0, t_drained=3.0, setup={"setup_s": 7.0,
                                                       "compile_s": 0.5},
        peaks=PEAKS, trace=TraceSummary(window_ns=4e9, busy_ns=1e9,
                                        n_ops=10,
                                        kernel_ns={"vta_gemm": 2e6}))

    def val(name):
        return layout.metric_reader(name).read(rec)
    assert val("img_per_s") == pytest.approx(3 / 2 / 2.0)   # 3 calls in
    assert val("img_p75_ms") == pytest.approx(1000.0)       # r1 only
    assert val("setup_s") == 7.0
    assert val("jit.compile_s") == 0.5
    assert val("serve.gang_mean") == pytest.approx(1.5)
    assert val("engine.launches_per_img") == pytest.approx(((2 + 3) + 4) / 2)
    assert val("engine.wall_ms_per_img") == pytest.approx(
        1e3 * ((0.1 + 0.2) + 0.2) / 2)
    assert val("device.idle") == pytest.approx(75.0)
    ops = 2 * sum(w.ops for w in work)
    nbytes = sum(w.weight_bytes / 2 + w.in_bytes + w.out_bytes for w in work) \
        + sum(w.weight_bytes + w.in_bytes + w.out_bytes for w in work)
    least = max(ops / PEAKS["int8_ops_per_s"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    assert val("vta_kernels_roofline") == pytest.approx(100 * least / 2e-3)
    assert val("mfu.img") == pytest.approx(
        100 * sum(w.ops for w in work) * 0.75 / PEAKS["int8_ops_per_s"])


# ---------------------------------------------------------------------
# faults planted under the timed path, and the control
# ---------------------------------------------------------------------
def test_an_altered_answer_is_not_correct(monkeypatch):
    from repro.core.program import CompiledProgram

    real = CompiledProgram.read_outputs

    def altered(self, device=None):
        out = np.array(real(self, device=device))
        out.flat[7] ^= 1
        return out
    monkeypatch.setattr(CompiledProgram, "read_outputs", altered)
    rec = _run()
    assert not rec.correct and rec.checks["wrong_values"]["value"] > 0


def test_half_of_a_gang_left_out_is_not_correct(monkeypatch):
    from repro.core.backend import PallasBackend

    real = PallasBackend.execute_gang

    def half(self, spec, devices, stream, **kw):
        keep = max(1, len(devices) // 2)
        stats = real(self, spec, list(devices)[:keep], stream, **kw)
        return stats + stats[:1] * (len(devices) - keep)
    monkeypatch.setattr(PallasBackend, "execute_gang", half)
    rec = _run(seconds=1.0)
    assert not rec.correct and rec.checks["wrong_values"]["value"] > 0


def test_a_failed_image_is_not_correct(monkeypatch):
    from repro.core.sched import QueueFull, Scheduler

    real = Scheduler.submit
    calls = [0]

    def lossy(self, *a, **kw):
        calls[0] += 1
        if calls[0] == 3:
            raise QueueFull("planted typed loss")
        return real(self, *a, **kw)
    monkeypatch.setattr(Scheduler, "submit", lossy)
    rec = _run()
    assert rec.finished and rec.checks["wrong_values"]["value"] == 0
    assert rec.failed == 1 and not rec.correct
    assert rec.checks["failed_images"] == {"value": 1, "limit": 0}


def test_the_control_fails_the_comparison():
    import control

    cfg = tiny_cfg()
    model = layout.model(cfg["model"])
    data = model.make_data(cfg, 3_000_000_021, 2)
    for c in control.readings(cfg, model, data, 2):
        assert not cellrun.passes(c)
        assert c["wrong_values"]["value"] > 0 and c["max_abs_err"]["value"] > 0
    # the exact reference against itself passes: the limit of 0 holds
    assert all(cellrun.passes(c) for c in
               control.readings(cfg, model, data, 2, wgt_bits=8))
