"""The JoyAI MoE configuration at a tiny size on the CPU: its model
module's data, programs, requests and plain reference agree through the
compiled path, its counted work follows the published widths, and a
whole run through the harness reads correct with the expert layer's
metrics."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

from benchkit import cellrun, layout  # noqa: E402

BENCH = layout.load_benchmark()
CELL = "joyai_flash.moe4.tpu_like.clients4"
CFG = layout.config(BENCH, "joyai_flash.moe4.tpu_like")
MODEL = layout.model(CFG["model"])


def tiny_cfg() -> dict:
    cfg = dict(CFG)
    cfg.update(hidden_size=256, moe_intermediate_size=128,
               n_routed_experts=16, num_experts_per_tok=4, routed_experts=4,
               ep_rank=1, depth=2, tokens=32)
    cfg["fixed_point"] = dict(CFG["fixed_point"], router_shift=11,
                              gate_up_shift=11, act_shift=6, down_shift=9)
    return cfg


def test_published_widths_and_counted_work():
    cat = {"hidden_size": 2048, "moe_intermediate_size": 768,
           "n_routed_experts": 256, "num_experts_per_tok": 8,
           "n_shared_experts": 1, "routed_scaling_factor": 2.5,
           "scoring_func": "sigmoid", "topk_method": "noaux_tc",
           "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
           "num_hidden_layers": 40, "first_k_dense_replace": 1}
    assert {k: CFG[k] for k in cat} == cat
    assert (CFG["routed_experts"], CFG["depth"], CFG["tokens"]) == \
        (8, 4, 256)
    work = MODEL.call_work(CFG)
    assert MODEL.call_names(CFG) == ["moe1", "moe2", "moe3", "moe4"]
    assert {(w.macs, w.weight_bytes) for w in work} == \
        {(1_644_167_168, 42_991_616)}
    assert work[0].gang_bytes(4) - work[0].gang_bytes(1) == 3 * 2 * 524_288


@pytest.mark.parametrize("backend", ["simulator", "pallas"])
def test_model_module_agrees_with_the_compiled_path(backend):
    from repro.core.program import compile_multi

    cfg = tiny_cfg()
    data = MODEL.make_data(cfg, 3_000_000_021, 3)
    progs = compile_multi(MODEL.build(cfg, data))
    assert MODEL.held(cfg) == [4, 5, 6, 7]
    for s in range(3):
        want = MODEL.reference(cfg, data, s)
        for prog, feed, ref in zip(progs, MODEL.request(data, s), want):
            assert np.array_equal(prog(backend=backend, **feed), ref)
    control = MODEL.reference(cfg, data, 0, wgt_bits=4)
    assert not cellrun.passes(cellrun.compare(
        [(control, MODEL.reference(cfg, data, 0))]))


def test_a_run_through_the_harness_is_correct():
    mix = {"loop": "closed", "clients": 2, "pool_size": 2,
           "sched": {"gang_width": 2}, "input_sets": 3}
    rec = cellrun.run(CELL, tiny_cfg(), mix, 3_000_000_023, 0.6, False,
                      time.perf_counter(),
                      peaks=layout.peaks("TPU v5 lite"))
    assert rec.correct and rec.failed == 0 and rec.finished
    got = cellrun.metric_values(rec, layout.cell_metrics(BENCH, CELL,
                                                         "per_layer"))
    assert got["moe.route_ms_per_img"]["value"] > 0
    assert 0 <= got["moe.pad_share"]["value"] < 100
    assert 0 < got["engine.plan_hit_share"]["value"] <= 1
    assert "vta_kernels_roofline" not in got        # no device trace here
