"""The useful work the benchmark counts per call and per gang, checked
against the repository's own table of the ResNet-18 Table-1 convs: one
image is 3.417 GOP over 11.16 M int8 weights."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

from benchkit import layout  # noqa: E402

BENCH = layout.load_benchmark()


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_work_per_call_matches_table1(config):
    from repro.core.workloads import resnet18_table1

    cfg = layout.config(BENCH, config)
    model = layout.model(cfg["model"])
    work = dict(zip(model.call_names(cfg), model.call_work(cfg)))
    table = [l for l in resnet18_table1() if not l.cpu_only]
    names = model.call_names(cfg)
    for layer in table:
        s = layer.shape
        assert names.count(layer.name) == layer.repeat
        w = work[layer.name]
        assert w.macs == s.macs
        assert w.weight_bytes == s.oc * s.ic * s.kh * s.kw
        assert w.in_bytes == s.n * s.ic * s.h * s.w
        assert w.out_bytes == s.n * s.oc * s.oh * s.ow
    all_work = model.call_work(cfg)
    assert len(all_work) == 20
    assert sum(w.ops for w in all_work) == pytest.approx(3.417e9, rel=1e-3)
    assert sum(w.weight_bytes for w in all_work) == \
        pytest.approx(11.16e6, rel=1e-3)
    assert sum(w.in_bytes + w.out_bytes for w in all_work) == \
        pytest.approx(4.114e6, rel=1e-3)


def test_gang_bytes_count_weights_once():
    cfg = layout.config(BENCH, BENCH["configs"][0]["name"])
    w = layout.model(cfg["model"]).call_work(cfg)[0]
    assert w.gang_bytes(1) == w.weight_bytes + w.in_bytes + w.out_bytes
    assert w.gang_bytes(4) - w.gang_bytes(1) == 3 * (w.in_bytes + w.out_bytes)
