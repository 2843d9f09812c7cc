"""BENCHMARK.json against the benchmark's contract, and discovery by name:
every cell's configuration, mix, model and metric readers are found from
the names alone, and new ones need only new files and entries."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

from benchkit import layout  # noqa: E402

BENCH = layout.load_benchmark(ROOT)
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _one_line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command():
    assert set(BENCH) == TOP_KEYS
    assert BENCH["paths"] == ["bench"]
    assert all(_one_line(w) for w in BENCH["command"])
    assert BENCH["command"][1].startswith("bench/")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


@pytest.mark.parametrize("kind,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entry_keys_and_names(kind, keys):
    names = [e["name"] for e in BENCH[kind]]
    assert len(names) == len(set(names))
    for e in BENCH[kind]:
        assert set(e) - {"workloads"} == keys, e
        assert layout.NAME_RE.match(e["name"]), e["name"]
        if "unit" in e:
            assert layout.UNIT_RE.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for k in ("why", "layer", "source"):
            if k in e:
                assert _one_line(e[k]), (e["name"], k)


def test_names_are_unique_across_metrics():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


def test_cells_find_their_files_by_name():
    for c in BENCH["workloads"]:
        assert c["chips"] in (1, 4)
        assert layout.NAME_RE.match(c["config"])
        assert layout.NAME_RE.match(c["traffic"])
        cfg = layout.config(BENCH, c["config"], ROOT)
        assert cfg["name"] == c["config"]
        mix = layout.traffic(c["traffic"])
        assert mix["loop"] in ("closed", "open")
        mod = layout.model(cfg["model"])
        assert len(mod.call_work(cfg)) == len(mod.call_names(cfg))
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_config_files_are_distinct_and_reduced_keys_valid():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert layout.NAME_RE.match(k)
            assert not k.endswith(("_dim", "_rank", "_size"))
        cfg = layout.config(BENCH, c["name"], ROOT)
        assert cfg["reduced"] == c["reduced"]


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25


def test_every_metric_has_a_reader_that_agrees():
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            r = layout.metric_reader(m["name"])
            assert r.NAME == m["name"] and r.UNIT == m["unit"]
            assert r.SOURCE == m["source"]
            assert callable(r.read)
            if kind == "per_layer":
                assert r.LAYER == m["layer"] and r.MOVES == m["moves"]


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for c in BENCH["workloads"]:
        mine = {m["name"] for m in
                layout.cell_metrics(BENCH, c["name"], "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        assert layout.cell_metrics(BENCH, c["name"], "per_layer")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for c in m.get("workloads", [w["name"] for w in BENCH["workloads"]]):
            mine = {x["name"] for x in
                    layout.cell_metrics(BENCH, c, "end_to_end")}
            assert m["moves"] in mine, (m["name"], c)


def test_roofline_and_mfu_names():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    roof = {m["moves"] for m in BENCH["per_layer"]
            if m["name"].endswith("_roofline")}
    mfu = {m["moves"] for m in BENCH["per_layer"] if "mfu" in m["name"]}
    assert roof <= mfu


def test_missing_device_kind_is_an_error():
    assert layout.peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(layout.LayoutError):
        layout.peaks("TPU v99 imaginary")


def test_files_under_paths_are_named_from_name_characters():
    import re
    ok = re.compile(r"^[A-Za-z0-9_./-]+$")
    for p in BENCH_DIR.rglob("*"):
        if "__pycache__" in p.parts or not p.is_file():
            continue
        assert ok.match(str(p.relative_to(ROOT))), p


def _run(argv, cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    p = _run(["bench/run.py", "--workload", BENCH["workloads"][0]["name"],
              "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
             ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_refuses_in_a_directory_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["bench/run.py", "--workload", BENCH["workloads"][0]["name"],
              "--seed", "1", "--seconds", "1", "--trace", "0"],
             tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout.strip() == ""


def _digest(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha1(p.read_bytes()).hexdigest()
            for p in d.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_need_only_new_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    bench_dir = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(bench_dir)

    # a new configuration, a new open-loop mix and a new metric: files
    cfg = json.loads((bench_dir / "configs/resnet18.pynq.json").read_text())
    cfg["name"] = "resnet18.pynq_b2"
    (bench_dir / "configs/resnet18.pynq_b2.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic/poisson1.json").write_text(json.dumps(
        {"loop": "open", "arrival": "poisson", "rate_per_s": 1.0,
         "pool_size": 4, "sched": {}, "input_sets": 8}))
    (bench_dir / "metrics/serve.calls_per_img.py").write_text(
        'NAME = "serve.calls_per_img"\nUNIT = "calls"\n'
        'LAYER = "serving plane"\nMOVES = "img_per_s"\n'
        'SOURCE = "program_counter"\n\n\ndef read(run):\n'
        '    return float(len(run.call_names))\n')
    # ... and entries in BENCHMARK.json
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "resnet18.pynq_b2", "source": "x",
                             "file": "bench/configs/resnet18.pynq_b2.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "resnet18.pynq_b2.poisson1",
                               "config": "resnet18.pynq_b2",
                               "traffic": "poisson1", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "serve.calls_per_img",
                               "unit": "calls", "better": "lower",
                               "source": "program_counter",
                               "layer": "serving plane",
                               "moves": "img_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    got = layout.load_benchmark(tmp_path)
    cell = layout.cell(got, "resnet18.pynq_b2.poisson1")
    assert layout.config(got, cell["config"], tmp_path)["name"] == \
        "resnet18.pynq_b2"
    assert layout.traffic(cell["traffic"], bench_dir)["loop"] == "open"
    entries = layout.cell_metrics(got, cell["name"], "per_layer")
    assert "serve.calls_per_img" in [m["name"] for m in entries]
    reader = layout.metric_reader("serve.calls_per_img", bench_dir)

    class _Run:
        call_names = ["a", "b"]
    assert reader.read(_Run()) == 2.0
    after = _digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
