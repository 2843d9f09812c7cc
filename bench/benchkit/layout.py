"""Where the benchmark's parts live, found by the names in BENCHMARK.json.

    BENCHMARK.json                 cells, configurations, metrics
    bench/configs/<config>.json    a configuration as it is run (its
                                   ``file`` entry in BENCHMARK.json)
    bench/models/<model>.py        how to build, feed, count and check a
                                   configuration's ``model``; its plain
                                   reference sits beside it
    bench/traffic/<mix>.json       one traffic mix (see benchkit.loadgen)
    bench/metrics/<metric>.py      one per-layer metric's reader
    bench/peaks.json               per-chip peaks, keyed by device_kind

A new cell, configuration, mix or metric is new files and new entries in
BENCHMARK.json; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

#: names of cells, configurations, mixes, metrics and reduced keys
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
#: units
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class LayoutError(LookupError):
    """A name in BENCHMARK.json has no file behind it."""


def load_benchmark(root: Path = ROOT) -> Dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise LayoutError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        return json.load(f)


def cell(bench: Dict, name: str) -> Dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise LayoutError(f"no cell {name!r} in BENCHMARK.json")


def config(bench: Dict, name: str, root: Path = ROOT) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(Path(root) / c["file"]) as f:
                return json.load(f)
    raise LayoutError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict:
    path = Path(bench_dir) / "traffic" / f"{name}.json"
    if not path.is_file():
        raise LayoutError(f"no traffic mix {name!r} ({path})")
    with open(path) as f:
        return json.load(f)


def _module(path: Path, modname: str) -> ModuleType:
    if not path.is_file():
        raise LayoutError(f"no file {path}")
    d = str(path.parent)
    if d not in sys.path:          # siblings (a model's reference) import
        sys.path.insert(0, d)
    have = sys.modules.get(modname)
    if have is not None and getattr(have, "__file__", None) == str(path):
        return have
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def model(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    return _module(Path(bench_dir) / "models" / f"{name}.py", name)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The reader module of one per-layer metric: ``NAME``, ``UNIT``,
    ``LAYER``, ``MOVES``, ``SOURCE`` and ``read(run) -> float | None``."""
    safe = "bench_metric_" + re.sub(r"\W", "_", name)
    return _module(Path(bench_dir) / "metrics" / f"{name}.py", safe)


def cell_metrics(bench: Dict, cell_name: str, kind: str) -> list:
    """The metric entries (``end_to_end`` or ``per_layer``) that a cell
    reports: those without ``workloads``, and those that list it."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def peaks(kind: str, bench_dir: Path = BENCH_DIR) -> Dict:
    """Peak rates of one chip, by JAX's ``device_kind``.  A kind missing
    from the table is an error, never a default."""
    with open(Path(bench_dir) / "peaks.json") as f:
        table = json.load(f)["chips"]
    if kind not in table:
        raise LayoutError(f"device_kind {kind!r} is not in bench/peaks.json "
                          f"(known: {sorted(table)})")
    return table[kind]


def find_checkout_src(root: Path = ROOT) -> Optional[Path]:
    src = Path(root) / "src"
    return src if (src / "repro").is_dir() else None
