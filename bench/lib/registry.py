"""Everything the harness finds by name.

- a cell: an entry of ``workloads`` in ``BENCHMARK.json``;
- its configuration: the file the ``configs`` entry names;
- its traffic mix: ``bench/traffic/<traffic>.json``, which names a driver
  kind;
- a driver kind: ``bench/drivers/<kind>.py`` with ``run(run) -> dict``;
- a per-layer metric: ``bench/layer_metrics/<metric name>.py`` with
  ``read(ctx) -> float | None``.

A later change adds a cell, a mix or a metric by adding files and
entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bench['workloads']]})")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(root / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: Path = BENCH) -> dict:
    return load_json(bench_dir / "traffic" / f"{name}.json")


def _module(path: Path, tag: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {tag} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str, bench_dir: Path = BENCH):
    return _module(bench_dir / "drivers" / f"{kind}.py", "driver")


def metric_reader(name: str, bench_dir: Path = BENCH):
    return _module(bench_dir / "layer_metrics" / f"{name}.py", "metric")


def end_to_end(bench: dict, cell_name: str) -> list[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(bench: dict, cell_name: str) -> list[dict]:
    """The per-layer metrics read in this cell's traced runs: those that
    list it, and those without a list whose end-to-end metric it
    reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell_name)}

    def reads_here(m):
        if "workloads" in m:
            return cell_name in m["workloads"]
        return m["moves"] in e2e
    return [m for m in bench["per_layer"] if reads_here(m)]
