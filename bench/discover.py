"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

* a configuration: the ``file`` its ``configs`` entry names;
* a traffic mix: ``bench/traffic/<traffic>.json``, which names its
  workload kind and holds the mix's parameters and comparison limits;
* a workload kind: ``bench/kinds/<kind>.py``;
* a per-layer metric: ``bench/metrics/<metric name>.py``, with a
  ``read(ctx)`` that returns the number or ``None``.

A new cell, configuration, traffic mix or metric is therefore new files
and new entries, with no edit to a file that is already there.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    kind: object
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def kind(name: str, bench_dir: Path = BENCH):
    path = bench_dir / "kinds" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no workload kind {path}")
    return importlib.import_module(f"{bench_dir.name}.kinds.{name}")


def reader(metric: str, bench_dir: Path = BENCH):
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"{bench_dir.name}_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(name: str, root: Path = ROOT, bench_dir: Path = BENCH) -> Cell:
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    # a per-layer metric with no "workloads" goes wherever its end-to-end
    # metric does
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", ())
             or ("workloads" not in m
                 and any(e["name"] == m["moves"] for e in e2e))]
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        kind=kind(traffic["kind"], bench_dir),
        end_to_end=e2e,
        per_layer=layer,
        readers={m["name"]: reader(m["name"], bench_dir) for m in layer})
