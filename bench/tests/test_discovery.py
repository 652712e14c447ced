"""A cell, a traffic mix and a per-layer metric are found by name: a new
one is new files and new ``BENCHMARK.json`` entries, and no file that is
already there changes."""

import hashlib
import json
import shutil
from pathlib import Path

from bench import discover

ROOT = discover.ROOT


def _digests(tree: Path) -> dict:
    return {str(p.relative_to(tree)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(tree.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_every_committed_cell_resolves():
    bench = discover.benchmark()
    for w in bench["workloads"]:
        cell = discover.resolve(w["name"])
        assert cell.config["name"] == w["config"]
        assert hasattr(cell.kind, "window")
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


def test_new_cell_is_found_by_adding_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digests(root / "bench")

    traffic = json.loads((root / "bench/traffic/suite-jitter32.json")
                         .read_text())
    traffic.update(jitter=0.05)
    (root / "bench/traffic/suite-jitter5pct.json").write_text(
        json.dumps(traffic))
    (root / "bench/metrics/gather_ms.suite.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "suite-bl260c-jitter5pct",
                               "config": "bl260c-64",
                               "traffic": "suite-jitter5pct",
                               "chips": 1, "why": "wider jitter"})
    for m in bench["end_to_end"]:
        if m["name"] == "suite_scenarios_per_s":
            m["workloads"].append("suite-bl260c-jitter5pct")
    bench["per_layer"].append({"name": "gather_ms.suite", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "relaxation (device)",
                               "moves": "suite_scenarios_per_s",
                               "workloads": ["suite-bl260c-jitter5pct"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = discover.resolve("suite-bl260c-jitter5pct", root=root,
                            bench_dir=root / "bench")
    assert cell.traffic["jitter"] == 0.05
    assert cell.kind.__name__ == "bench.kinds.suite"
    assert [m["name"] for m in cell.per_layer] == ["gather_ms.suite"]
    assert cell.readers["gather_ms.suite"].read(None) == 1.0
    assert "suite_scenarios_per_s" in {m["name"] for m in cell.end_to_end}
    after = _digests(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
