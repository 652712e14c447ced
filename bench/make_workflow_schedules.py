"""Writes the schedules a ``suite_workflow`` traffic mix validates, once,
to a file committed with the mix, as ``bench/make_schedules.py`` does
for the §5.1 applications.

    python3 -m bench.make_workflow_schedules \\
        --config bench/configs/montage-bl260c-64.json \\
        --graph-seeds 200 201 202 203 --out bench/schedules/<name>.json

Each workflow is generated from its graph seed by ``bench/workflows.py``
and mapped by the program's ``engine`` scheduler (AMTHA); the file keeps,
per job, its core and start in model seconds. The benchmark's runs
never call this.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

from . import program, workflows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--graph-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    program.import_path()
    from repro.core import get_scheduler

    cfg = json.loads(Path(args.config).read_text())
    machine = program.machine(cfg)
    engine = get_scheduler("engine")
    apps = []
    for seed in args.graph_seeds:
        app = workflows.from_config(cfg, seed)
        pl = engine(program.graph(app), machine).placements
        n = app.n_subtasks
        apps.append({"graph_seed": seed, "n_subtasks": n,
                     "core": [int(pl[s].core) for s in range(n)],
                     "start": [float(pl[s].start) for s in range(n)]})
    Path(args.out).write_text(json.dumps(
        {"config": cfg["name"], "scheduler": "engine", "apps": apps}) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
