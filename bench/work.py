"""The work an algorithm needs, counted from shapes, and the least time
the chip could do it in.

A relaxation of B scenarios of S subtasks with at most P predecessor
columns (the in-order core edge included) needs one acyclic pass:

* per edge (B·S·P): read its source index (int32), its two lags
  (float32 latency and bytes over bandwidth) and the source's finish
  time (float32), then two adds and one max;
* per subtask (B·S): read its duration and release, write its end
  (float32), and take two maxes and one add.

That is the work whatever implements it: a kernel that sweeps S times,
a scan, or a fused loop all count the same. Padding is not work.
"""

from __future__ import annotations

import json
from pathlib import Path

EDGE_BYTES = 4 + 4 + 4 + 4
NODE_BYTES = 4 + 4 + 4
EDGE_OPS = 3
NODE_OPS = 3


def relax_pass(b: int, s: int, p: int) -> dict:
    """Operations and bytes of one relaxation pass over (B, S, P)."""
    edges, nodes = b * s * p, b * s
    return {"ops": EDGE_OPS * edges + NODE_OPS * nodes,
            "bytes": EDGE_BYTES * edges + NODE_BYTES * nodes}


def add(a: dict, b: dict) -> dict:
    return {k: a.get(k, 0) + b.get(k, 0) for k in set(a) | set(b)}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a kind missing from the
    table is an error, not a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")


def least_time(work: dict, peak: dict) -> tuple[float, str]:
    """(seconds, bound): the larger of bytes over peak bandwidth and
    operations over peak rate, and which of the two binds."""
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    t_ops = work["ops"] / peak["flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
