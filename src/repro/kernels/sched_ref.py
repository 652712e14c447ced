"""NumPy-only scheduler-scoring helpers — the JAX-free leaf that the
Pallas kernel (``sched_score.py``) and the oracle registry (``ref.py``)
import; ``sched_score_np`` is the reference the kernel is checked
against."""

from __future__ import annotations

import warnings

import numpy as np

from repro.core.lowering import drain_matrix as _drain_matrix


def sched_score_np(drain, frontiers, release) -> np.ndarray:
    """Oracle for ``sched_score``: elementwise
    ``max(frontier[j], release[i]) + drain[i, j]`` over the
    (apps × cores) candidate matrix."""
    drain = np.asarray(drain, np.float32)
    f = np.asarray(frontiers, np.float32)[None, :]
    r = np.asarray(release, np.float32)[:, None]
    return np.maximum(f, r) + drain


def drain_matrix(graphs, machine) -> np.ndarray:
    """(apps × cores) serial drain times — the scoring input.

    Deprecated alias: the lowering lives in
    :func:`repro.core.lowering.drain_matrix` now (the shared scenario
    IR owns every graph/machine -> array derivation). Emits a
    ``DeprecationWarning`` — import from ``repro.core.lowering``."""
    warnings.warn(
        "repro.kernels.sched_ref.drain_matrix is deprecated; use "
        "repro.core.lowering.drain_matrix",
        DeprecationWarning, stacklevel=2)
    return _drain_matrix(graphs, machine)
