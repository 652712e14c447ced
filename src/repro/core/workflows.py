"""Scientific workflows as MPAHA graphs: the Montage astronomy mosaic.

The DAG is the one the Pegasus workflow generator builds (Bharathi et
al., "Characterization of Scientific Workflows", WORKS 2008); the job
runtimes and file sizes follow the Montage profile of Juve et al.,
"Characterizing and profiling scientific workflows", FGCS 29(3), 2013.
On an image grid of ``g × g`` (``n = g²`` images):

* ``mProjectPP`` — one per image, no predecessors;
* ``mDiffFit`` — one per pair of overlapping images, reading both
  projected images; each image overlaps its right, lower and
  lower-right neighbour, so ``2g(g-1) + (g-1)²`` pairs (assumed);
* ``mConcatFit`` — one job reading every fit;
* ``mBgModel`` — reads the concatenated fits;
* ``mBackground`` — one per image, reading the background model and
  that image's projection;
* ``mImgtbl`` — reads every corrected image;
* ``mAdd`` — reads the image table and every corrected image;
* ``mShrink``, then ``mJPEG``.

Each job is one task of one subtask; each message is the file the
consumer reads from its producer. The wide joins (``mConcatFit``,
``mImgtbl``, ``mAdd``) are what the bounded predecessor layout of
``core/lowering.py`` folds into join rows.
"""

from __future__ import annotations

import numpy as np

from .mpaha import AppGraph

#: mean runtime in seconds per job type (Juve et al. 2013, Montage)
MONTAGE_RUNTIME_S = {
    "mProjectPP": 1.73, "mDiffFit": 0.66, "mConcatFit": 143.26,
    "mBgModel": 384.49, "mBackground": 1.72, "mImgtbl": 2.78,
    "mAdd": 282.37, "mShrink": 66.10, "mJPEG": 0.64,
}

#: bytes of each file a job reads (assumed, after Juve et al. 2013):
#: the fits table holds one fit per overlap pair and the mosaic one
#: corrected image per image, so both scale with the grid
MONTAGE_FILE_BYTES = {
    "projected_image": 8.0e6, "fit": 1.0e3, "background_model": 1.0e5,
    "corrected_image": 8.0e6, "image_table_row": 1.0e3,
    "shrunk_mosaic": 5.0e7,
}


def montage_pairs(g: int) -> list[tuple[int, int]]:
    """Overlapping image pairs of a ``g × g`` grid, row-major: each image
    with its right, lower and lower-right neighbour."""
    pairs = []
    for r in range(g):
        for c in range(g):
            for dr, dc in ((0, 1), (1, 0), (1, 1)):
                if r + dr < g and c + dc < g:
                    pairs.append((r * g + c, (r + dr) * g + c + dc))
    return pairs


def montage(g: int, seed: int) -> AppGraph:
    """The Montage workflow of a ``g × g`` grid. Each job's time is its
    type's mean runtime times ``U(0.8, 1.2)``, drawn from ``seed`` in job
    order (assumed)."""
    if g < 2:
        raise ValueError("a Montage grid needs at least 2 x 2 images")
    mean, size = MONTAGE_RUNTIME_S, MONTAGE_FILE_BYTES
    rng = np.random.default_rng(seed)
    n = g * g
    pairs = montage_pairs(g)
    kinds = (["mProjectPP"] * n + ["mDiffFit"] * len(pairs)
             + ["mConcatFit", "mBgModel"] + ["mBackground"] * n
             + ["mImgtbl", "mAdd", "mShrink", "mJPEG"])
    graph = AppGraph(n_types=1)
    for tid, kind in enumerate(kinds):
        graph.add_task(tid, [(mean[kind] * float(rng.uniform(0.8, 1.2)),)])
    project = range(n)
    diff = range(n, n + len(pairs))
    concat = diff.stop
    bgmodel = concat + 1
    background = range(bgmodel + 1, bgmodel + 1 + n)
    imgtbl, add, shrink, jpeg = range(background.stop, background.stop + 4)
    for d, (a, b) in zip(diff, pairs):
        graph.add_edge(project[a], d, size["projected_image"])
        graph.add_edge(project[b], d, size["projected_image"])
    for d in diff:
        graph.add_edge(d, concat, size["fit"])
    graph.add_edge(concat, bgmodel, size["fit"] * len(pairs))
    for i, bg in enumerate(background):
        graph.add_edge(bgmodel, bg, size["background_model"])
        graph.add_edge(project[i], bg, size["projected_image"])
    for bg in background:
        graph.add_edge(bg, imgtbl, size["corrected_image"])
    graph.add_edge(imgtbl, add, size["image_table_row"] * n)
    for bg in background:
        graph.add_edge(bg, add, size["corrected_image"])
    graph.add_edge(add, shrink, size["corrected_image"] * n)
    graph.add_edge(shrink, jpeg, size["shrunk_mosaic"])
    graph.finalize()
    return graph
