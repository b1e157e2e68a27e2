"""Synthetic tabular data from ``--seed``: the generator a configuration
names in its ``data`` block (``"generator": "class_gaussian"``), which
also holds its parameters.  A later configuration that needs another
kind of data brings ``datagen/<name>.py`` with the same ``make``.

Binary labels, every feature continuous (so each fills its ``max_bin``
bins), the two classes' means ``2 * separation`` apart along one random
direction.

The data set comes from ``base_seed`` in the configuration's file, as a
benchmark's data set is one data set, and ``--seed`` changes nothing of
it.  Two reasons, both measured (PERF.md section 2).  The time of a
boosting round depends on the shapes of the trees (the grower compacts
the rows of the leaves it splits into buckets of their size), the trees
on the noise of the gradient quantisation, and that noise is keyed by row
position: with a data set of its own for every seed, and also with one
data set whose training rows the seed reordered, runs of different seeds
differed by 2-4% where two runs of one seed differed by 0.3%.  And the
program compiles the labels and the held-out rows' bins into its round
program as constants: whatever of them follows the seed makes every new
seed a program of its own for the compiler, 60-90 s of set-up that a
seed seen before does not pay.  So every seed is the same data, the same
work and the same compiled program; ``--seed`` draws what the comparison
samples (comparisons/).

Features are returned feature-major, ``[F, rows]``: a column of the
``[rows, F]`` view is contiguous, which is what the program's per-column
binning reads fastest.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_THREADS = 8
_LABELS, _DIRECTION, _FEATURES = range(3)


def direction(spec: dict, features: int) -> np.ndarray:
    w = np.random.default_rng([int(spec["base_seed"]), _DIRECTION]).normal(size=features)
    return w / np.linalg.norm(w)


def strongest_feature(spec: dict, features: int) -> int:
    """The feature whose class means lie farthest apart: where a planted
    fault (tools/faults.py) hurts most."""
    return int(np.abs(direction(spec, features)).argmax())


def make(spec: dict, seed: int, part: int, rows: int, features: int):
    """``(xt32 [F, rows] f32, xt64 [F, rows] f64, y [rows] f32)`` for data part
    ``part`` (0 train, 1 valid).  Both parts share the direction.  ``seed``
    is the run's ``--seed``, which this generator leaves unused."""
    base = int(spec["base_seed"])
    y0 = (np.random.default_rng([base, _LABELS, part]).random(rows, dtype=np.float32)
          < float(spec["pos_rate"])).astype(np.float32)
    sign0 = np.where(y0 > 0, np.float32(1), np.float32(-1))
    shift = (float(spec["separation"]) * direction(spec, features)).astype(np.float32)
    xt32 = np.empty((features, rows), np.float32)
    xt64 = np.empty((features, rows), np.float64)

    def fill(j: int) -> None:
        col = np.random.default_rng([base, _FEATURES, part, j]) \
            .standard_normal(rows, dtype=np.float32)
        col += sign0 * shift[j]
        xt32[j] = col
        xt64[j] = col

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(features)))
    return xt32, xt64, y0
