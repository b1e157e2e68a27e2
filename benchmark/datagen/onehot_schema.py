"""Synthetic wide one-hot data as scipy CSR: the generator a
configuration names in its ``data`` block (``"generator":
"onehot_schema"``), which also holds its parameters.

The schema is a claims table's: ``numeric`` continuous columns, each row
holding a value in every one (dense singletons), then one block of
indicator columns per categorical column, ``levels[k]`` of them, exactly
one of which is 1 in a row (one-hot coded, no level dropped).  Level
frequencies fall as ``1 / rank ** zipf``: a few levels hold most rows and
the long tail holds hundreds each.  Nothing is missing.

Labels are Bernoulli draws from a logistic model over the numeric
columns AND a per-level effect of every block, ``logit_sd`` in all (the
two halves share its variance), shifted so that ``pos_rate`` of the rows
are positive (the shift solves ``E sigmoid(shift + logit_sd * Z) =
pos_rate`` for a standard normal Z, the same for both parts).

The data set comes from ``base_seed`` and is the same for every
``--seed``, as ``class_gaussian``'s is and for its two reasons (the
trees, and so the work, follow the rows; the program compiles the labels
and the held-out bins into its round program).  ``--seed`` draws what
the comparison samples.

``make`` returns ``(csr, y)``: float64 values (what ``lgb.Dataset``
takes), each numeric value a float32 widened, int32 indices, every row
with ``numeric + len(levels)`` stored entries in column order; at
13,184,290 x 4,228 that is 3.4 + 1.7 GB.  No dense block is ever built.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

_THREADS = 8
_LABELS, _WEIGHTS, _NUMERIC, _LEVELS, _EFFECTS = range(5)


def columns(spec: dict) -> int:
    return int(spec["numeric"]) + int(sum(spec["levels"]))


def level_probabilities(spec: dict, k: int) -> np.ndarray:
    rank = np.arange(1, int(spec["levels"][k]) + 1, dtype=np.float64)
    p = rank ** -float(spec["zipf"])
    return p / p.sum()


def intercept(spec: dict) -> float:
    z, wts = np.polynomial.hermite_e.hermegauss(64)
    sd, want = float(spec["logit_sd"]), float(spec["pos_rate"])
    lo, hi = -30.0, 30.0
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        rate = (wts / (1.0 + np.exp(-(mid + sd * z)))).sum() / wts.sum()
        lo, hi = (mid, hi) if rate < want else (lo, mid)
    return 0.5 * (lo + hi)


def _model(spec: dict):
    """The label model: a unit direction over the numeric columns, and per
    block the level effects, centred and scaled to unit variance under
    the block's level frequencies."""
    base = int(spec["base_seed"])
    w = np.random.default_rng([base, _WEIGHTS]).normal(size=int(spec["numeric"]))
    w /= np.linalg.norm(w)
    effects = []
    for k in range(len(spec["levels"])):
        p = level_probabilities(spec, k)
        e = np.random.default_rng([base, _EFFECTS, k]).normal(size=len(p))
        e -= (p * e).sum()
        effects.append(e / np.sqrt((p * e * e).sum()))
    return w, effects


def make(spec: dict, seed: int, part: int, rows: int, features: int):
    """``(csr [rows, features] float64, y [rows] float32)`` for data part
    ``part`` (0 train, 1 valid); both parts share the model.  ``seed`` is
    the run's ``--seed``, which this generator leaves unused."""
    base, numeric = int(spec["base_seed"]), int(spec["numeric"])
    blocks = len(spec["levels"])
    assert columns(spec) == features, (columns(spec), features)
    width = numeric + blocks
    w, effects = _model(spec)
    first = numeric + np.concatenate([[0], np.cumsum(spec["levels"])[:-1]])
    data = np.empty((rows, width), np.float64)
    indices = np.empty((rows, width), np.int32)
    data[:, numeric:] = 1.0
    indices[:, :numeric] = np.arange(numeric, dtype=np.int32)
    logit = [np.zeros(rows, np.float32) for _ in range(_THREADS)]

    def fill(job):
        slot, j = job
        if j < numeric:
            col = np.random.default_rng([base, _NUMERIC, part, j]) \
                .standard_normal(rows, dtype=np.float32)
            data[:, j] = col
            logit[slot] += np.float32(w[j]) * col
        else:
            k = j - numeric
            p = level_probabilities(spec, k)
            u = np.random.default_rng([base, _LEVELS, part, k]).random(rows)
            level = np.minimum(np.searchsorted(np.cumsum(p), u), len(p) - 1)
            indices[:, j] = (first[k] + level).astype(np.int32)
            logit[slot] += (effects[k] / np.sqrt(blocks)).astype(np.float32)[level]

    def worker(slot):
        for j in range(slot, width, _THREADS):
            fill((slot, j))
    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(worker, range(_THREADS)))
    # the two halves share the variance: the numeric part has unit
    # variance, the blocks' sum too
    z = (np.sum(logit, axis=0) * np.float32(float(spec["logit_sd"]) / np.sqrt(2.0))
         + np.float32(intercept(spec)))
    u = np.random.default_rng([base, _LABELS, part]).random(rows, dtype=np.float32)
    y = (u < 1.0 / (1.0 + np.exp(-z))).astype(np.float32)
    indptr = np.arange(rows + 1, dtype=np.int64) * width
    if indptr[-1] < np.iinfo(np.int32).max:
        indptr = indptr.astype(np.int32)
    csr = sp.csr_matrix((data.reshape(-1), indices.reshape(-1), indptr),
                        shape=(rows, features))
    csr.has_sorted_indices = True
    return csr, y
