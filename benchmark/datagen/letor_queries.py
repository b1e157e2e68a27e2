"""Synthetic learning-to-rank data: the generator a ranking configuration
names in its ``data`` block (``"generator": "letor_queries"``), which also
holds its parameters.  Query-grouped docs with graded relevance 0-4,
every feature continuous and none missing.

``make(spec, seed, part, rows, features)`` returns ``(xt64 [F, rows] f64,
y [rows] f32, sizes [queries] i64)`` for data part ``part`` (0 train,
1 valid).  ONE copy of the matrix, in the dtype ``lgb.Dataset`` takes
without converting (float64, feature-major: a column of the ``[rows, F]``
view is contiguous) and with float32 values in it, so that the plain
reference reads blocks of it as float32 exactly.

The data set comes from ``base_seed`` in the configuration's file and is
the same for every ``--seed`` (datagen/class_gaussian.py says why: the
trees, the work and the compiled program follow the data).

Query lengths: a lognormal shape (``length_sigma``) cut to ``[min_docs,
max_docs]`` and scaled so that the ``queries[part]`` lengths add up to
``rows`` exactly; the mean is then the source's (rows / queries).

Labels: a latent relevance per doc, of unit variance,

    z = signal * (x . w) / |w|  +  query_sd * o_q  +  noise_sd * e

with ``w`` non-zero on ``informative`` features (both parts share it),
``o_q`` a standard normal per QUERY (some queries hold many relevant
docs, many hold none) and ``e`` per doc; ``noise_sd`` is what is left of
the unit variance.  The grade is the number of thresholds ``z`` passes,
the thresholds being the normal quantiles that give ``label_shares``.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist

import numpy as np

_THREADS = 8
_SIZES, _WEIGHTS, _FEATURES, _OFFSETS, _NOISE = range(5)


def query_sizes(spec: dict, part: int, rows: int) -> np.ndarray:
    """i64 [queries]: lengths in ``[min_docs, max_docs]`` that add up to
    ``rows``."""
    nq = int(spec["queries"][part])
    lo, hi = int(spec["min_docs"]), int(spec["max_docs"])
    if not lo * nq <= rows <= hi * nq:
        raise ValueError(f"{rows} docs do not fit {nq} queries of {lo} to {hi}")
    rng = np.random.default_rng([int(spec["base_seed"]), _SIZES, part])
    raw = np.exp(float(spec["length_sigma"]) * rng.standard_normal(nq))
    # the scale at which the cut lengths add up to the rows: the sum grows
    # with the scale, so halve the interval that holds it
    a, b = lo / raw.max(), hi / raw.min()
    for _ in range(80):
        mid = 0.5 * (a + b)
        a, b = (mid, b) if np.clip(raw * mid, lo, hi).sum() < rows else (a, mid)
    sizes = np.clip(raw * a, lo, hi)
    sizes = np.floor(sizes).astype(np.int64)
    # the rounding's remainder, one doc at a time where there is room
    order = rng.permutation(nq)
    while (short := rows - int(sizes.sum())) != 0:
        step = 1 if short > 0 else -1
        ok = order[(sizes[order] + step >= lo) & (sizes[order] + step <= hi)]
        sizes[ok[:abs(short)]] += step
    return sizes


def weights(spec: dict, features: int) -> np.ndarray:
    """The relevance direction, unit length, non-zero on ``informative``
    features drawn from the seed."""
    rng = np.random.default_rng([int(spec["base_seed"]), _WEIGHTS])
    w = np.zeros(features)
    k = min(int(spec["informative"]), features)
    w[rng.choice(features, size=k, replace=False)] = rng.standard_normal(k)
    return w / np.linalg.norm(w)


def thresholds(spec: dict) -> np.ndarray:
    shares = np.asarray(spec["label_shares"], np.float64)
    assert abs(shares.sum() - 1.0) < 1e-9
    return np.array([NormalDist().inv_cdf(float(c))
                     for c in np.cumsum(shares)[:-1]], np.float32)


def make(spec: dict, seed: int, part: int, rows: int, features: int):
    """``seed`` is the run's ``--seed``, which this generator leaves
    unused."""
    base = int(spec["base_seed"])
    sizes = query_sizes(spec, part, rows)
    w = weights(spec, features).astype(np.float32)
    signal, query_sd = float(spec["signal"]), float(spec["query_sd"])
    noise_sd = max(0.0, 1.0 - signal ** 2 - query_sd ** 2) ** 0.5
    xt64 = np.empty((features, rows), np.float64)

    def fill(j: int) -> None:
        xt64[j] = np.random.default_rng([base, _FEATURES, part, j]) \
            .standard_normal(rows, dtype=np.float32)

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(features)))
    z = np.zeros(rows, np.float32)
    for j in np.flatnonzero(w):
        z += xt64[j].astype(np.float32) * w[j]
    z *= np.float32(signal)
    offsets = np.random.default_rng([base, _OFFSETS, part]) \
        .standard_normal(len(sizes), dtype=np.float32)
    z += np.float32(query_sd) * np.repeat(offsets, sizes)
    z += np.float32(noise_sd) * np.random.default_rng([base, _NOISE, part]) \
        .standard_normal(rows, dtype=np.float32)
    y = np.searchsorted(thresholds(spec), z, side="right").astype(np.float32)
    return xt64, y, sizes
