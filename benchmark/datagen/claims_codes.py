"""Synthetic claims table with its categorical columns INTEGER-CODED: the
generator a configuration names in its ``data`` block (``"generator":
"claims_codes"``), which also holds its parameters.

The rows are ``onehot_schema``'s, letter for letter: the same ``numeric``
continuous columns, and for each of the ``levels`` blocks the level that
``onehot_schema`` sets to 1 in that row, from the same ``base_seed``, with
the same labels.  Nothing of its label model, its level probabilities or
its draws is restated here: ``onehot_schema.make`` is called, and the
level of a block is read back from the column index of the one stored
entry the row has in that block.  What this module adds is the coding:
a column a user hands to ``lgb.Dataset`` as ``categorical_feature``
holds one non-negative integer a level, and a real table's codes are
neither in frequency order nor in effect order, so the code of level
``r`` (``r`` the frequency rank, 0 the commonest) is ``perm[r]`` for a
fixed permutation of ``0..levels-1`` drawn from ``base_seed``
(``code_of_level``).

``make`` returns ``(xt32 [F, rows] f32, xt64 [F, rows] f64, y [rows]
f32)`` feature-major, as ``class_gaussian`` does: ``xt64.T`` is the dense
float64 ``[rows, F]`` matrix ``lgb.Dataset`` takes (a view), ``xt32`` the
copy the plain reference takes (a code below 2**24 is exact in float32).
One data set from ``base_seed`` for every ``--seed``.
"""

from __future__ import annotations

import numpy as np

from harness import load_module

_CODES = 5      # onehot_schema's streams are 0..4


def categorical_columns(spec: dict) -> list:
    numeric = int(spec["numeric"])
    return list(range(numeric, numeric + len(spec["levels"])))


def code_of_level(spec: dict, k: int) -> np.ndarray:
    """``perm`` [levels[k]] int64: the code of block ``k``'s level of
    frequency rank ``r`` is ``perm[r]``."""
    return np.random.default_rng([int(spec["base_seed"]), _CODES, k]) \
        .permutation(int(spec["levels"][k])).astype(np.int64)


def make(spec: dict, seed: int, part: int, rows: int, features: int):
    """``(xt32, xt64, y)`` for data part ``part`` (0 train, 1 valid).
    ``seed`` is the run's ``--seed``, which this generator leaves unused."""
    schema = load_module("datagen", "onehot_schema")
    numeric, blocks = int(spec["numeric"]), len(spec["levels"])
    assert features == numeric + blocks, (features, numeric, blocks)
    csr, y = schema.make(spec, seed, part, rows, schema.columns(spec))
    width = numeric + blocks
    value = csr.data.reshape(rows, width)
    column = csr.indices.reshape(rows, width)
    first = numeric + np.concatenate([[0], np.cumsum(spec["levels"])[:-1]])
    xt64 = np.empty((features, rows), np.float64)
    xt32 = np.empty((features, rows), np.float32)
    for j in range(numeric):
        xt64[j] = value[:, j]
    for k in range(blocks):
        level = column[:, numeric + k] - np.int32(first[k])
        xt64[numeric + k] = code_of_level(spec, k)[level]
    del csr, value, column
    xt32[:] = xt64
    return xt32, xt64, y
