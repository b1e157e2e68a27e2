"""The comparison that decides ``correct`` for a cell that trains a
binary-logloss GBDT on SPARSE rows (a configuration names it:
``"comparison": "gbdt_binary_csr"``).

The seven readings are ``gbdt_binary``'s, computed by its own ``gaps``
(comparisons/gbdt_binary.py says what each holds and how): the program's
answers are the same, and the plain reference the configuration names
(reference/gbdt_plain_csr.py) takes the rows feature-major as
``gbdt_plain`` does.  What this module adds is the hand-over: the inputs
are scipy CSR matrices, and their ``[F, n]`` transposes (views, nothing
is copied or made dense) are what the reference is given.  For a
one-hot column ``split_regret_mean`` searches the reference's ONE
candidate, ``x <= 0``; ``leaf_count_mismatch`` holds the bundles' encoding
and the range-predicate partition (a row routed by the raw column must
land in the leaf the program counted it in).
"""

from __future__ import annotations

from harness import load_module

_dense = load_module("comparisons", "gbdt_binary")
NOT_COMPARED = _dense.NOT_COMPARED


def feature_major(inputs: dict) -> dict:
    return {part: (rows.T, y) for part, (rows, y) in inputs.items()}


def gaps(ref, cfg: dict, answers: dict, inputs: dict, seed: int,
         split_trees="configured") -> dict:
    """Every compared number of one job.  ``inputs``: ``train`` and
    ``valid`` as ``(csr [n, F], y)``."""
    return _dense.gaps(ref, cfg, answers, feature_major(inputs), seed,
                       split_trees)


def control_answers(ref, cfg: dict, answers: dict, inputs: dict, dtype) -> dict:
    """``gbdt_binary.control_answers`` on the sparse rows: the reference in
    the program's place, in the precision below."""
    return _dense.control_answers(ref, cfg, answers, feature_major(inputs),
                                  dtype)
