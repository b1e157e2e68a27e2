"""Faults of a job with categorical columns, planted as ``faults.py``
plants its own (each takes ``setattr(object, name, value)``).  The
program's compiled runners hold what was traced: clear them
(``harness.program.free_everything``) before a planted job and after.
A fault with ``rebins = True`` acts on the binning: its job constructs
its own ``Dataset`` under the fault (tools/control_cat.py)."""

from __future__ import annotations


def fold_unbinned_levels(setattr_) -> None:
    """The parent's binning (before PR 43): a categorical column keeps no
    bin for a level beyond the ``max_bin - 1`` it has bins for; such a
    row is FOLDED into bin 0, the most frequent level's.  Wherever a left
    set holds bin 0 the partition sends those rows left, and the stated
    model, whose sets are over raw codes, sends the same rows right: the
    leaf counts, the training scores and every later gradient belong to
    a tree the booster does not hold."""
    from lightgbm_tpu.io import binning
    setattr_(binning.BinMapper, "other_bin", property(lambda self: -1))


fold_unbinned_levels.rebins = True


def shift_left_sets(setattr_, by: int = 1) -> None:
    """The partition is handed every left set ``by`` bins off its place
    (a word table built one off): rows of the neighbouring levels change
    sides, while the stated model and the held-out scoring keep the true
    sets."""
    import jax.numpy as jnp
    from lightgbm_tpu.learner import batch_grower
    real = batch_grower.pack_left_bins
    setattr_(batch_grower, "pack_left_bins",
             lambda bits: real(jnp.roll(bits, by, axis=1)))


def _hyper(setattr_, **over) -> None:
    """The learner's static parameters come out of the configuration
    with ``over`` in place of what the job asked for."""
    import dataclasses
    from lightgbm_tpu.boosting import gbdt
    real = gbdt._hp_from_config

    def changed(*args, **kwargs):
        hp = real(*args, **kwargs)
        return dataclasses.replace(
            hp, **{k: change(getattr(hp, k)) for k, change in over.items()})
    setattr_(gbdt, "_hp_from_config", changed)


def drop_cat_l2(setattr_) -> None:
    """``cat_l2`` is left out: the subset scan ranks its candidate sets
    under ``lambda_l2`` alone and overstates what a small set of levels
    is worth; the partition and the stated sets are sound."""
    _hyper(setattr_, cat_l2=lambda _: 0.0)


def widen_left_sets(setattr_, by: int = 4) -> None:
    """``max_cat_threshold`` comes out ``by`` times what the job asked
    for: the scan states sets of up to 128 levels, which no scan under
    the published parameters could state."""
    _hyper(setattr_, max_cat_threshold=lambda v: int(v) * by)


def skip_descending(setattr_) -> None:
    """The subset scan never looks at the descending direction (it scans
    the ascending one twice): every stated set is a true and allowed one
    and only the gain it gives away shows."""
    from lightgbm_tpu.ops import split
    real = split.sort_by_score
    setattr_(split, "sort_by_score",
             lambda score, cand_bin, stats, descending:
             real(score, cand_bin, stats, False))
