"""Faults of a ranking job, planted as ``faults.py`` plants its own (each
takes ``setattr(object, name, value)``).  The program's compiled runners
hold what was traced: clear them (``harness.program.free_everything``)
before a planted job and after.

Each is a mistake a LambdaMART implementation can make and still train:
the trees grow, the NDCG climbs, and only the comparison with the plain
reference (comparisons/gbdt_rank.py) shows it, in the leaf values (they
hold the gradients), in ``valid_ndcg_gap`` (the device NDCG) or in
``stated_hessian_shortfall`` (the one constraint the settings state)."""

from __future__ import annotations

import inspect
import textwrap


def _wrap_bucket(setattr_, change) -> None:
    """``change(real, score, qidx, inv_dcg, gain_slot, label_slot, **kw)``
    in place of objectives.py's ``_lambdarank_bucket``."""
    from lightgbm_tpu import objectives
    real = objectives._lambdarank_bucket
    setattr_(objectives, "_lambdarank_bucket",
             lambda *a, **kw: change(real, *a, **kw))


def no_normalisation(setattr_) -> None:
    """The ``log2(1 + S) / S`` normalisation of a query's gradients is
    left out."""
    _wrap_bucket(setattr_, lambda real, *a, **kw: real(*a, **{**kw, "norm": False}))


def truncation_quadrupled(setattr_) -> None:
    """The truncation level is not the job's: pairs whose better-placed
    doc lies below it count too (four times the level: the whole query at
    this size would not fit the chip)."""
    _wrap_bucket(setattr_, lambda real, *a, **kw:
                 real(*a, **{**kw, "trunc": 4 * kw["trunc"]}))


def drop_max_dcg(setattr_) -> None:
    """``|dNDCG|`` without the query's inverse ideal DCG: a query with
    many relevant docs weighs as one with a single one."""
    import jax.numpy as jnp
    _wrap_bucket(setattr_, lambda real, score, qidx, inv, *a, **kw:
                 real(score, qidx, jnp.where(inv > 0, 1.0, 0.0), *a, **kw))


def ties_reversed(setattr_) -> None:
    """Docs of equal score are ranked in REVERSE index order (an unstable
    or a backwards sort): early trees leave whole leaves of a query tied."""
    def flipped(real, score, qidx, inv, gain_slot, label_slot, **kw):
        g, h = real(score, qidx[:, ::-1], inv, gain_slot[:, ::-1],
                    label_slot[:, ::-1], **kw)
        return g[:, ::-1], h[:, ::-1]
    _wrap_bucket(setattr_, flipped)


def boundary_off_by_one(setattr_) -> None:
    """Every query's docs are taken one doc late: the first doc of the
    next query in place of its own first (a boundary table built one
    off).  Labels and gains stay where they are."""
    import jax.numpy as jnp

    def late(real, score, qidx, *a, **kw):
        last = score.shape[0] - 1
        return real(score, jnp.where(qidx >= 0, jnp.minimum(qidx + 1, last), -1),
                    *a, **kw)
    _wrap_bucket(setattr_, late)


def drop_delta_ndcg(setattr_) -> None:
    """The pair weight ``|dNDCG|`` is dropped: every pair of another label
    weighs the same (RankNet's gradient under LambdaMART's name).  Planted
    in the function's SOURCE, one line of it: the weight is a local value
    no argument reaches."""
    from lightgbm_tpu import objectives
    src = textwrap.dedent(inspect.getsource(objectives._lambdarank_bucket))
    old = "        * inv                                            # [nq_b, T, Q]"
    assert src.count(old) == 1, "objectives._lambdarank_bucket changed its text"
    scope = dict(vars(objectives))
    exec(compile(src.replace(old, "        * 0.0 + inv"), objectives.__file__,
                 "exec"), scope)
    setattr_(objectives, "_lambdarank_bucket", scope["_lambdarank_bucket"])


def ndcg_one_ideal(setattr_) -> None:
    """The device NDCG divides every cut-off's DCG by the LAST cut-off's
    ideal DCG: NDCG@1, @3 and @5 read low."""
    import jax.numpy as jnp
    from lightgbm_tpu import metrics
    real = metrics._dev_ndcg_sums

    def sums(ks):
        fn = real(ks)
        return lambda score, qidx, gain_slot, idcgs, disc: fn(
            score, qidx, gain_slot, jnp.broadcast_to(idcgs[-1:], idcgs.shape),
            disc)
    setattr_(metrics, "_dev_ndcg_sums", sums)


def ignore_min_hessian(setattr_) -> None:
    """The split search takes no notice of ``min_sum_hessian_in_leaf``
    (the job's 100 never reaches it: the default's 1e-3 does): children
    of a handful of docs, which the published job does not grow."""
    import dataclasses
    from lightgbm_tpu.boosting import gbdt
    real = gbdt._hp_from_config
    setattr_(gbdt, "_hp_from_config", lambda cfg, n_bins: dataclasses.replace(
        real(cfg, n_bins), min_sum_hessian_in_leaf=1e-3))


GRADIENTS = ("no_normalisation", "truncation_quadrupled", "drop_max_dcg",
             "ties_reversed", "boundary_off_by_one", "drop_delta_ndcg")
