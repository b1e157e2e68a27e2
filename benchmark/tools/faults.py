"""Faults planted in the program's timed path, for the readings the
limits of ``correct`` are set against (tools/control.py) and for the
tests that a broken run reports ``correct: false`` (tests/).  Each takes
``setattr(object, name, value)``: pytest's ``monkeypatch.setattr``, or
``Planted`` below, which puts everything back."""

from __future__ import annotations


class Planted:
    """``with Planted() as plant: hist_zero_feature(plant, 3)``."""

    def __init__(self):
        self._undo = []

    def __call__(self, obj, name, value):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for obj, name, old in reversed(self._undo):
            setattr(obj, name, old)


def _on_histograms(setattr_, change) -> None:
    """``change(hist)`` on every histogram the batched grower is handed:
    the root's ``[F, B, C]`` and the children's ``[K, F, B, C]``, channel
    0 the gradient sums."""
    from lightgbm_tpu.learner import batch_grower
    for name in ("root_histogram", "histogram_for_leaves_auto"):
        real = getattr(batch_grower, name)
        setattr_(batch_grower, name,
                 lambda *a, _real=real, **kw: change(_real(*a, **kw)))


def hist_zero_feature(setattr_, feature: int) -> None:
    """The histogram kernels lose one feature's gradient sums: the split
    search never sees a gain there."""
    _on_histograms(setattr_, lambda h: h.at[..., feature, :, 0].set(0.0))


def hist_scale_feature(setattr_, feature: int, by: float = 2.0) -> None:
    """One feature's gradient sums come out mis-scaled (a wrong shift of
    a packed word): its gains are overstated and it wins where it should
    not."""
    _on_histograms(setattr_, lambda h: h.at[..., feature, :, 0].multiply(by))


def hist_drop_upper_bins(setattr_) -> None:
    """The gradient sums of the upper half of every feature's bins are
    lost (a truncated radix pass)."""
    _on_histograms(setattr_, lambda h: h.at[..., h.shape[-2] // 2:, 0].set(0.0))


def drop_score_update(setattr_) -> None:
    """The step returns its state unchanged: the training scores never
    take a tree's leaf values."""
    import jax.numpy as jnp
    from lightgbm_tpu.boosting import gbdt
    setattr_(gbdt, "take_small_table",
             lambda table, idx: jnp.zeros(idx.shape, table.dtype))


def alter_leaf_values(setattr_, by: float = 1.01) -> None:
    """An answer altered where it is produced: every tree's leaf values
    come to the host 1% too large."""
    from lightgbm_tpu.models.tree import Tree
    real = Tree.from_arrays.__func__

    def altered(cls, arrays, dataset):
        return real(cls, arrays._replace(leaf_value=arrays.leaf_value * by),
                    dataset)
    setattr_(Tree, "from_arrays", classmethod(altered))


HISTOGRAM = {"hist_zero_feature": hist_zero_feature,
             "hist_scale_feature": hist_scale_feature,
             "hist_drop_upper_bins": lambda s, feature: hist_drop_upper_bins(s)}
