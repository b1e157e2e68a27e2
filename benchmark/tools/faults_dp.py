"""Faults of a job that is split over chips, planted as ``faults.py``
plants its own (each takes ``setattr(object, name, value)``).  The
program's compiled runners hold what was traced: clear them
(``harness.program.free_everything``) before a planted job and after."""

from __future__ import annotations


def hist_drop_shard(setattr_, shard: int = 1) -> None:
    """One shard's histograms are left out of every sum over the mesh
    (a chip that missed the exchange): the counts, gradient sums and
    splits the other shards see lack that shard's rows."""
    import jax.numpy as jnp
    from jax import lax
    from lightgbm_tpu.ops import histogram
    real = histogram.reduce_hist

    def dropped(hist, axis_name, overlap=False):
        if axis_name is not None:
            hist = jnp.where(lax.axis_index(axis_name) == shard,
                             jnp.zeros_like(hist), hist)
        return real(hist, axis_name, overlap)
    setattr_(histogram, "reduce_hist", dropped)
