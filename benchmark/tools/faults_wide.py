"""Faults planted in the timed path of a wide dense job (the cell
``epsilon-train``, PR 45): what goes wrong when a histogram kernel's
output is blocked over the columns, and when the per-leaf state is
updated in place.  Each takes ``setattr_`` like ``tools/faults.py``'s
(pytest's ``monkeypatch.setattr`` or ``faults.Planted``) and a
``feature``: the column where the fault should hurt most (the direction's
heaviest, ``datagen/class_gaussian.py`` ``strongest_feature``).

The column blocks are the program's own (``ops/hist_pallas.py``
``col_blocks`` for a compacted pass over the split batch's leaves): at
2,000 columns 63 blocks of 32, the heaviest column's (1,812) from 1,792.
"""

from __future__ import annotations

from faults import _on_histograms


def _blocks(num_f: int, batch: int = 42, bins: int = 256):
    """``(columns a block, blocks)`` of a K-leaf pass at this width."""
    from lightgbm_tpu.ops import hist_pallas
    return hist_pallas.col_blocks(4 * -(-num_f // 4), 3 * batch * bins * 4, 4)


def drop_col_block(setattr_, feature: int) -> None:
    """The histogram of the column block that holds ``feature`` never
    leaves the kernel: its columns come out as zeros (no rows, no
    gradient), in the root's histogram and in every pass's.  The split
    search never finds a split there."""
    def change(h):
        cb, _ = _blocks(h.shape[-3])
        lo = feature // cb * cb
        return h.at[..., lo:lo + cb, :, :].set(0.0)
    _on_histograms(setattr_, change)


def shift_col_block(setattr_, feature: int) -> None:
    """The column block that holds ``feature`` is written one column off:
    inside it column f carries column f - 1's histogram (a block's offset
    counted in columns where the kernel counts in chunks).  The search
    states splits on a column from the sums of its neighbour."""
    import jax.numpy as jnp

    def change(h):
        cb, _ = _blocks(h.shape[-3])
        lo = feature // cb * cb
        hi = min(lo + cb, h.shape[-3])
        return h.at[..., lo:hi, :, :].set(
            jnp.roll(h[..., lo:hi, :, :], 1, axis=-3))
    _on_histograms(setattr_, change)


def skip_state_update(setattr_, feature: int = 0) -> None:
    """The per-leaf state's update is skipped for ONE slot of every round
    (slot 0: the split of most gain): its children's histograms are never
    written, so the left child's place keeps the parent's sums and the
    right child's whatever was there; what is split off them later is the
    wrong difference."""
    from lightgbm_tpu.learner import batch_grower
    real = batch_grower.write_children

    def skipping(hist, parents, new_leaves, valid, h_left, h_right):
        return real(hist, parents, new_leaves, valid.at[0].set(False),
                    h_left, h_right)
    setattr_(batch_grower, "write_children", skipping)


WIDE = {"drop_col_block": drop_col_block,
        "shift_col_block": shift_col_block,
        "skip_state_update": skip_state_update}
