"""Histogram kernels whose OUTPUT is blocked over the columns (PR 45).

Every kernel of ops/hist_pallas.py takes a grid of (column blocks, row
blocks), rows innermost, with the accumulator of one column block resident
while its rows stream; the block's width follows from the one VMEM budget
(``VMEM_BUDGET_BYTES``) by ``col_blocks``.  Here each kernel runs in
interpret mode against the XLA one-hot histogram, bit for bit (integer
gradients: every sum exact), at widths that give one block, several whole
blocks and a ragged last block, and at the Epsilon job's 2,000 columns;
one tree grown through the kernels at 2,000 columns is the tree the
sorted gather grows.  What the chip's compiler makes of the same kernels
at (2000, 400000) is tests/test_chip_compile.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import numpy.testing as npt
import pytest

from lightgbm_tpu.learner.batch_grower import grow_tree_batched
from lightgbm_tpu.ops import hist_pallas as HP
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops import round_fuse as RF
from lightgbm_tpu.ops.split import SplitHyper


def _problem(num_f, n, K, n_bins=256, seed=0):
    rng = np.random.default_rng([seed, num_f, n, K])
    bins = rng.integers(0, n_bins, (n, num_f)).astype(np.uint8)
    bins[rng.random((n, num_f)) < 0.05] = n_bins - 1
    grad = rng.integers(-8, 8, n).astype(np.float32)
    hess = rng.integers(0, 8, n).astype(np.float32)
    lor = rng.integers(-1, K + 2, n).astype(np.int32)
    leaves = rng.choice(K + 2, K, replace=False).astype(np.int32)
    return tuple(jnp.asarray(a) for a in (bins, grad, hess, lor, leaves))


def _xla(bins, grad, hess, lor, leaves, n_bins=256):
    """The XLA one-hot histogram: what ``hist_dispatch`` answers off the
    TPU, f32 [K, F, B, 4]."""
    return np.asarray(H.histogram_for_leaves_masked(
        bins.T, grad, hess, lor, leaves, None, n_bins=n_bins,
        rows_per_block=512, hist_dtype="float32", hist_kernel="onehot"))


@pytest.fixture
def small_budget(monkeypatch):
    """A budget under which 300 columns of a K = 5 pass do not fit one
    block and take ten of 32 columns, the last with 12 of them.  (The budget is read when a
    kernel is traced, and the kernels are jitted: the problems here have
    712 rows, which no other test's have.)"""
    body = HP._VMEM_BODY_BYTES
    per_col = 3 * 5 * 256 * 4
    monkeypatch.setattr(HP, "VMEM_BUDGET_BYTES", body + 2 * 64 * per_col)
    assert HP.col_blocks(300, per_col, 4) == (32, 10)


def _flat(p, cd, **kw):
    bins, grad, hess, lor, leaves = p
    return HP.histogram_leaves_pallas(
        bins.T, grad, hess, lor, leaves, n_bins=256, rows_per_block=256,
        compute_dtype=cd, interpret=True, **kw)


def _packed(p, cd):
    bins, grad, hess, lor, leaves = p
    return HP.histogram_leaves_packed_pallas(
        H.bins_to_words(bins).T, grad, hess, lor, leaves,
        num_f=bins.shape[1], n_bins=256, rows_per_block=256,
        compute_dtype=cd, interpret=True)


def _radix2(p, cd):
    bins, grad, hess, lor, leaves = p
    return HP.histogram_leaves_radix2_pallas(
        bins.T, grad, hess, lor, leaves, n_bins=256, rows_per_block=256,
        p=2, compute_dtype=cd, interpret=True)


def _joint(p, cd):
    bins, grad, hess, lor, leaves = p
    return HP.histogram_radix_joint_pallas(
        bins.T, grad, hess, lor, leaves, n_bins=256, rows_per_block=256,
        compute_dtype=cd, interpret=True)


def _payload(p, cd, from_bytes):
    """The compacted pass: the selected rows streamed into the payload
    from either resident source, then the payload kernel on the bucket."""
    bins, grad, hess, lor, leaves = p
    n = grad.shape[0]
    sel = np.isin(np.asarray(lor), np.asarray(leaves))
    iota = np.arange(n, dtype=np.int32)
    key = jnp.asarray(np.where(sel, iota, iota | (1 << 30)))
    size = HP._round_up(int(sel.sum()), 256)
    src = bins.T if from_bytes else H.bins_to_words(bins).T
    pc = HP.compact_payload_pallas(src, key, grad, hess, lor, size=size,
                                   interpret=True)
    return HP.histogram_payload_pallas(
        pc, leaves, jnp.int32(sel.sum()), num_f=bins.shape[1], n_bins=256,
        rows_per_block=256, compute_dtype=cd, interpret=True)


KERNELS = {
    "flat": _flat, "packed": _packed, "radix2": _radix2,
    "radix_joint": _joint,
    "payload_words": lambda p, cd: _payload(p, cd, False),
    "payload_bytes": lambda p, cd: _payload(p, cd, True),
}


@pytest.mark.parametrize("num_f,blocks", [(40, 1), (256, 8), (300, 10)],
                         ids=["one-block", "whole-blocks", "ragged-last"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_blocked_kernel_is_the_xla_histogram(small_budget, kernel, num_f,
                                             blocks):
    """One block, several whole blocks, a ragged last block: bit for bit
    the XLA one-hot histogram, in int8 (exact i32 sums) mode."""
    p = _problem(num_f, 712, 5)
    want = _xla(*p)
    got = np.asarray(KERNELS[kernel](p, jnp.int8))
    npt.assert_array_equal(got, want)
    if kernel in ("flat", "packed") or kernel.startswith("payload"):
        assert HP.col_blocks(HP._round_up(num_f, 4), 3 * 5 * 256 * 4,
                             4)[1] == blocks


@pytest.mark.parametrize("kernel", ["flat", "payload_bytes"])
def test_blocked_kernel_in_float32(small_budget, kernel):
    """The float accumulator's blocks (integer-valued gradients: exact)."""
    p = _problem(300, 712, 5, seed=1)
    npt.assert_array_equal(np.asarray(KERNELS[kernel](p, jnp.float32)),
                           _xla(*p))


def test_root_kernel_blocked(small_budget):
    """The one-leaf radix kernel of the root pass over ten blocks."""
    bins, grad, hess, lor, _ = _problem(300, 712, 1)
    got = HP.histogram_radix_single_pallas(
        bins.T, grad, hess, lor, n_bins=256, rows_per_block=256,
        compute_dtype=jnp.int8, interpret=True)
    want = _xla(bins, grad, hess, jnp.where(lor >= 0, 0, -1),
                jnp.zeros((1,), jnp.int32))[0]
    assert HP.col_blocks(300, 64 * 192, 4)[1] > 1
    npt.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kernel", ["flat", "radix_joint", "payload_bytes",
                                    "payload_words", "packed"])
def test_kernels_at_2000_columns(kernel):
    """The Epsilon job's width under the REAL budget: the K = 42 flat and
    payload passes the cell runs and the K = 4 radix pass take 63 blocks of
    32 columns, the last with 16; so do the packed-word kernels (not the
    cell's: 255 bins keep no mirror) at K = 8.  520 rows: two whole row
    blocks and a ragged one under every column block, which is all the
    rows a column block's accumulator has to live through (the time here
    is the interpreter's, a grid step at a time: 63 x 3 of them)."""
    K = {"radix_joint": 4, "flat": 42, "payload_bytes": 42}.get(kernel, 8)
    p = _problem(2000, 520, K, seed=2)
    want = _xla(*p)
    got = np.asarray(KERNELS[kernel](p, jnp.int8))
    npt.assert_array_equal(got, want)


def test_the_rule_at_the_cells_shapes():
    """Every accepted cell's widest pass stays ONE column block (the
    parent's kernels, the limit stated); Epsilon's are blocked, and no
    block with its second buffer passes the budget."""
    acc = lambda K: 3 * K * 256 * 4
    for f_pad in (70, 68, 48, 32, 220):       # flat / payload columns
        assert HP.col_blocks(f_pad, acc(42), 4)[1] == 1
    # istella-rank-train's 27.1 MiB accumulator: stated, no longer by leave
    assert HP.vmem_limit(220 * acc(42)) == 220 * acc(42) + HP._VMEM_BODY_BYTES
    assert HP.vmem_limit(70 * acc(42)) < (25 << 20)
    cb, ncb = HP.col_blocks(2000, acc(42), 16)
    assert (cb, ncb) == (32, 63)
    assert HP.vmem_limit(cb * acc(42), ncb) == \
        2 * cb * acc(42) + HP._VMEM_BODY_BYTES <= HP.VMEM_BUDGET_BYTES
    assert HP.col_blocks(2000, 64 * 192, 4) == (32, 63)       # the root
    assert HP.col_blocks(2000, 4 * 64 * 192, 4) == (32, 63)   # K = 4
    # the unroll is a block's: at most 64 contractions a kernel body where
    # one block holds every column, one tile of columns (what the chip's
    # compiler takes its time over) where a pass takes several
    for cols, col_bytes, chunk in ((2000, acc(42), 16), (2000, acc(42), 4),
                                   (2000, 64 * 192, 4), (4228, acc(42), 4),
                                   (257, 64 * 192, 4)):
        cb, ncb = HP.col_blocks(cols, col_bytes, chunk)
        assert (cb, ncb) == (HP._COL_TILE, -(-cols // HP._COL_TILE))
    assert HP.col_blocks(256, 64 * 192, 4) == (256, 1)    # 64 contractions


def test_one_tree_at_2000_columns_through_the_blocked_kernels():
    """F = 2000 gives 500 packed words and 504 payload rows (sixteen
    plane groups in ``compact_payload_pallas``), 63 column blocks in every
    histogram kernel.  The tree grown through the
    kernels (interpret mode) is the tree the sorted gather grows."""
    rng = np.random.default_rng(3)
    n, f = 1000, 2000
    bins = jnp.asarray(rng.integers(0, 255, size=(n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.integers(-2, 3, size=n).astype(np.float32))
    hess = jnp.asarray(rng.integers(1, 5, size=n).astype(np.float32))
    args = (bins, grad, hess, None, jnp.full((f,), 256, jnp.int32),
            jnp.full((f,), -1, jnp.int32), jnp.zeros((f,), bool), None,
            SplitHyper(num_leaves=7, min_data_in_leaf=5, n_bins=256,
                       hist_dtype="float32"))
    t0, lor0 = grow_tree_batched.__wrapped__(*args, batch=4)
    H._PAYLOAD_TEST_INTERPRET = True
    H._MODE_TEST_INTERPRET = True
    RF._FUSE_TEST_INTERPRET = True
    try:
        t1, lor1 = grow_tree_batched.__wrapped__(*args, batch=4)
    finally:
        H._PAYLOAD_TEST_INTERPRET = False
        H._MODE_TEST_INTERPRET = False
        RF._FUSE_TEST_INTERPRET = False
    for name in ("split_feature", "split_bin", "leaf_value", "leaf_count"):
        npt.assert_array_equal(np.asarray(getattr(t0, name)),
                               np.asarray(getattr(t1, name)))
    npt.assert_array_equal(np.asarray(lor0), np.asarray(lor1))
