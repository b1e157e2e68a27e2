"""Round-fusion kernels (VERDICT r3 perf items a+c), exercised on CPU via
the Pallas interpreter: the payload histogram kernel, the compaction
kernel that feeds it and the fused partition+key kernel must be
bit-identical to the XLA reference paths.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu.ops.histogram as H
import lightgbm_tpu.ops.round_fuse as RF
from lightgbm_tpu.ops.hist_pallas import (compact_payload_pallas,
                                          compaction_ranks,
                                          histogram_payload_pallas)
from lightgbm_tpu.ops.split import SplitHyper
from lightgbm_tpu.learner import grower
from lightgbm_tpu.learner.batch_grower import grow_tree_batched


def _mk(n=4096, f=9, n_bins=64, k=4, seed=0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins - 1, size=(n, f)).astype(np.uint8)
    grad = rng.integers(-3, 4, size=n).astype(np.float32)
    hess = rng.integers(1, 5, size=n).astype(np.float32)
    lor = rng.integers(-1, 7, size=n).astype(np.int32)   # -1 = masked out
    leaves = np.array([0, 2, 5, 6][:k], np.int32)
    return (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(lor), jnp.asarray(leaves))


def test_payload_kernel_matches_masked_reference():
    bins, grad, hess, lor, leaves = _mk()
    n, f = bins.shape
    words = H.bins_to_words(bins)
    key = jnp.where(
        jnp.any(lor[None, :] == leaves[:, None], axis=0),
        jnp.arange(n, dtype=jnp.int32),
        jnp.arange(n, dtype=jnp.int32) | (1 << 30))
    cnt = jnp.sum(jnp.any(lor[None, :] == leaves[:, None], axis=0)
                  .astype(jnp.int32))
    S = 2560
    assert int(cnt) <= S
    payload = jnp.concatenate([
        words,
        jax.lax.bitcast_convert_type(grad, jnp.int32)[:, None],
        jax.lax.bitcast_convert_type(hess, jnp.int32)[:, None],
        lor[:, None]], axis=1)
    idxc = jnp.sort(key, stable=False)[:S] & ((1 << 30) - 1)
    pc = payload[idxc]
    got = histogram_payload_pallas(pc.T, leaves, cnt, num_f=f, n_bins=64,
                                   rows_per_block=512,
                                   compute_dtype=jnp.float32,
                                   interpret=True)
    want = H.histogram_for_leaves_masked(
        bins.T, grad, hess, lor, leaves, None, n_bins=64,
        hist_dtype="float32")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


PAYLOAD_BLK = 512
PAYLOAD_S = 5 * PAYLOAD_BLK + 128   # the sixth block reaches 384 past S


@pytest.mark.parametrize("hist_dtype", ["int8", "float32"])
@pytest.mark.parametrize("cnt", [0, 1, PAYLOAD_BLK - 1, PAYLOAD_BLK,
                                 PAYLOAD_BLK + 1, PAYLOAD_S - 1, PAYLOAD_S],
                         ids=["0", "1", "blk-1", "blk", "blk+1", "S-1", "S"])
def test_payload_kernel_stops_at_the_count(cnt, hist_dtype):
    """The grid steps from ``cnt`` on are skipped and fetch nothing; the
    result is the XLA masked pass over the positions below ``cnt``, bit
    for bit (integer levels: float32 sums are exact in any order), with
    NaN values and leaf ids that DO match in the columns from ``cnt`` on
    and a last block that reaches past S."""
    rng = np.random.default_rng(11)
    S, f, n_bins = PAYLOAD_S, 9, 64
    bins, grad, hess, lor, leaves = _mk(n=S, f=f, n_bins=n_bins, seed=11)
    pc_t = np.concatenate([
        np.asarray(H.bins_to_words(bins)).T,
        np.asarray(grad).view(np.int32)[None],
        np.asarray(hess).view(np.int32)[None], np.asarray(lor)[None]])
    w = pc_t.shape[0] - 3
    # what the compaction leaves past the count is anything at all
    pc_t[:w, cnt:] = rng.integers(-2 ** 31, 2 ** 31, size=(w, S - cnt))
    pc_t[w:w + 2, cnt:] = np.float32(np.nan).view(np.int32)
    pc_t[w + 2, cnt:] = np.asarray(leaves)[rng.integers(0, 4, size=S - cnt)]
    got = histogram_payload_pallas(
        jnp.asarray(pc_t), leaves, jnp.int32(cnt), num_f=f,
        n_bins=n_bins, rows_per_block=PAYLOAD_BLK,
        compute_dtype=jnp.dtype(hist_dtype).type, interpret=True)
    want = H.histogram_for_leaves_masked(
        bins[:cnt].T, grad[:cnt], hess[:cnt], lor[:cnt], leaves, None,
        n_bins=n_bins, hist_dtype="float32")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------- the compaction kernel
ROWS = 2048 + 994       # the remainder 13,281,250 leaves in a 1024-row block
SIZE = 1024             # the bucket: two 512-column output blocks
BLOCK = 512             # rows a grid step takes: two runs of 256


def _selection(name, n, size, rng):
    sel = np.zeros(n, bool)
    if name.startswith("cnt"):
        cnt = {"cnt0": 0, "cnt1": 1, "cntS-1": size - 1, "cntS": size,
               # exactly one output block; a window's edge inside a block
               "cnt_block": BLOCK, "cnt_window": BLOCK + 128}[name]
        sel[rng.choice(n, cnt, replace=False)] = True
    elif name.startswith("density"):
        sel = rng.random(n) < 1 / int(name[len("density"):])
    elif name == "first":
        sel[:size] = True
    elif name == "last":            # the masked tail of the last block too
        sel[n - size:] = True
    elif name == "one_block":       # every row of the second 512-row block
        sel[512:1024] = True
    elif name == "gaps":            # full blocks, empty blocks between them
        sel[0:BLOCK] = True
        sel[3 * BLOCK:3 * BLOCK + 300] = True
        sel[n - 40:] = True
    elif name == "window_ends":     # runs that end exactly on column 128 k
        sel[0:128] = True
        sel[256:512] = True
        sel[BLOCK:BLOCK + 128] = True
        sel[4 * BLOCK + 7:4 * BLOCK + 7 + 128] = True
    elif name == "excess":          # more rows than columns: the rest drops
        sel = rng.random(n) < 0.6
    return sel


def _payload_t(words, grad, hess, lor):
    return np.concatenate([
        np.asarray(words).T, np.asarray(grad).view(np.int32)[None],
        np.asarray(hess).view(np.int32)[None], np.asarray(lor)[None]])


#: name -> (rows, features, bucket, rows a grid step takes)
SHAPES = {
    "remainder": (ROWS, 10, SIZE, BLOCK),      # 10 % 4 != 0: a padded word
    "below_one_block": (300, 10, 256, BLOCK),
    "blocks_plus_1": (3 * BLOCK + 1, 9, SIZE, BLOCK),
    # the steps the program runs, eight runs each but the last: runs of
    # 256 rows (a bucket over n/3), 512 (over n/6) and 1024 (four runs)
    "default_step": (3 * 2048 + 994, 12, 4096, 4096),
    "runs_of_512": (3 * 2048 + 994, 12, 2048, 4096),
    "runs_of_1024": (3 * 2048 + 994, 12, 1024, 4096),
    # F > 116: a second byte-plane group, u8 rows past one 128-row tile
    "two_groups": (700, 130, 256, 256),
}
CASES = [("remainder", place) for place in (
    "cnt0", "cnt1", "cntS-1", "cntS", "density8", "density4", "first",
    "last", "one_block", "cnt_block", "cnt_window", "gaps", "window_ends",
    "excess")]
CASES += [("below_one_block", "density4"), ("below_one_block", "first"),
          ("blocks_plus_1", "last"), ("blocks_plus_1", "density4"),
          ("default_step", "density4"), ("default_step", "density2"),
          ("default_step", "gaps"), ("default_step", "first"),
          ("runs_of_512", "density4"), ("runs_of_512", "gaps"),
          ("runs_of_512", "first"), ("runs_of_1024", "density8"),
          ("runs_of_1024", "gaps"), ("runs_of_1024", "first"),
          ("two_groups", "density4"), ("two_groups", "first")]


@pytest.mark.parametrize("source", ["bins_t", "words_t"])
@pytest.mark.parametrize("shape,place", CASES,
                         ids=[f"{s}-{p}" if s != "remainder" else p
                              for s, p in CASES])
def test_compaction_kernel_matches_sorted_gather(shape, place, source):
    """The first ``cnt`` columns are bit for bit the row-major payload's
    rows at the sorted keys, for any placement of the selected rows (runs
    of empty blocks, counts on an output block's and a window's edge, n
    below a block and one past a block, more rows than the bucket has
    columns); the float operands move as their bits (no rounding, NaN
    included)."""
    rng = np.random.default_rng(7)
    n, f, size, block = SHAPES[shape]
    bins = rng.integers(0, 256, size=(n, f)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.integers(-2 ** 31, 2 ** 31, size=n).astype(np.int32).view(
        np.float32)                       # any bit pattern
    lor = rng.integers(-1, 255, size=n).astype(np.int32)
    sel = _selection(place, n, size, rng)
    cnt = min(int(sel.sum()), size)
    assert (sel.sum() > size) == (place == "excess")
    rows = np.arange(n, dtype=np.int32)
    key = np.where(sel, rows, rows | (1 << 30)).astype(np.int32)
    words = H.bins_to_words(jnp.asarray(bins))
    src = jnp.asarray(bins.T) if source == "bins_t" else words.T
    got = np.asarray(compact_payload_pallas(
        src, jnp.asarray(key), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(lor), size=size, rows_per_block=block, interpret=True))
    w = words.shape[1]
    assert got.shape[0] == 8 * -(-(w + 3) // 8) and got.shape[1] >= size
    want = _payload_t(words, grad, hess, lor)[
        :, np.sort(key)[:cnt] & ((1 << 30) - 1)]
    np.testing.assert_array_equal(got[:w + 3, :cnt], want)


@pytest.mark.parametrize("n,block,run", [
    (ROWS, BLOCK, 256), (300, BLOCK, 256), (3 * BLOCK + 1, BLOCK, 256),
    (128, 128, 128), (40_000, 2048, 256)],
    ids=["remainder", "below_one_block", "blocks_plus_1", "one_tile",
         "default_step"])
def test_compaction_ranks_match_numpy(n, block, run):
    """What precedes the kernel, on the keys alone: each selected row's
    output column (its rank in row order), -1 for the others and for the
    pad up to a whole block, and the count before every run of rows."""
    rng = np.random.default_rng(n)
    sel = rng.random(n) < 0.3
    sel[n // 2:n // 2 + 2 * run] = True       # runs with every row
    sel[n // 4:n // 4 + 2 * run] = False      # and with none
    rows = np.arange(n, dtype=np.int32)
    key = np.where(sel, rows, rows | (1 << 30)).astype(np.int32)
    t, cum = compaction_ranks(jnp.asarray(key), rows_per_block=block,
                              rows_per_dot=run)
    n_pad = -(-n // block) * block
    sel_pad = np.zeros(n_pad, bool)
    sel_pad[:n] = sel
    before = np.cumsum(sel_pad) - sel_pad
    np.testing.assert_array_equal(
        np.asarray(t), np.where(sel_pad, before, -1)[None])
    np.testing.assert_array_equal(
        np.asarray(cum), np.append(before[::run], sel.sum()))


@pytest.mark.parametrize("hist_dtype", ["int8", "float32"])
@pytest.mark.parametrize("mirror", [False, True], ids=["bins_t", "words_t"])
@pytest.mark.parametrize("divisor", [4, 8, 16, 64])
def test_auto_through_each_compacted_bucket_equals_the_full_pass(
        divisor, mirror, hist_dtype):
    """``histogram_for_leaves_auto`` with the kernels (interpret mode):
    a selection that lands in the bucket n / divisor gives the full
    masked pass's histograms exactly (integer fixtures)."""
    bins, grad, hess, _, leaves = _mk(n=16384 + 994)
    n = bins.shape[0]
    rng = np.random.default_rng(divisor)
    # about 0.8 of the bucket's rows in the four leaves, the rest elsewhere
    lor = np.where(rng.random(n) < 0.8 / divisor,
                   np.asarray(leaves)[rng.integers(0, 4, size=n)], 1)
    lor = jnp.asarray(lor.astype(np.int32))
    want = H.histogram_for_leaves_masked(
        bins.T, grad, hess, lor, leaves, None, n_bins=64,
        hist_dtype="float32")
    H._PAYLOAD_TEST_INTERPRET = True
    try:
        got = H.histogram_for_leaves_auto(
            bins, bins.T, grad, hess, lor, leaves, None, n_bins=64,
            rows_per_block=256, hist_dtype=hist_dtype, buckets=(divisor,),
            bins_words_t=H.bins_to_words(bins).T if mirror else None,
            hist_kernel="onehot")
    finally:
        H._PAYLOAD_TEST_INTERPRET = False
    cnt = int(jnp.sum(jnp.any(lor[None, :] == leaves[:, None], axis=0)))
    size = -(-(n // divisor) // 256) * 256
    assert 0 < cnt <= size < n, "the fixture misses the bucket"
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@functools.lru_cache(maxsize=None)
def _ladder(n_bins):
    """``histogram_for_leaves_auto`` through the kernels (interpret mode)
    under ONE jit for all the cases of a bin count: they differ in which
    rows are selected, not in a shape.  ``ran`` takes the size of every
    compaction that RUNS (a callback from inside the taken branch)."""
    import lightgbm_tpu.ops.hist_pallas as HP
    ran = []
    real = HP.compact_payload_pallas

    def spy(src, key, *rest, size, **kw):
        jax.debug.callback(lambda _: ran.append(size), key[0])
        return real(src, key, *rest, size=size, **kw)

    @jax.jit
    def auto(bins, grad, hess, lor, leaves):
        # both are read when this is traced, which is once
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(HP, "compact_payload_pallas", spy)
            patch.setattr(H, "_PAYLOAD_TEST_INTERPRET", True)
            return H.histogram_for_leaves_auto(
                bins, bins.T, grad, hess, lor, leaves, None, n_bins=n_bins,
                rows_per_block=256, hist_dtype="int8", hist_kernel="onehot")
    return auto, ran


@pytest.mark.parametrize("n_bins,share,branch", [
    (128, 0.40, "n/2"), (128, 0.60, "full"), (128, 0.20, "n/4"),
    (64, 0.40, "full"), (64, 0.20, "n/4"),
], ids=["128bins-0.40n", "128bins-0.60n", "128bins-0.20n", "64bins-0.40n",
        "64bins-0.20n"])
def test_auto_starts_the_ladder_where_the_dispatch_says(n_bins, share, branch):
    """The flat kernel's full pass above 64 bins is dear enough for a
    bucket of n/2 (``hist_dispatch(...).top_rung == 2``): a count in
    (n/4, n/2] takes it, a count above n/2 the full pass.  At 64 bins
    the ladder starts at n/4 as before.  Which branch RAN is told by the
    compaction it called; the histograms equal the masked pass's either
    way."""
    bins, grad, hess, _, leaves = _mk(n=8192 + 994, n_bins=n_bins)
    n = bins.shape[0]
    top = H.hist_dispatch("onehot", n_bins, 4, bins.shape[1]).top_rung
    assert top == (2 if n_bins > 64 else 4)
    rng = np.random.default_rng(5)
    lor = np.where(rng.random(n) < share,
                   np.asarray(leaves)[rng.integers(0, 4, size=n)], 1)
    lor = jnp.asarray(lor.astype(np.int32))
    auto, ran = _ladder(n_bins)
    ran.clear()
    got = auto(bins, grad, hess, lor, leaves)
    jax.effects_barrier()
    want = H.histogram_for_leaves_masked(
        bins.T, grad, hess, lor, leaves, None, n_bins=n_bins,
        hist_dtype="float32")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    cnt = int(jnp.sum(jnp.any(lor[None, :] == leaves[:, None], axis=0)))
    sizes = {b: -(-(n // d) // 256) * 256 for b, d in (("n/2", 2), ("n/4", 4))}
    if branch == "full":
        assert cnt > sizes["n/2" if top == 2 else "n/4"] and ran == []
    else:
        assert ran == [sizes[branch]]
        assert sizes[branch] >= cnt > sizes[branch] // 2, "fixture"


def test_bins_to_words_roundtrip():
    bins, *_ = _mk(f=10)  # 10 % 4 != 0: exercises the pad
    words = H.bins_to_words(bins)
    n, f = bins.shape
    back = jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(
        n, -1)[:, :f]
    np.testing.assert_array_equal(np.asarray(back), np.asarray(bins))


@pytest.mark.parametrize("n", [2048, 3000, 1537, 300])
def test_partition_kernel_matches_xla(n):
    """Four slots: a missing bin that is NOT the last (the zero bin, as
    ``zero_as_missing`` puts it) going left, no missing bin, a missing bin
    that is the last going left past the threshold, and a disabled slot
    whose parent has rows.  Over the tail of the last block of 512 rows,
    which the kernel's grid reads ragged from the unpadded operands: none,
    440 rows, ONE row, and a row count below one block."""
    rng = np.random.default_rng(3)
    f, K = 7, 4
    bins = rng.integers(0, 32, size=(n, f)).astype(np.uint8)
    lor = rng.integers(0, 5, size=n).astype(np.int32)
    mask = rng.integers(0, 2, size=n).astype(np.int32)
    feats = np.array([2, 0, 5, 6], np.int32)
    thr = np.array([10, 3, 20, 12], np.int32)
    dl = np.array([1, 0, 1, 0], np.int32)
    nanb = np.array([0, -1, 31, 31], np.int32)
    parents = np.array([1, 3, 4, 2], np.int32)
    new_leaves = np.array([5, 6, 7, 8], np.int32)
    validk = np.array([1, 1, 1, 0], np.int32)
    smaller = np.array([1, 6, 7, 8], np.int32)

    new_lor, key = RF.partition_select_pallas(
        jnp.asarray(bins.T), jnp.asarray(lor), jnp.asarray(mask),
        *grower.split_ranges(jnp.asarray(feats), jnp.asarray(thr),
                             jnp.asarray(dl),
                             jnp.full((f,), -1).at[feats].set(nanb), None, 32),
        jnp.asarray(parents), jnp.asarray(new_leaves),
        jnp.asarray(validk), jnp.asarray(smaller),
        rows_per_block=512, interpret=True)

    # XLA reference (the batch grower's original partition math)
    cols = bins[:, feats].T.astype(np.int32)                  # [K, n]
    go_left = np.where(cols == nanb[:, None], dl[:, None] != 0,
                       cols <= thr[:, None])
    in_par = (lor[None, :] == parents[:, None]) & (validk[:, None] != 0)
    move = in_par & ~go_left
    tgt = (move * new_leaves[:, None]).sum(axis=0)
    want_lor = np.where(move.any(axis=0), tgt, lor)
    np.testing.assert_array_equal(np.asarray(new_lor), want_lor)

    lor_m = np.where(mask != 0, want_lor, -1)
    sel = (lor_m[None, :] == smaller[:, None]).any(axis=0)
    rows = np.arange(n, dtype=np.int32)
    want_key = np.where(sel, rows, rows | (1 << 30))
    np.testing.assert_array_equal(np.asarray(key), want_key)


@pytest.mark.parametrize("budget_mib,n", [(72, 3000), (18, 2900)],
                         ids=["whole-row-block", "row-block-from-width"])
def test_partition_kernel_at_2000_columns(monkeypatch, budget_mib, n):
    """The Epsilon job's width: the block of bins with its two casts is
    8 bytes a column and row, so the row block follows from the width
    under the kernels' one VMEM budget (2,048 rows under 72 MiB, 128 under
    18), the last block ragged as before, and slots on the first, a middle
    and the last column.  (The budget is read when the kernel is traced:
    each case has a row count of its own.)"""
    from lightgbm_tpu.ops import hist_pallas as HP
    monkeypatch.setattr(HP, "VMEM_BUDGET_BYTES", budget_mib << 20)
    rng = np.random.default_rng(11)
    f = 2000
    bins = rng.integers(0, 256, size=(n, f)).astype(np.uint8)
    lor = rng.integers(0, 4, size=n).astype(np.int32)
    mask = rng.integers(0, 2, size=n).astype(np.int32)
    feats = np.array([0, 1812, 1999, 777], np.int32)
    thr = np.array([100, 3, 200, 128], np.int32)
    dl = np.array([0, 0, 1, 0], np.int32)
    parents = np.array([0, 1, 2, 3], np.int32)
    new_leaves = np.array([4, 5, 6, 7], np.int32)
    validk = np.array([1, 1, 1, 0], np.int32)
    smaller = np.array([4, 1, 6, 7], np.int32)
    new_lor, key = RF.partition_select_pallas(
        jnp.asarray(bins.T), jnp.asarray(lor), jnp.asarray(mask),
        *grower.split_ranges(jnp.asarray(feats), jnp.asarray(thr),
                             jnp.asarray(dl), jnp.full((f,), -1), None, 256),
        jnp.asarray(parents), jnp.asarray(new_leaves),
        jnp.asarray(validk), jnp.asarray(smaller),
        rows_per_block=2048, interpret=True)
    go_left = bins[:, feats].T.astype(np.int32) <= thr[:, None]
    move = (lor[None, :] == parents[:, None]) & (validk[:, None] != 0) \
        & ~go_left
    want_lor = np.where(move.any(axis=0),
                        (move * new_leaves[:, None]).sum(axis=0), lor)
    np.testing.assert_array_equal(np.asarray(new_lor), want_lor)
    sel = (np.where(mask != 0, want_lor, -1)[None, :]
           == smaller[:, None]).any(axis=0)
    rows = np.arange(n, dtype=np.int32)
    np.testing.assert_array_equal(np.asarray(key),
                                  np.where(sel, rows, rows | (1 << 30)))


@pytest.mark.parametrize("n", [2048, 3000, 300])
def test_partition_kernel_with_left_sets_matches_xla(n):
    """The variant a job with categorical columns runs: numeric and
    categorical slots side by side.  Slot 0 numeric with a missing bin,
    1 a set that holds its column's LAST bin (255: bit 31 of word 7), 2
    the EMPTY set (every row of the parent moves right), 3 a set of
    scattered bins across words, 4 numeric, 5 a disabled categorical slot
    whose parent has rows; the kernel tests every slot's set, a numeric
    slot's made from its range descriptors (the bits its row of the sets
    held are not looked at, nor a categorical slot's descriptors).
    Over no tail, a ragged tail of the last block of 512 rows, and a row
    count below one block."""
    rng = np.random.default_rng(11)
    f, K, B = 6, 6, 256
    bins = rng.integers(0, 256, size=(n, f)).astype(np.uint8)
    bins[::7, 1] = 255
    lor = rng.integers(0, 7, size=n).astype(np.int32)
    mask = rng.integers(0, 2, size=n).astype(np.int32)
    feats = np.array([2, 1, 4, 0, 5, 3], np.int32)
    thr = np.array([100, 7, 200, 31, 17, 90], np.int32)
    dl = np.array([1, 0, 1, 0, 0, 1], np.int32)
    nanb = np.array([255, -1, -1, 254, -1, -1], np.int32)
    cat = np.array([0, 1, 1, 1, 0, 1], np.int32)
    sets = np.zeros((K, B), bool)
    sets[1, [3, 64, 255]] = True
    sets[3, rng.choice(254, size=40, replace=False)] = True
    sets[5, :128] = True
    sets[0, 5] = sets[4, 9] = True      # a numeric slot's stale bits
    parents = np.array([1, 3, 4, 2, 0, 6], np.int32)
    new_leaves = np.array([7, 8, 9, 10, 11, 12], np.int32)
    validk = np.array([1, 1, 1, 1, 1, 0], np.int32)
    smaller = np.array([1, 8, 9, 2, 11, 12], np.int32)

    words = RF.pack_left_bins(jnp.asarray(sets))
    assert words.shape == (8, K) and words.dtype == jnp.int32
    unpacked = (np.asarray(words).astype(np.uint32).T[:, :, None]
                >> np.arange(32, dtype=np.uint32)) & 1
    np.testing.assert_array_equal(unpacked.reshape(K, B).astype(bool), sets)

    new_lor, key = RF.partition_select_pallas(
        jnp.asarray(bins.T), jnp.asarray(lor), jnp.asarray(mask),
        *grower.split_ranges(jnp.asarray(feats), jnp.asarray(thr),
                             jnp.asarray(dl),
                             jnp.full((f,), -1).at[feats].set(nanb), None, B),
        jnp.asarray(parents), jnp.asarray(new_leaves),
        jnp.asarray(validk), jnp.asarray(smaller), words, jnp.asarray(cat),
        rows_per_block=512, interpret=True)

    # XLA reference (the batch grower's partition by table lookup)
    cols = bins[:, feats].T.astype(np.int32)                  # [K, n]
    go_left = np.where(cols == nanb[:, None], dl[:, None] != 0,
                       cols <= thr[:, None])
    go_left = np.where(cat[:, None] != 0,
                       np.take_along_axis(sets, cols, axis=1), go_left)
    in_par = (lor[None, :] == parents[:, None]) & (validk[:, None] != 0)
    move = in_par & ~go_left
    want_lor = np.where(move.any(axis=0),
                        (move * new_leaves[:, None]).sum(axis=0), lor)
    np.testing.assert_array_equal(np.asarray(new_lor), want_lor)
    assert (want_lor[lor == 4] == 9).all() and (lor == 4).any()   # the empty set
    assert (want_lor[(lor == 3) & (bins[:, 1] == 255)] == 3).all()  # last bin
    lor_m = np.where(mask != 0, want_lor, -1)
    sel = (lor_m[None, :] == smaller[:, None]).any(axis=0)
    rows = np.arange(n, dtype=np.int32)
    np.testing.assert_array_equal(np.asarray(key),
                                  np.where(sel, rows, rows | (1 << 30)))


def test_a_categorical_job_returns_the_model_it_trained():
    """6,000 rows with a 600-level column through ``lgb.train``'s fused
    scan: the trees with the partition in the fused kernel (interpret
    mode) are the XLA path's, and the training scores the job holds are
    what the model it returns says of the raw training rows.  The parent
    folded the 346 rarest levels into the most frequent level's bin:
    wherever a left set held that bin the rows went left in training and
    right in the returned model."""
    import chip_smoke
    import lightgbm_tpu as lgb
    # four numeric columns, then 600 levels (zipf, codes permuted), 3, 20
    X, y = chip_smoke._claims(6000, 0)
    params = dict(objective="binary", metric="auc", num_leaves=15,
                  min_data_in_leaf=5, verbose=-1, tpu_split_batch=4,
                  min_data_per_group=20)

    def train(fused):
        RF._FUSE_TEST_INTERPRET = fused         # read when traced
        jax.clear_caches()
        try:
            ds = lgb.Dataset(X[:5000], label=y[:5000], params=params,
                             categorical_feature=[4, 5, 6])
            dv = ds.create_valid(X[5000:], label=y[5000:])
            evals = {}
            bst = lgb.train(params, ds, num_boost_round=8, valid_sets=[dv],
                            callbacks=[lgb.record_evaluation(evals)])
        finally:
            RF._FUSE_TEST_INTERPRET = False
        return bst, evals["valid_0"]["auc"]

    (xla, auc0), (fused, auc1) = train(False), train(True)
    gb = fused._gbdt
    assert gb.metrics.counter("fused_rounds") == 8
    assert gb.metrics.counter("fused_partition_declined") == 0
    assert xla._gbdt.metrics.counter("fused_partition_declined") == 8
    assert fused.model_to_string() == xla.model_to_string() and auc0 == auc1
    mapper = gb.train_set.mappers[4]
    assert (mapper.num_bin, mapper.other_bin) == (255, 254)
    assert gb.metrics.counter("cat_other_rows") > 0
    used = {(int(t.split_feature[i]), len(t.cat_threshold[int(t.cat_split_index[i])]))
            for t in gb.models for i in range(t.num_leaves - 1)
            if t.decision_type[i] & 1}
    assert any(f == 4 and size > 1 for f, size in used)
    held = np.asarray(gb.scores)[:, 0]
    said = fused.predict(X[:5000], raw_score=True)
    np.testing.assert_allclose(held, said, rtol=0, atol=2e-6)
    jax.clear_caches()


@pytest.mark.parametrize("batch", [4, 8])
def test_fused_round_tree_identical(batch):
    """grow_tree_batched with the fused kernels (interpret mode) produces
    the IDENTICAL tree to the pure-XLA path (integer grads: all sums
    exact, so any divergence is a real bug)."""
    rng = np.random.default_rng(1)
    n, f = 6000, 8
    bins = jnp.asarray(rng.integers(0, 63, size=(n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.integers(-2, 3, size=n).astype(np.float32))
    hess = jnp.asarray(rng.integers(1, 5, size=n).astype(np.float32))
    row_mask = jnp.asarray(rng.integers(0, 2, size=n) > 0)
    num_bins = jnp.full((f,), 64, jnp.int32)
    nan_bin = jnp.full((f,), -1, jnp.int32)
    is_cat = jnp.zeros((f,), bool)
    hp = SplitHyper(num_leaves=31, min_data_in_leaf=5, n_bins=64,
                    hist_dtype="float32")

    t0, lor0 = grow_tree_batched(bins, grad, hess, row_mask, num_bins,
                                 nan_bin, is_cat, None, hp, batch=batch)
    H._PAYLOAD_TEST_INTERPRET = True
    RF._FUSE_TEST_INTERPRET = True
    try:
        # fresh trace: the hooks are read at trace time
        t1, lor1 = grow_tree_batched.__wrapped__(
            bins, grad, hess, row_mask, num_bins, nan_bin, is_cat, None,
            hp, batch=batch)
    finally:
        H._PAYLOAD_TEST_INTERPRET = False
        RF._FUSE_TEST_INTERPRET = False
    np.testing.assert_array_equal(np.asarray(t0.split_feature),
                                  np.asarray(t1.split_feature))
    np.testing.assert_array_equal(np.asarray(t0.split_bin),
                                  np.asarray(t1.split_bin))
    np.testing.assert_array_equal(np.asarray(t0.leaf_value),
                                  np.asarray(t1.leaf_value))
    np.testing.assert_array_equal(np.asarray(lor0), np.asarray(lor1))
    assert int(t0.num_leaves) > 8


@pytest.mark.parametrize("default_left", [0, 1])
def test_xor_ranges_state_the_range_predicate(default_left):
    """The two ranges the kernel and the matmul scorer compare with,
    against the predicate ``split_ranges`` documents, for every value of
    the column: an unbundled feature with its missing bin nowhere, first,
    below, at and past the threshold and last; a bundle member's segment
    with the threshold nowhere (``pos = lo - 1``: every one-hot split),
    inside and at its end."""
    cases = [(0, 255, t, m) for t in (0, 7, 254) for m in (-1, 0, 5, 7, 8, 255)] \
        + [(lo, hi, pos, -1) for lo, hi in ((1, 1), (3, 9), (200, 255))
           for pos in (lo - 1, lo, hi - 1, hi)]
    lo, hi, pos, miss = (jnp.asarray(v, jnp.int32) for v in zip(*cases))
    dl = jnp.full_like(lo, default_left)
    a1, n1, a2, n2 = (np.asarray(v, np.int64)[:, None]
                      for v in RF.xor_ranges(lo, hi, pos, dl, miss))
    c = np.arange(256)[None, :]
    got = ((c >= a1) & (c - a1 < n1)) != ((c >= a2) & (c - a2 < n2))
    lo, hi, pos, miss = (np.asarray(v)[:, None] for v in (lo, hi, pos, miss))
    want = np.where(c == miss, bool(default_left),
                    ((c >= lo) & (c <= pos))
                    | (((c < lo) | (c > hi)) & bool(default_left)))
    np.testing.assert_array_equal(got, want)


def test_a_zero_as_missing_job_keeps_the_fused_kernel_and_the_matmul_scorer(
        monkeypatch):
    """``zero_as_missing`` puts a feature's missing bin at its zero bin,
    in the MIDDLE of its bins.  Such a job rides the fused partition
    kernel and the matmul valid scorer as any numeric job does (the
    kernel's ``miss`` descriptor): the round program holds the kernel, the
    tree and the rows' leaves are the XLA path's, both default directions
    occur, and the matmul scorer gives the frontier walk's scores."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner import batch_grower
    from lightgbm_tpu.models.predict import (predict_bins_tree,
                                             predict_bins_tree_matmul)
    rng = np.random.default_rng(11)
    n, f = 6000, 6
    X = rng.normal(size=(n + 1500, f))
    X[rng.random(X.shape) < 0.3] = 0.0
    # zeros of feature 0 belong with its high values, zeros of feature 1
    # with its low ones: missing goes right at one split and left at other
    z = (np.where(X[:, 0] == 0, 2.0, X[:, 0]) > 0.5).astype(float) \
        + (np.where(X[:, 1] == 0, -2.0, X[:, 1]) > -0.5) + 0.3 * X[:, 2]
    y = (z + 0.3 * rng.normal(size=len(z)) > 1.2).astype(float)
    params = {"objective": "binary", "metric": ["auc"], "num_leaves": 15,
              "min_data_in_leaf": 5, "zero_as_missing": True, "max_bin": 63,
              "verbosity": -1}
    ds = lgb.Dataset(X[:n], label=y[:n], params=params)
    dv = ds.create_valid(X[n:], label=y[n:])
    bst = lgb.train(params, ds, num_boost_round=1, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation({})])
    gb = bst._gbdt
    nanb, nbins = np.asarray(gb.nan_bin_arr), np.asarray(gb.num_bins_arr)
    assert ((nanb > 0) & (nanb < nbins - 1)).all()
    assert gb._matmul_valid_ok() and gb._valid_bins_t[0] is not None

    sign = jnp.where(jnp.asarray(y[:n]) > 0, 1.0, -1.0)
    grad, hess = (-sign * 0.5).astype(jnp.float32), jnp.full((n,), 0.25)
    grow = lambda: grow_tree_batched.__wrapped__(
        gb.bins, grad, hess, None, gb.num_bins_arr, gb.nan_bin_arr,
        gb.is_cat_arr, None, gb.hp, batch=4)
    calls = []
    kernel = batch_grower.partition_select_pallas
    monkeypatch.setattr(batch_grower, "partition_select_pallas",
                        lambda *a, **k: calls.append(1) or kernel(*a, **k))
    t0, lor0 = grow()
    assert not calls
    RF._FUSE_TEST_INTERPRET = True          # read when traced
    try:
        t1, lor1 = grow()
    finally:
        RF._FUSE_TEST_INTERPRET = False
    assert calls
    for a, b in zip(t0, t1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(lor0), np.asarray(lor1))
    ni = int(t1.num_leaves) - 1
    assert ni == 14 and len(set(np.asarray(t1.default_left)[:ni])) == 2
    walk = predict_bins_tree(t1, gb._valid_bins[0], gb.nan_bin_arr, None,
                             False)
    fast = predict_bins_tree_matmul(t1, gb._valid_bins_t[0], gb.nan_bin_arr,
                                    None, n_bins=gb.hp.n_bins)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(walk))
    # rows sit at the missing position on both sides of such splits
    vb = np.asarray(gb._valid_bins[0])
    assert all((vb[:, ft] == nanb[ft]).any()
               for ft in np.asarray(t1.split_feature)[:ni])


@pytest.mark.parametrize("bagging", [False, True], ids=["all_rows", "bagging"])
def test_round_passes_select_at_most_half_the_rows(monkeypatch, bagging):
    """The invariant the n/2 rung rests on: every round pass of the
    serial batched grower asks for the SMALLER child of each of its
    disjoint split leaves, so the count its branch is chosen by is at
    most half the (in-bag) rows, and it IS the number of rows the keys
    select.  (Only a full tree's last pass may select more than it
    counts: its slots past the room hold real leaves.  Nothing reads
    that pass's histograms.)"""
    from lightgbm_tpu.learner import batch_grower
    rng = np.random.default_rng(2)
    n, f = 6000, 8
    bins = jnp.asarray(rng.integers(0, 63, size=(n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.integers(-2, 3, size=n).astype(np.float32))
    hess = jnp.asarray(rng.integers(1, 5, size=n).astype(np.float32))
    row_mask = jnp.asarray(rng.random(n) < 0.6) if bagging else None
    in_bag = int(row_mask.sum()) if bagging else n
    hp = SplitHyper(num_leaves=31, min_data_in_leaf=5, n_bins=64,
                    hist_dtype="float32")
    passes = []
    real = batch_grower.histogram_for_leaves_auto

    def spy(bins_rows, bins_t, g, h, lor, leaves, mask=None, **kw):
        lor_m = lor if mask is None else jnp.where(mask, lor, -1)
        selected = jnp.sum(jnp.any(lor_m[None, :] == leaves[:, None], axis=0))
        jax.debug.callback(
            lambda c, s: passes.append((float(c), int(s))),
            jnp.sum(kw["counts"]), selected)
        return real(bins_rows, bins_t, g, h, lor, leaves, mask, **kw)

    monkeypatch.setattr(batch_grower, "histogram_for_leaves_auto", spy)
    tree, _ = grow_tree_batched.__wrapped__(
        bins, grad, hess, row_mask, jnp.full((f,), 64, jnp.int32),
        jnp.full((f,), -1, jnp.int32), jnp.zeros((f,), bool), None, hp,
        batch=8)
    jax.effects_barrier()
    assert int(tree.num_leaves) == 31 and len(passes) >= 5
    assert all(0 < counted <= in_bag / 2 for counted, _ in passes), passes
    assert all(counted == selected for counted, selected in passes[:-1])
