"""Fused batched-round partition + frontier-key Pallas kernel.

TPU-native equivalent of the reference's data partition step (reference:
src/treelearner/cuda/cuda_data_partition.cu:288 ``GenDataToLeftBitVector``
+ ``SplitInnerKernel`` :907 — bitvector, prefix sums, stable scatter).
This framework keeps rows in place and maintains a dense ``leaf_of_row``
map instead (learner/grower.py); the batched grower moves rows of all K
split parents in one pass.

In XLA that pass materializes several [K, n] HBM intermediates (the
per-slot feature columns, go-left masks and membership masks) plus a
separate [n] frontier-membership reduction for the compaction sort key —
profiled at ~8 ms/tree of small fusions (docs/PERF_NOTES.md round-2
plan item 2).  This kernel fuses all of it into ONE elementwise pass over
row blocks:

  - per-slot feature columns come from ONE [K, F] x [F, blk] one-hot
    contraction against the resident transposed bin matrix (bin values
    <= 255 are exact in bfloat16, each sum has exactly one term — exact);
  - the split decisions, the new ``leaf_of_row``, the bagging-masked
    leaf id and the (selected ? row : row | 2^30) compaction sort key
    (consumed by ops/histogram.py ``histogram_for_leaves_auto``) are all
    computed in VMEM and written once.

A split is handed over as a RANGE PREDICATE on its physical column
(learner/grower.py ``split_ranges``): a row at the feature's missing
position goes by ``default_left``; any other goes left when its value c
has ``lo <= c <= pos``, or when c lies outside ``[lo, hi]`` and
``default_left``.  An unbundled numeric feature is the trivial range (the
whole column, ``pos`` its threshold, its missing bin wherever the bin
mapper put it); a member of an EFB bundle is its segment of the column,
outside which it sits at its default bin.  The kernel itself is handed
the predicate folded into TWO ranges of the column whose exclusive-or is
"left" (``xor_ranges``): two unsigned compares a row and slot, the
parent's count of ``[K, block]`` operations but two casts (six
descriptors compared in the body cost the Criteo cells 1.25%).

In a job with categorical columns a slot may split by a SET of its
column's bins instead.  The kernel is then built as a static variant (a
numeric job's kernel has neither the operand nor the operations) in which
EVERY slot goes by a set: the left set as ``n_bins / 32`` words of 32
bins (``pack_left_bins``: bit b of word w set = bin 32w + b goes left),
a numeric slot's made outside the kernel from its two ranges (the bins
in exactly one), so the body has one test and no blend of two.  The
value's word is picked by compares on ``c >> 5``, its bit by a
shift and a mask (``bin_in_set``), from the ``[K, block]`` values the
kernel holds already: no ``[K, n_bins]`` one-hot, no gather.  Only the
inverse table of a bundle plan without ranges is still a per-row gather
(the slowest TPU primitive) and keeps the XLA path in
learner/batch_grower.py, which is also the tests' oracle.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import hist_pallas as _hp

# test hook: CPU suite runs the kernel through the interpreter
_FUSE_TEST_INTERPRET = False


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def use_fused_partition() -> bool:
    from .histogram import use_pallas
    return _FUSE_TEST_INTERPRET or use_pallas()


def xor_ranges(lo: jax.Array, hi: jax.Array, pos: jax.Array,
               default_left: jax.Array, miss: jax.Array):
    """The range predicate of ``split_ranges`` as two ranges ``(start,
    length)`` of the column, a value going left when it lies in exactly
    ONE of them.  Without a missing position: ``[lo, pos]``, or with
    ``default_left`` everything but ``(pos, hi]``.  With one (the slot's
    ``[lo, hi]`` is then the whole column: a bundle plan with ranges has
    no missing bin): ``[lo, pos]``, and the missing position alone where
    it has to change sides, in past ``pos`` when ``default_left``, out
    from below it when not.  Returns ``(a1, n1, a2, n2)`` i32."""
    dl = default_left != 0
    has = miss >= 0
    flip = has & ((miss > pos) == dl)
    outside = dl & ~has
    zero = jnp.zeros_like(lo)
    a1 = jnp.where(outside, zero, lo)
    n1 = jnp.where(outside, jnp.iinfo(jnp.int32).max, pos - lo + 1)
    a2 = jnp.where(has, miss, pos + 1)
    n2 = jnp.where(has, flip.astype(jnp.int32),
                   jnp.where(outside, hi - pos, zero))
    return a1, n1, a2, n2


def pack_left_bins(left_bins: jax.Array) -> jax.Array:
    """Left sets bool ``[K, B]`` as words i32 ``[ceil(B / 32), K]``: bit b
    of word w of slot k is set when bin ``32 w + b`` of its column goes
    left (what ``partition_select_pallas`` takes as ``cat_words``)."""
    K, B = left_bins.shape
    W = -(-B // 32)
    bits = jnp.pad(left_bins, ((0, 0), (0, W * 32 - B))) \
        .reshape(K, W, 32).astype(jnp.uint32)
    words = jnp.sum(bits << lax.iota(jnp.uint32, 32)[None, None, :],
                    axis=2, dtype=jnp.uint32)
    return lax.bitcast_convert_type(words, jnp.int32).T


def bin_in_set(cols: jax.Array, word_rows) -> jax.Array:
    """0/1 i32 ``[K, block]``: whether each value's bin is in its slot's
    set.  ``cols`` i32 ``[K, block]`` bin values, ``word_rows`` the set's
    words, one ``[K]`` i32 a word (``pack_left_bins``'s rows): the word
    that holds the bin by one compare and one 32-bit select a word (a tree
    of 7 selects on the bits of ``cols >> 5`` read the same time on the
    chip: PERF.md section 6, PR 43), then the bin's bit by a shift and a
    mask.  Shared by the partition kernel's body and ``models/predict.py``
    ``predict_bins_tree_matmul``."""
    high = cols >> 5
    word = jnp.zeros_like(cols)
    for w, row in enumerate(word_rows):
        word = jnp.where(high == w, row[:, None], word)
    return lax.shift_right_logical(word, cols & 31) & 1


def range_left_bins(a1: jax.Array, n1: jax.Array, a2: jax.Array,
                    n2: jax.Array, n_bins: int) -> jax.Array:
    """bool ``[K, n_bins]``: the bins of ``xor_ranges``' two ranges that go
    left (those in exactly one), a numeric slot's split as a left set."""
    b = lax.iota(jnp.int32, n_bins)[None, :]
    within = lambda a, m: ((b - a[:, None]).astype(jnp.uint32)
                           < m[:, None].astype(jnp.uint32))
    return within(a1, n1) ^ within(a2, n2)


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def partition_select_pallas(bins_t: jax.Array, lor: jax.Array,
                            mask: jax.Array, cols: jax.Array,
                            lo: jax.Array, hi: jax.Array,
                            pos: jax.Array, default_left: jax.Array,
                            miss: jax.Array, parents: jax.Array,
                            new_leaves: jax.Array, validk: jax.Array,
                            smaller: jax.Array,
                            cat_words: Optional[jax.Array] = None,
                            slot_is_cat: Optional[jax.Array] = None, *,
                            rows_per_block: int = 2048,
                            interpret: bool = False
                            ) -> Tuple[jax.Array, jax.Array]:
    """One fused pass: rows move to their split side and the next
    histogram call's compaction keys come out with them.

    bins_t: u8 [F, n] resident transposed bins; lor: i32 [n] current leaf
    map (unmasked); mask: i32 [n] 1/0 bagging mask; per-slot descriptors
    i32 [K]: cols (physical column), lo/hi/pos (the range ``[lo, hi]`` the
    split's feature holds of that column and the last position that goes
    left, ``lo - 1`` for none), default_left (0/1: where a missing value
    and a value outside ``[lo, hi]`` go), miss (the column position of
    the feature's missing bin, -1 for none), parents (parent leaf id, -1
    disables the slot), new_leaves (right-child leaf id), validk (0/1),
    smaller (the leaf ids the NEXT histogram pass will compact, dummy
    slots may repeat).  In a job with categorical columns also cat_words
    i32 [W, K] (``pack_left_bins``) and slot_is_cat i32 [K] (0/1): a slot
    with the flag set sends a row left when its value's bit is set in its
    words; the others' words are made here from their range descriptors,
    and the kernel tests every slot's set.

    Returns (new_lor i32 [n], sort_key i32 [n]) where sort_key =
    (row in smaller-frontier AND mask) ? row : row | 2^30.
    """
    num_f, n = bins_t.shape
    K = cols.shape[0]
    # no operand is padded to the block: every output lane depends on the
    # SAME lane of the row inputs only (the one contraction runs over F, the
    # sums over K), so whatever the last block's lanes past n hold stays in
    # lanes the write-back drops
    blk = min(rows_per_block, max(128, _round_up(n, 128)))
    # the block of bins with its two casts (two u8 windows, i32, bfloat16:
    # 8 bytes a column and row) stays inside the kernels' one VMEM budget:
    # the row block follows from the width, and the compiler is told
    room = _hp.VMEM_BUDGET_BYTES - _hp._VMEM_BODY_BYTES
    blk = min(blk, max(128, room // (8 * num_f) // 128 * 128))

    a1, n1, a2, n2 = xor_ranges(lo, hi, pos, default_left, miss)

    k_spec = pl.BlockSpec((1, K), lambda i: (0, 0))
    # what the body tests a slot's value against: its two ranges, or in
    # the static variant with sets every slot's words
    with_sets = cat_words is not None
    if with_sets:
        words = jnp.where(
            slot_is_cat[None, :] != 0, cat_words,
            pack_left_bins(range_left_bins(a1, n1, a2, n2,
                                           32 * cat_words.shape[0])))
        test_specs = [pl.BlockSpec(words.shape, lambda i: (0, 0))]
    else:
        test_specs = [k_spec] * 4

    def kernel(bins_ref, lor_ref, mask_ref, cols_ref, *refs):
        (*test_refs, par_ref, nl_ref, vk_ref, sm_ref,
         out_lor_ref, out_key_ref) = refs
        step = pl.program_id(0)
        fk = cols_ref[0, :]                                   # [K]
        iota_f = lax.iota(jnp.int32, num_f)
        ohf = (fk[:, None] == iota_f[None, :]).astype(jnp.bfloat16)
        # via i32: Mosaic has no u8->bf16 cast (docs/PERF_NOTES.md round 3)
        b_blk = bins_ref[:].astype(jnp.int32).astype(jnp.bfloat16)  # [F, blk]
        # per-slot feature column: exactly one one-hot term per sum and
        # bin values <= 255 are exact in bf16 -> exact integers out
        cols = lax.dot_general(
            ohf, b_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)  # [K, blk]
        lor_b = lor_ref[0, :]                                 # [blk]
        # boolean logic as 0/1 i32 arithmetic: Mosaic legalizes only
        # 32-bit cmp/select here — a select_n over i1 payloads fails to
        # compile (arith.trunci i8->i1), so where() is reserved for
        # 32-bit payloads only
        if with_sets:
            words_ref, = test_refs
            go_left = bin_in_set(cols, [words_ref[w, :] for w
                                        in range(words_ref.shape[0])])
        else:
            a1_ref, n1_ref, a2_ref, n2_ref = test_refs
            # a <= c < a + n as ONE unsigned compare, c - a < n (a c below
            # a wraps past every n, and n = 0 is the empty range); left is
            # being in exactly one of the slot's two ranges (xor_ranges)
            within = lambda a_ref, n_ref: (
                (cols - a_ref[0, :][:, None]).astype(jnp.uint32)
                < n_ref[0, :][:, None].astype(jnp.uint32)).astype(jnp.int32)
            go_left = within(a1_ref, n1_ref) ^ within(a2_ref, n2_ref)
        in_par = (lor_b[None, :] == par_ref[0, :][:, None]
                  ).astype(jnp.int32) * vk_ref[0, :][:, None]
        move = in_par * (1 - go_left)     # one-hot across K: parents are
        tgt = jnp.sum(move * nl_ref[0, :][:, None], axis=0)   # distinct
        new_lor = jnp.where(jnp.sum(move, axis=0) > 0, tgt, lor_b)
        out_lor_ref[0, :] = new_lor
        lor_m = jnp.where(mask_ref[0, :] != 0, new_lor, -1)
        selv = jnp.sum((lor_m[None, :] == sm_ref[0, :][:, None]
                        ).astype(jnp.int32), axis=0)          # [blk]
        row = step * blk + lax.iota(jnp.int32, blk)
        out_key_ref[0, :] = jnp.where(selv > 0, row, row | (1 << 30))

    row_spec = pl.BlockSpec((1, blk), lambda i: (0, i))
    out_lor, out_key = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, blk),),
        in_specs=[pl.BlockSpec((num_f, blk), lambda i: (0, i)),
                  row_spec, row_spec, k_spec] + test_specs
        + [k_spec, k_spec, k_spec, k_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct((1, n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_hp.vmem_limit(8 * num_f * blk)),
        interpret=interpret,
    )(bins_t, lor[None, :], mask[None, :], cols[None, :],
      *((words,) if with_sets else
        (a1[None, :], n1[None, :], a2[None, :], n2[None, :])),
      parents[None, :], new_leaves[None, :], validk[None, :],
      smaller[None, :])
    return out_lor[0], out_key[0]
