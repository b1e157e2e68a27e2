"""Gradient/hessian histogram construction.

TPU-native re-design of the reference's hot kernel (reference:
src/treelearner/cuda/cuda_histogram_constructor.cu:18
``CUDAConstructHistogramDenseKernel`` — shared-memory atomic scatter-add; CPU
path src/io/dense_bin.hpp ``ConstructHistogram`` 4-way unrolled loops).

TPUs have no fast scatter-add, so the histogram is reformulated as a
contraction the MXU can run: for a block of rows, the per-feature one-hot of
the bin index contracted against the per-row value channels

    hist[f, b, c] = sum_r onehot(bins[r, f] == b) * vals[r, c]

which is ``dot_general`` with contracting dim r (one matmul per row block,
accumulated with ``lax.scan`` so the one-hot only ever exists for one block).
Channels are (grad, hess, count, pad) so a single contraction produces the
(g, h, n) triple the split finder needs — the reference interleaves grad/hess
the same way (train_share_states.h ordered gradients).

Leaf masking happens in ``vals`` (masked rows carry zeros), so one op serves
both the root pass and per-leaf passes; the caller implements the reference's
histogram-subtraction trick (serial_tree_learner.cpp:364-378) on top.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

NUM_CHANNELS = 4  # grad, hess, count, pad

#: histogram-build formulations selectable via the ``hist_kernel`` config
#: key (round 6 — VERDICT r5 #1: the one-hot contraction is
#: formulation-bound, so the comparison itself must change).  All modes
#: are bit-identical on the same inputs; only the kernel arithmetic
#: differs:
#:   auto   — measured dispatch: radix single/joint where round-3 data
#:            says they win, the new packed/radix2 formulations where
#:            the one-hot build floor binds (see hist_dispatch);
#:   onehot — the flat one-hot kernels everywhere (the bit-identity
#:            reference path);
#:   packed — 4 bins per i32 lane, SWAR compares
#:            (hist_pallas.histogram_leaves_packed_pallas);
#:   radix2 — shared hi/lo nibble planes reused across all K leaf
#:            channels (hist_pallas.histogram_leaves_radix2_pallas).
HIST_KERNELS = ("auto", "onehot", "packed", "radix2")


def resolve_hist_kernel(name) -> str:
    """Validate a ``hist_kernel`` value; LightGBMError names the key."""
    n = str(name or "auto").strip().lower()
    if n not in HIST_KERNELS:
        from ..utils import log
        log.fatal("unknown hist_kernel=%r (expected one of %s)"
                  % (name, "/".join(HIST_KERNELS)))
    return n


# test hook: lets the CPU suite exercise the mode kernels through the
# Pallas interpreter (use_pallas() is False off-TPU)
_MODE_TEST_INTERPRET = False


class HistDispatch(NamedTuple):
    """What ``hist_dispatch`` answers for one masked pass."""
    kernel: str    # xla | flat | packed | radix2 | radix_joint | radix_single
    mirror: bool   # keep the packed word mirror ``bins_words_t`` resident
    ladder: bool   # the batched grower's width-matched warm-up ladder pays
    top_rung: int  # the row ladder's largest bucket holds n / top_rung rows


def hist_dispatch(hist_kernel, n_bins: int, K: int = 1, num_f: int = 1,
                  have_words: bool = False,
                  single: bool = False) -> HistDispatch:
    """The one place that says which kernel a masked pass over ``K``
    leaves of ``num_f`` features takes, whether the mode wants the packed
    mirror shipped, whether the warm-up ladder pays, and where the row
    ladder of ``histogram_for_leaves_auto`` starts.  Pure in its
    arguments plus the platform (``use_pallas()``; ``_MODE_TEST_INTERPRET``
    stands in for it in the CPU suite), and only ``kernel`` depends on
    the platform.  ``single`` is the one-leaf entry
    (``histogram_for_leaf_masked``: the root pass, the strict grower).

    auto: at >= 128 bins (a multiple of 16: the radix kernels decompose
    bin = 16*hi + lo, ops/hist_pallas.py ``_radix_shapes``) one leaf
    takes radix-single, K <= 4 radix-joint, whose build grows with the
    leaf count, K > 4 the shared-radix kernel where its accumulator
    fits.  Below 128 bins the radix build's small [p*nhi, 3*p*nlo] tiles
    waste the MXU and the pass goes to the packed-compare kernel where
    the mirror is resident.  Explicit modes force their kernel where its
    shape constraints hold; every other case is the flat one-hot kernel
    (bit-identical).

    The warm-up ladder pays only where the K <= 4 pass takes the
    radix-JOINT kernel; every other kernel is K-independent below one MXU
    channel tile, so those modes seed the round loop at full width from
    the root histogram: identical selections, fewer compiled round bodies.

    The rung rule.  A compacted pass costs a fixed streaming pass over
    the rows plus the payload kernel over the rows it selected; a full
    pass costs its kernel over all of them.  So the largest bucket worth
    compiling follows from what the full pass would cost (PERF.md section
    5 has the chip's numbers): n/2 where it is the flat or the
    shared-radix kernel above 64 bins (a round pass never selects more
    than half the rows, so the full pass is then only the fallback), and
    n/4 elsewhere: at 64 bins and below a full pass is about as cheap as
    compaction plus payload at n/2, and so are the radix-joint passes of
    the K <= 4 bodies.
    """
    hk = resolve_hist_kernel(hist_kernel)
    radix = hk == "auto" and n_bins % 16 == 0 and n_bins >= 128
    mirror = hk == "packed" or (hk == "auto" and not radix)
    if radix and single:
        kernel = "radix_single"
    elif radix and K <= 4:
        kernel = "radix_joint"
    elif radix or hk == "radix2":
        from .hist_pallas import radix2_pick_p
        fits = (n_bins % 16 == 0 and n_bins >= 16
                and radix2_pick_p(num_f, K, n_bins) > 0)
        kernel = "radix2" if fits else "flat"
    elif mirror and have_words:
        kernel = "packed"
    else:
        kernel = "flat"
    top_rung = 2 if kernel in ("flat", "radix2") and n_bins > 64 else 4
    if not (use_pallas() or _MODE_TEST_INTERPRET):
        kernel = "xla"
    return HistDispatch(kernel, mirror, radix, top_rung)


def reduce_hist(hist: jax.Array, axis_name: Optional[str],
                _unused=False) -> jax.Array:
    """All-reduce a histogram across ``axis_name`` (no-op when serial):
    the single sink every histogram builder's cross-device reduction
    flows through."""
    # ``_unused``: benchmark/tools/faults_dp.py wraps this function and
    # forwards three positional arguments, and this repo's PRs may not edit
    # benchmark/ outside a benchmark issue; goes once that wrapper forwards
    # two (ROADMAP D5).  No caller in lightgbm_tpu/ passes it.
    if axis_name is None:
        return hist
    # device scope ``hist_allreduce`` (docs/OBSERVABILITY.md): a trace
    # finds the collective by this name, whatever XLA makes of it
    with jax.named_scope("hist_allreduce"):
        return lax.psum(hist, axis_name)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def use_pallas() -> bool:
    """Pallas kernels on TPU; the XLA one-hot contraction on the CPU
    (tests).  Selected by platform only."""
    return jax.default_backend() == "tpu"


def _pallas_blk(hist_dtype: str, n_bins: int = 256,
                float_cap: int = 1024) -> int:
    """Row-block cap for the flat/payload Pallas kernels.

    Round-4 tuning: ISOLATED int8 kernels run ~1.7x faster at blk=2048
    (flat 11.7/6.8/12.3 ms per 1M-row pass at 1024/2048/4096; payload
    13.4 -> 8.2 at a 250k bucket, K=28) — but IN CONTEXT at K=42 the
    2048 clamp regressed the tree loop 76.9 -> 84.9 ms/tree: the
    [3K, F*B] f32 accumulator plus the wider one-hot crowd VMEM and
    stall the grid's double buffering.  Standalone wins do not survive
    composition here; stay at 1024 until a K-aware model is measured.

    At <= 64 bins (the reference GPU docs' speed configuration) the
    accumulator and one-hot are 4x smaller, VMEM pressure disappears and
    the wider block wins in context too (round-5 measurement).
    """
    if n_bins <= 64:
        return 2048
    return float_cap


@functools.partial(jax.jit, static_argnames=("n_bins", "rows_per_block",
                                             "feats_per_chunk"))
def build_histogram(bins: jax.Array, vals: jax.Array, *, n_bins: int = 256,
                    rows_per_block: int = 4096,
                    feats_per_chunk: int = 8) -> jax.Array:
    """hist[f, b, c] = sum over rows of onehot(bin) * vals.

    bins: uint8/int32 [n, F]; vals: f32 [n, C] (masked rows must be zero).
    Returns f32 [F, n_bins, C].
    """
    n, num_feat = bins.shape
    c = vals.shape[1]
    blk = min(rows_per_block, _round_up(max(n, 1), 128))
    n_pad = _round_up(max(n, 1), blk)
    if n_pad != n:
        bins = jnp.pad(bins, ((0, n_pad - n), (0, 0)))
        vals = jnp.pad(vals, ((0, n_pad - n), (0, 0)))  # zero vals: no effect
    nb = n_pad // blk
    fc = min(feats_per_chunk, num_feat)
    f_pad = _round_up(num_feat, fc)
    if f_pad != num_feat:
        bins = jnp.pad(bins, ((0, 0), (0, f_pad - num_feat)))
    bins_b = bins.astype(jnp.int32).reshape(nb, blk, f_pad)
    vals_b = vals.reshape(nb, blk, c)
    iota = lax.iota(jnp.int32, n_bins)

    def block_step(acc, xs):
        b_blk, v_blk = xs  # [blk, f_pad], [blk, c]
        parts = []
        for f0 in range(0, f_pad, fc):
            chunk = b_blk[:, f0:f0 + fc]                     # [blk, fc]
            onehot = (chunk[:, :, None] == iota).astype(vals.dtype)  # [blk, fc, B]
            lhs = onehot.reshape(blk, fc * n_bins)
            h = lax.dot_general(lhs, v_blk, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=lax.Precision.HIGHEST)
            parts.append(h.reshape(fc, n_bins, c))
        return acc + jnp.concatenate(parts, axis=0), None

    acc0 = jnp.zeros((f_pad, n_bins, c), dtype=jnp.float32)
    hist, _ = lax.scan(block_step, acc0, (bins_b, vals_b))
    return hist[:num_feat]


def histogram_for_leaf_masked(bins_t: jax.Array, grad: jax.Array,
                              hess: jax.Array, leaf_of_row: jax.Array,
                              leaf: jax.Array,
                              row_mask: Optional[jax.Array] = None, *,
                              n_bins: int = 256, rows_per_block: int = 4096,
                              hist_dtype: str = "float32",
                              axis_name: Optional[str] = None,
                              hist_kernel: str = "auto",
                              bins_words_t: Optional[jax.Array] = None
                              ) -> jax.Array:
    """Leaf histogram by masking: one full-data pass with non-leaf rows
    zeroed.  O(n) per call but with NO compaction machinery.  Under
    ``hist_kernel=auto`` on TPU the single-group radix kernel carries it
    (~1.7x the flat one-hot kernel, docs/PERF_NOTES.md round 3);
    ``bins_t`` is the TRANSPOSED [F, n] matrix."""
    if hist_dispatch(hist_kernel, n_bins,
                     single=True).kernel == "radix_single":
        from .hist_pallas import histogram_radix_single_pallas
        lor = jnp.asarray(leaf_of_row, jnp.int32)
        sel = lor == jnp.asarray(leaf, jnp.int32)
        if row_mask is not None:
            sel = sel & row_mask
        lor1 = jnp.where(sel, 0, -1)
        hist = histogram_radix_single_pallas(
            bins_t, grad, hess, lor1, n_bins=n_bins,
            rows_per_block=min(rows_per_block, 2048),
            compute_dtype=jnp.dtype(hist_dtype).type,
            interpret=not use_pallas())
        return reduce_hist(hist, axis_name)
    leaf_arr = jnp.asarray(leaf, jnp.int32).reshape(1)
    hist = histogram_for_leaves_masked(
        bins_t, grad, hess, leaf_of_row, leaf_arr, row_mask, n_bins=n_bins,
        rows_per_block=rows_per_block, hist_dtype=hist_dtype,
        axis_name=axis_name, hist_kernel=hist_kernel,
        bins_words_t=bins_words_t)
    return hist[0]


def histogram_for_leaves_masked(bins_t: jax.Array, grad: jax.Array,
                                hess: jax.Array, leaf_of_row: jax.Array,
                                leaves: jax.Array,
                                row_mask: Optional[jax.Array] = None, *,
                                n_bins: int = 256,
                                rows_per_block: int = 4096,
                                hist_dtype: str = "float32",
                                axis_name: Optional[str] = None,
                                hist_kernel: str = "auto",
                                bins_words_t: Optional[jax.Array] = None
                                ) -> jax.Array:
    """Histograms of K leaves in ONE data pass -> f32 [K, F, B, C].

    The one-hot construction (the TPU kernel's dominant cost) is built once
    and contracted against K x C masked value channels, so K leaves cost
    barely more than one — the enabler of batched split rounds
    (learner/batch_grower.py).  Widening channels also fills the MXU's
    sublane dimension (M = 4K instead of 4).  ``leaves``: i32 [K]; invalid
    slots may repeat a leaf (their histograms are simply unused).

    ``hist_kernel`` selects the build formulation (``HIST_KERNELS``; all
    modes bit-identical); ``bins_words_t`` is the resident packed-word
    mirror [W, n] the packed mode consumes (io/dataset.py
    ``packed_mirror``).
    """
    K = leaves.shape[0]
    num_f = bins_t.shape[0]
    leaves = jnp.asarray(leaves, jnp.int32)
    lor = jnp.asarray(leaf_of_row, jnp.int32)
    if row_mask is not None:
        lor = jnp.where(row_mask, lor, -1)
    kern = hist_dispatch(hist_kernel, n_bins, K, num_f,
                         bins_words_t is not None).kernel
    interp = not use_pallas()
    if kern == "radix_joint":
        # joint (leaf, hi) radix kernel: measured 4.0/5.0/7.5 ms per 1M-row
        # pass at K=1/2/4 vs the flat kernel's K-independent ~9.8
        # (docs/PERF_NOTES.md round 3) — the warmup-round accelerator
        from .hist_pallas import histogram_radix_joint_pallas
        hist = histogram_radix_joint_pallas(
            bins_t, grad, hess, lor, leaves, n_bins=n_bins,
            rows_per_block=min(rows_per_block, 2048),
            compute_dtype=jnp.dtype(hist_dtype).type, interpret=interp)
        return reduce_hist(hist, axis_name)
    if kern == "radix2":
        from .hist_pallas import (histogram_leaves_radix2_pallas,
                                  radix2_pick_p)
        hist = histogram_leaves_radix2_pallas(
            bins_t, grad, hess, lor, leaves, n_bins=n_bins,
            rows_per_block=min(rows_per_block, 1024),
            p=radix2_pick_p(num_f, K, n_bins),
            compute_dtype=jnp.dtype(hist_dtype).type, interpret=interp)
        return reduce_hist(hist, axis_name)
    if kern == "packed":
        from .hist_pallas import histogram_leaves_packed_pallas
        hist = histogram_leaves_packed_pallas(
            bins_words_t, grad, hess, lor, leaves, num_f=num_f,
            n_bins=n_bins,
            rows_per_block=min(rows_per_block, _pallas_blk(hist_dtype, n_bins)),
            compute_dtype=jnp.dtype(hist_dtype).type, interpret=interp)
        return reduce_hist(hist, axis_name)
    if kern == "flat":
        from .hist_pallas import histogram_leaves_pallas
        hist = histogram_leaves_pallas(
            bins_t, grad, hess, lor, leaves, n_bins=n_bins,
            rows_per_block=min(rows_per_block, _pallas_blk(hist_dtype, n_bins)),
            compute_dtype=jnp.dtype(hist_dtype).type,
            interpret=interp)                                 # [K, F, B, C]
    else:
        sel = lor[None, :] == leaves[:, None]                 # [K, n]
        m = sel.astype(grad.dtype)
        # where(), not multiply: 0 * NaN = NaN would let one bad excluded
        # row poison the sums (matches the Pallas kernel's masking)
        vals_t = jnp.stack([jnp.where(sel, grad[None, :], 0.0),
                            jnp.where(sel, hess[None, :], 0.0), m,
                            jnp.zeros_like(m)], axis=0)
        C = vals_t.shape[0]
        vals_t = vals_t.reshape(C * K, -1)
        hist = build_histogram(bins_t.T, vals_t.T, n_bins=n_bins,
                               rows_per_block=rows_per_block)  # [F, B, C*K]
        F, B = hist.shape[0], hist.shape[1]
        hist = hist.reshape(F, B, C, K).transpose(3, 0, 1, 2)  # [K, F, B, C]
    return reduce_hist(hist, axis_name)


# test hook: lets the CPU suite exercise the payload Pallas kernel via the
# interpreter (use_pallas() is False off-TPU)
_PAYLOAD_TEST_INTERPRET = False


def bins_to_words(bins_rows: jax.Array) -> jax.Array:
    """u8 [n, F] row-major bins -> i32 [n, ceil(F/4)] word view (each word
    packs 4 bin bytes little-endian).  Tree-invariant: built once and
    reused by every compacted round's payload concat."""
    n, num_f = bins_rows.shape
    pad = (-num_f) % 4
    if pad:
        bins_rows = jnp.pad(bins_rows, ((0, 0), (0, pad)))
    w = (num_f + pad) // 4
    return lax.bitcast_convert_type(
        bins_rows.reshape(n, w, 4), jnp.int32)


def histogram_for_leaves_auto(bins_rows: jax.Array, bins_t: jax.Array,
                              grad: jax.Array, hess: jax.Array,
                              leaf_of_row: jax.Array, leaves: jax.Array,
                              row_mask: Optional[jax.Array] = None, *,
                              n_bins: int = 256, rows_per_block: int = 2048,
                              hist_dtype: str = "float32",
                              axis_name: Optional[str] = None,
                              buckets=(4, 8, 16, 64),
                              counts: Optional[jax.Array] = None,
                              bins_words: Optional[jax.Array] = None,
                              sort_key: Optional[jax.Array] = None,
                              hist_kernel: str = "auto",
                              bins_words_t: Optional[jax.Array] = None
                              ) -> jax.Array:
    """K-leaf histograms with frontier compaction -> f32 [K, F, B, C].

    The TPU reformulation of the reference's O(smaller-child) histogram cost
    (serial_tree_learner.cpp:364-378 iterates only the leaf's data indices):
    a pass does work in proportion to the rows it selects.  When the rows
    belonging to ``leaves`` fit a bucket of the row ladder, their output
    columns are ranked from the keys alone (``compaction_ranks``: XLA, a
    few operations on ``[n]`` words), one streaming pass over the resident
    lane-dense bins (``compact_payload_pallas``) moves them, in row
    order, into an i32 WORD payload with the compacted positions on the
    lanes (4 bin bytes per word + grad/hess/leaf words) and the payload
    kernel runs on the bucket, its grid stopping at the count; otherwise
    one full masked pass
    (``histogram_for_leaves_masked``).  Exact: the same rows contribute
    either way.  Off the TPU the compacted bucket is a sort of the keys and
    a row gather of the row-major payload, the reference the kernels are
    tested against.

    The ladder's buckets hold n / d rows for the divisors d of ``buckets``
    from ``hist_dispatch(...).top_rung`` down (the top rung itself always;
    smaller divisors are dropped): where the full pass is expensive the
    ladder starts at n/2.  The invariant that rung rests on: the batched
    grower asks for the SMALLER child of each of a round's disjoint split
    leaves (learner/batch_grower.py ``smaller``), so a round pass selects
    at most half the rows it could.  The full pass stays as branch 0 all
    the same: under ``shard_map`` the globally smaller child can be more
    than half of one shard's rows, and the bounded pool's pass holds
    larger children too.

    A leaf-GROUPED compaction variant (rows sorted by leaf, block->leaf
    scalar-prefetch steering) was built and measured slower end-to-end:
    the K-channel MXU multiplier it removes does not exist below 128
    output channels, while its layout glue is real.  It was deleted.

    ``bins_rows``: u8 [n, F] row-major; ``bins_t``: u8 [F, n] transposed.

    ``counts`` (f32 [K], optional): the caller's known masked row count per
    leaf slot (0 for dummy slots); saves the [K, n] membership reduction.
    ``bins_words`` (i32 [n, ceil(F/4)], optional): ``bins_to_words`` result
    hoisted out of the round loop by the caller (the XLA path's payload).
    ``sort_key`` (i32 [n], optional): precomputed (selected ? row :
    row | 2^30) keys from the fused partition kernel (ops/round_fuse.py);
    built here from the membership mask otherwise.
    ``hist_kernel``/``bins_words_t``: masked-pass formulation + packed
    mirror, forwarded to ``histogram_for_leaves_masked``; where the mirror
    is resident the compaction reads it, else ``bins_t``.
    """
    hist_kernel = resolve_hist_kernel(hist_kernel)
    n = grad.shape[0]
    leaves = jnp.asarray(leaves, jnp.int32)
    lor = jnp.asarray(leaf_of_row, jnp.int32)
    if row_mask is not None:
        lor = jnp.where(row_mask, lor, -1)
    assert n < (1 << 30), "compaction packing needs n < 2^30 rows per shard"
    num_f = bins_rows.shape[1]

    # Device scopes (docs/OBSERVABILITY.md): ``hist_compact`` is every
    # movement of data that prepares a kernel's operands (selection keys,
    # the compaction of the selected rows), ``hist_kernel`` the kernels
    # themselves; each ``lax.switch`` branch's histogram kernel sits in a
    # ``hist_rows_<S>`` of its static row count, so a trace says how many
    # rows each pass was handed.  Metadata only.
    with jax.named_scope("hist_compact"):
        if counts is not None:
            cnt = jnp.sum(counts).astype(jnp.int32)
        else:
            sel = jnp.any(lor[None, :] == leaves[:, None], axis=0)  # [n]
            cnt = jnp.sum(sel.astype(jnp.int32))
        if sort_key is None:
            if counts is not None:
                sel = jnp.any(lor[None, :] == leaves[:, None], axis=0)
            # pack (selected?, row) into ONE i32 and single-sort in the
            # branch — the first ``cnt`` sorted entries are exactly the
            # selected rows in order.  A non-stable single-operand sort
            # costs ~0.4 ms/1M on TPU vs ~1.4 ms for stable argsort and
            # ~9 ms for sized ``nonzero`` (docs/PERF_NOTES.md).
            iota_n = lax.iota(jnp.int32, n)
            sort_key = jnp.where(sel, iota_n, iota_n | (1 << 30))

    blk = min(rows_per_block, 2048)
    top = hist_dispatch(hist_kernel, n_bins, leaves.shape[0], num_f,
                        bins_words_t is not None).top_rung
    sizes = []
    for d in (top,) + tuple(b for b in buckets if b > top):
        s = _round_up(max(n // d, 1), blk)
        if s < n and s not in sizes:
            sizes.append(s)

    def full_branch(operands):
        with jax.named_scope("hist_rows_full"), \
                jax.named_scope("hist_kernel"):
            return histogram_for_leaves_masked(
                bins_t, grad, hess, lor, leaves, None, n_bins=n_bins,
                rows_per_block=rows_per_block, hist_dtype=hist_dtype,
                hist_kernel=hist_kernel, bins_words_t=bins_words_t)

    def compacted(S: int, operands):
        key_, grad_, hess_, lor_ = operands
        if use_pallas() or _PAYLOAD_TEST_INTERPRET:
            from .hist_pallas import (compact_payload_pallas,
                                      histogram_payload_pallas)
            interp = not use_pallas()
            # the compaction (the ranks of the selected rows, XLA on the
            # keys, then a second pallas_call of the pass) stays OUTSIDE
            # hist_rows_<S>, so that a trace counts the pass once and
            # all of its time goes to hist_compact
            with jax.named_scope("hist_compact"):
                pc = compact_payload_pallas(
                    bins_t if bins_words_t is None else bins_words_t,
                    key_, grad_, hess_, lor_, size=S,
                    interpret=interp)                         # [W+3.., S]
            with jax.named_scope(f"hist_rows_{S}"), \
                    jax.named_scope("hist_kernel"):
                return histogram_payload_pallas(
                    pc, leaves, cnt, num_f=num_f, n_bins=n_bins,
                    rows_per_block=min(rows_per_block,
                                       _pallas_blk(hist_dtype, n_bins)),
                    compute_dtype=jnp.dtype(hist_dtype).type,
                    interpret=interp)
        # XLA path (CPU tests / non-TPU): sort the keys, gather the rows
        # of the row-major payload, unpack and run the masked pass on them
        with jax.named_scope(f"hist_rows_{S}"):
            with jax.named_scope("hist_compact"):
                words = bins_to_words(bins_rows) if bins_words is None \
                    else bins_words
                W = words.shape[1]
                payload = jnp.concatenate([
                    words,
                    lax.bitcast_convert_type(grad_, jnp.int32)[:, None],
                    lax.bitcast_convert_type(hess_, jnp.int32)[:, None],
                    lor_[:, None],
                ], axis=1)                                    # [n, W+3] i32
                idxc = jnp.sort(key_, stable=False)[:S] & ((1 << 30) - 1)
                pc = payload[idxc]                            # [S, W+3]
            with jax.named_scope("hist_kernel"):
                valid = lax.iota(jnp.int32, S) < cnt
                rows_c = lax.bitcast_convert_type(
                    pc[:, :W], jnp.uint8).reshape(S, 4 * W)[:, :num_f]
                grad_c = lax.bitcast_convert_type(pc[:, W], jnp.float32)
                hess_c = lax.bitcast_convert_type(pc[:, W + 1], jnp.float32)
                lor_c = jnp.where(valid, pc[:, W + 2], -1)
                return histogram_for_leaves_masked(
                    rows_c.T, grad_c, hess_c, lor_c, leaves, None,
                    n_bins=n_bins, rows_per_block=rows_per_block,
                    hist_dtype=hist_dtype, hist_kernel="onehot")

    branches = [full_branch] + [functools.partial(compacted, s)
                                for s in sizes]
    with jax.named_scope("hist_compact"):
        j = jnp.int32(0)
        for k, s in enumerate(sizes):  # sizes descending: smallest fit wins
            j = jnp.where(cnt <= s, jnp.int32(k + 1), j)
    hist = lax.switch(j, branches, (sort_key, grad, hess, lor))
    with jax.named_scope("hist_kernel"):
        return reduce_hist(hist, axis_name)


def root_histogram(bins_t: jax.Array, grad: jax.Array, hess: jax.Array,
                   row_mask: Optional[jax.Array] = None, *,
                   n_bins: int = 256, rows_per_block: int = 4096,
                   hist_dtype: str = "float32",
                   axis_name: Optional[str] = None,
                   hist_kernel: str = "auto",
                   bins_words_t: Optional[jax.Array] = None) -> jax.Array:
    """Root histogram from the TRANSPOSED [F, n] bin matrix: the one-leaf
    pass with every row in leaf 0."""
    # a full pass over every row, under the same scopes as the full
    # branch of ``histogram_for_leaves_auto``
    with jax.named_scope("hist_rows_full"), jax.named_scope("hist_kernel"):
        return histogram_for_leaf_masked(
            bins_t, grad, hess, jnp.zeros(grad.shape, jnp.int32),
            jnp.int32(0), row_mask, n_bins=n_bins,
            rows_per_block=rows_per_block, hist_dtype=hist_dtype,
            axis_name=axis_name, hist_kernel=hist_kernel,
            bins_words_t=bins_words_t)
