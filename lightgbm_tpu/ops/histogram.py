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
from typing import Optional

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
#:            the one-hot build floor binds (see _masked_kernel_for);
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


def wants_packed_mirror(hist_kernel, n_bins: int) -> bool:
    """True when the resolved masked-pass kernel may consume the packed
    word mirror — the callers' cue to keep ``bins_words_t`` resident."""
    hk = resolve_hist_kernel(hist_kernel)
    if hk == "packed":
        return True
    return hk == "auto" and not _radix_ok(n_bins) and not _no_packed()


def ladder_profitable(hist_kernel, n_bins: int) -> bool:
    """True when the batched grower's width-matched warmup ladder still
    pays: only where the K<=4 masked pass takes the radix-JOINT kernel,
    whose build scales with the leaf count (auto dispatch at >= 128
    bins).  Every other mode's masked kernel is K-independent below one
    MXU channel tile (round-3 measurement; packed/onehot/radix2 share
    one build per block), so those configs seed the round loop at full
    width straight from the root histogram instead — identical
    selections (widths always cover the frontier), fewer compiled round
    bodies (docs/PERF_NOTES.md round 6)."""
    return resolve_hist_kernel(hist_kernel) == "auto" and _radix_ok(n_bins)


def _no_packed() -> bool:
    import os
    return bool(os.environ.get("LGBMTPU_NO_PACKED"))  # perf A/B hatch


def _no_radix2() -> bool:
    import os
    return bool(os.environ.get("LGBMTPU_NO_RADIX2"))  # perf A/B hatch


def _no_overlap() -> bool:
    import os
    return bool(os.environ.get("LGBMTPU_NO_OVERLAP"))  # perf A/B hatch


def overlap_enabled(overlap: bool) -> bool:
    """Trace-time resolution of the overlapped-collective request:
    the caller's ``overlap`` flag gated by the ``LGBMTPU_NO_OVERLAP``
    A/B hatch.  Shared by :func:`reduce_hist` and the growers' scalar
    root reductions so one env var kills every overlapped schedule."""
    return bool(overlap) and not _no_overlap()


def reduce_hist(hist: jax.Array, axis_name: Optional[str],
                overlap: bool = False) -> jax.Array:
    """All-reduce a histogram across ``axis_name`` (no-op when serial).

    The single sink every histogram builder's cross-device reduction
    flows through (``collective_overlap``, ISSUE 7).  With ``overlap``
    off this is exactly the blocking ``lax.psum`` the builders always
    issued.  With it on (and a leading axis to split), the reduction is
    issued as TWO independent psums over disjoint leading-axis halves,
    concatenated back together.  Bit-identical to the single psum: the
    halves are disjoint slices, and each element still sums the same
    per-device contributions in the same deterministic all-reduce order
    — only the *scheduling* changes.  Two independent collective
    start/done pairs give XLA's latency-hiding scheduler (TPU) a window
    to overlap the first half's wire time with the second half's local
    compute, instead of one monolithic blocking all-reduce.

    ``LGBMTPU_NO_OVERLAP`` is the trace-time A/B hatch (same contract
    as ``LGBMTPU_NO_PACKED``): set it to force the single-psum schedule
    regardless of config.
    """
    if axis_name is None:
        return hist
    # device scope ``hist_allreduce`` (docs/OBSERVABILITY.md): a trace
    # finds the collective by this name, whatever XLA makes of it
    with jax.named_scope("hist_allreduce"):
        if overlap_enabled(overlap) and hist.ndim >= 1 \
                and int(hist.shape[0]) >= 2:
            k = int(hist.shape[0]) // 2
            lo = lax.psum(hist[:k], axis_name)
            hi = lax.psum(hist[k:], axis_name)
            return jnp.concatenate([lo, hi], axis=0)
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


def histogram_rows(bins: jax.Array, vals: jax.Array, *, n_bins: int,
                   rows_per_block: int = 4096,
                   hist_dtype: str = "float32") -> jax.Array:
    """Backend-dispatched histogram over a row set.

    bins: uint8 [S, F]; vals: f32 [S, C] (masked rows zero).
    Returns f32 [F, n_bins, C].
    """
    return histogram_rows_t(bins.T, vals.T, n_bins=n_bins,
                            rows_per_block=rows_per_block,
                            hist_dtype=hist_dtype)


def histogram_rows_t(bins_t: jax.Array, vals_t: jax.Array, *, n_bins: int,
                     rows_per_block: int = 4096,
                     hist_dtype: str = "float32") -> jax.Array:
    """Histogram from TRANSPOSED operands — the layout the TPU kernel wants
    (row dim on lanes).  Callers on the hot path keep ``bins_t`` [F, n]
    resident so no per-call 28-byte-strided transpose happens.

    bins_t: uint8 [F, S]; vals_t: f32 [C, S].  Returns f32 [F, n_bins, C].
    """
    if use_pallas():
        from .hist_pallas import histogram_pallas
        return histogram_pallas(bins_t, vals_t, n_bins=n_bins,
                                rows_per_block=min(rows_per_block,
                                                   _pallas_blk(hist_dtype, n_bins)),
                                compute_dtype=jnp.dtype(hist_dtype).type)
    return build_histogram(bins_t.T, vals_t.T, n_bins=n_bins,
                           rows_per_block=rows_per_block)


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


def _radix_ok(n_bins: int) -> bool:
    """The radix kernels decompose bin = 16*hi + lo (ops/hist_pallas.py
    ``_radix_shapes``); any other bin width falls back to the flat kernel.
    ``LGBMTPU_NO_RADIX=1`` disables them (perf A/B escape hatch).

    Below 128 bins the flat kernel wins outright: the radix build cost is
    nibble-bound (nhi + nlo one-hot elements — 20 at 64 bins vs the flat
    kernel's 64) but its small [p*nhi, 3*p*nlo] matmul tiles waste the
    MXU, measured 2.4 ms (radix joint) vs 1.7 ms (flat, full 63-bin K=42
    masked pass) on the live chip in round 5."""
    import os
    if os.environ.get("LGBMTPU_NO_RADIX"):
        return False
    return n_bins % 16 == 0 and n_bins >= 128


def histogram_for_leaf_masked(bins_t: jax.Array, grad: jax.Array,
                              hess: jax.Array, leaf_of_row: jax.Array,
                              leaf: jax.Array,
                              row_mask: Optional[jax.Array] = None, *,
                              n_bins: int = 256, rows_per_block: int = 4096,
                              hist_dtype: str = "float32",
                              axis_name: Optional[str] = None,
                              hist_kernel: str = "auto",
                              bins_words_t: Optional[jax.Array] = None,
                              overlap: bool = False
                              ) -> jax.Array:
    """Leaf histogram by masking: one full-data pass with non-leaf rows
    zeroed.  O(n) per call but with NO compaction machinery.  Under
    ``hist_kernel=auto`` on TPU the single-group radix kernel carries it
    (~1.7x the flat one-hot kernel, docs/PERF_NOTES.md round 3);
    ``bins_t`` is the TRANSPOSED [F, n] matrix."""
    hk = resolve_hist_kernel(hist_kernel)
    if (use_pallas() or _MODE_TEST_INTERPRET) and hk == "auto" \
            and _radix_ok(n_bins):
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
        return reduce_hist(hist, axis_name, overlap)
    leaf_arr = jnp.asarray(leaf, jnp.int32).reshape(1)
    hist = histogram_for_leaves_masked(
        bins_t, grad, hess, leaf_of_row, leaf_arr, row_mask, n_bins=n_bins,
        rows_per_block=rows_per_block, hist_dtype=hist_dtype,
        axis_name=axis_name, hist_kernel=hk, bins_words_t=bins_words_t,
        overlap=overlap)
    return hist[0]


def _masked_kernel_for(hk: str, n_bins: int, K: int, num_f: int,
                       have_words: bool) -> str:
    """Resolve the masked-pass kernel for a mode: one of
    flat / packed / radix2 / radix_joint.

    auto keeps the round-3 measured dispatch (radix joint at K<=4 and
    >= 128 bins) and routes the two cases the round-5 floor analysis
    proved formulation-bound to the new kernels: the >= 128-bin K>4
    masked pass (256-wide one-hot build, ~21% of int8 peak) to the
    shared-radix kernel, and the sub-128-bin masked pass (build-phase
    share grows as the dot shrinks, ~17% peak at 63 bins) to the
    packed-compare kernel.  Explicit modes force their kernel where its
    shape constraints hold and fall back to flat (bit-identical) where
    they don't."""
    from .hist_pallas import radix2_pick_p
    radix2_fits = (n_bins % 16 == 0 and n_bins >= 16
                   and radix2_pick_p(num_f, K, n_bins) > 0)
    if hk == "packed":
        return "packed" if have_words else "flat"
    if hk == "radix2":
        return "radix2" if radix2_fits else "flat"
    if hk == "auto":
        if _radix_ok(n_bins):
            if K <= 4:
                return "radix_joint"
            if radix2_fits and not _no_radix2():
                return "radix2"
        elif have_words and not _no_packed():
            return "packed"
    return "flat"


def histogram_for_leaves_masked(bins_t: jax.Array, grad: jax.Array,
                                hess: jax.Array, leaf_of_row: jax.Array,
                                leaves: jax.Array,
                                row_mask: Optional[jax.Array] = None, *,
                                n_bins: int = 256,
                                rows_per_block: int = 4096,
                                hist_dtype: str = "float32",
                                axis_name: Optional[str] = None,
                                hist_kernel: str = "auto",
                                bins_words_t: Optional[jax.Array] = None,
                                overlap: bool = False
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
    hk = resolve_hist_kernel(hist_kernel)
    K = leaves.shape[0]
    num_f = bins_t.shape[0]
    leaves = jnp.asarray(leaves, jnp.int32)
    lor = jnp.asarray(leaf_of_row, jnp.int32)
    if row_mask is not None:
        lor = jnp.where(row_mask, lor, -1)
    kern_active = use_pallas() or _MODE_TEST_INTERPRET
    kern = _masked_kernel_for(hk, n_bins, K, num_f,
                              bins_words_t is not None) \
        if kern_active else "xla"
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
        return reduce_hist(hist, axis_name, overlap)
    if kern == "radix2":
        from .hist_pallas import (histogram_leaves_radix2_pallas,
                                  radix2_pick_p)
        hist = histogram_leaves_radix2_pallas(
            bins_t, grad, hess, lor, leaves, n_bins=n_bins,
            rows_per_block=min(rows_per_block, 1024),
            p=radix2_pick_p(num_f, K, n_bins),
            compute_dtype=jnp.dtype(hist_dtype).type, interpret=interp)
        return reduce_hist(hist, axis_name, overlap)
    if kern == "packed":
        from .hist_pallas import histogram_leaves_packed_pallas
        hist = histogram_leaves_packed_pallas(
            bins_words_t, grad, hess, lor, leaves, num_f=num_f,
            n_bins=n_bins,
            rows_per_block=min(rows_per_block, _pallas_blk(hist_dtype, n_bins)),
            compute_dtype=jnp.dtype(hist_dtype).type, interpret=interp)
        return reduce_hist(hist, axis_name, overlap)
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
        hist = histogram_rows_t(bins_t, vals_t, n_bins=n_bins,
                                rows_per_block=rows_per_block,
                                hist_dtype=hist_dtype)        # [F, B, C*K]
        F, B = hist.shape[0], hist.shape[1]
        hist = hist.reshape(F, B, C, K).transpose(3, 0, 1, 2)  # [K, F, B, C]
    return reduce_hist(hist, axis_name, overlap)


def _rows_leaves_hist(bins_rows: jax.Array, grad: jax.Array,
                      hess: jax.Array, lor: jax.Array, leaves: jax.Array, *,
                      n_bins: int, rows_per_block: int,
                      hist_dtype: str) -> jax.Array:
    """[K, F, B, C] histograms from row-major bins (backend-dispatched)."""
    if use_pallas():
        from .hist_pallas import histogram_leaves_rows_pallas
        return histogram_leaves_rows_pallas(
            bins_rows, grad, hess, lor, leaves, n_bins=n_bins,
            rows_per_block=min(rows_per_block, _pallas_blk(hist_dtype, n_bins)),
            compute_dtype=jnp.dtype(hist_dtype).type)
    return histogram_for_leaves_masked(
        jnp.asarray(bins_rows).T, grad, hess, lor, leaves, None,
        n_bins=n_bins, rows_per_block=rows_per_block, hist_dtype=hist_dtype,
        hist_kernel="onehot")


# test hook: lets the CPU suite exercise the payload Pallas kernel via the
# interpreter (use_pallas() is False off-TPU)
_PAYLOAD_TEST_INTERPRET = False


def _use_payload_kernel() -> bool:
    import os
    if os.environ.get("LGBMTPU_NO_PAYLOAD_KERNEL"):  # perf A/B escape hatch
        return False
    return use_pallas() or _PAYLOAD_TEST_INTERPRET


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
                              bins_words_t: Optional[jax.Array] = None,
                              overlap: bool = False
                              ) -> jax.Array:
    """K-leaf histograms with frontier compaction -> f32 [K, F, B, C].

    The TPU reformulation of the reference's O(smaller-child) histogram cost
    (serial_tree_learner.cpp:364-378 iterates only the leaf's data indices):
    when the rows belonging to ``leaves`` fit a power-of-two bucket, one
    streaming pass over the resident lane-dense bins
    (``compact_payload_pallas``) moves them, in row order, into an i32 WORD
    payload with the compacted positions on the lanes (4 bin bytes per word
    + grad/hess/leaf words) and the payload kernel runs on the bucket;
    otherwise one full masked pass (``histogram_for_leaves_masked``).
    Total histogram work per tree drops from O(n x rounds) to ~O(n log L),
    which the flat masked pass cannot do.  Exact: the same rows contribute
    either way.  Off the TPU the compacted bucket is a sort of the keys and
    a row gather of the row-major payload, the reference the kernels are
    tested against.

    A leaf-GROUPED compaction variant (rows sorted by leaf, block->leaf
    scalar-prefetch steering) was built and measured slower end-to-end in
    round 3 — the K-channel MXU multiplier it removes does not exist below
    128 output channels, while its layout glue is real — and was deleted
    (docs/PERF_NOTES.md round 3).

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
    sizes = []
    for d in buckets:
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
        if _use_payload_kernel():
            from .hist_pallas import (compact_payload_pallas,
                                      histogram_payload_pallas)
            interp = not use_pallas()
            # the compaction is a second pallas_call of the pass: it
            # stays OUTSIDE hist_rows_<S>, so that a trace counts the
            # pass once and its time goes to hist_compact
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
        # of the row-major payload, unpack and run the generic rows path
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
                return _rows_leaves_hist(rows_c, grad_c, hess_c, lor_c,
                                         leaves, n_bins=n_bins,
                                         rows_per_block=rows_per_block,
                                         hist_dtype=hist_dtype)

    branches = [full_branch] + [functools.partial(compacted, s)
                                for s in sizes]
    with jax.named_scope("hist_compact"):
        j = jnp.int32(0)
        for k, s in enumerate(sizes):  # sizes descending: smallest fit wins
            j = jnp.where(cnt <= s, jnp.int32(k + 1), j)
    hist = lax.switch(j, branches, (sort_key, grad, hess, lor))
    with jax.named_scope("hist_kernel"):
        return reduce_hist(hist, axis_name, overlap)


def histogram_for_leaf_bucketed(bins: jax.Array, grad: jax.Array,
                                hess: jax.Array, leaf_of_row: jax.Array,
                                leaf: jax.Array, leaf_count: jax.Array,
                                row_mask: Optional[jax.Array] = None, *,
                                n_bins: int = 256, rows_per_block: int = 4096,
                                min_bucket: int = 8192, hist_dtype: str = "float32",
                                axis_name: Optional[str] = None,
                                overlap: bool = False) -> jax.Array:
    """Histogram of one leaf touching only ~leaf_count rows.

    The TPU reformulation of the reference's ordered-index iteration
    (CUDADataPartition keeps rows physically grouped by leaf;
    dense_bin.hpp iterates data_indices): rows stay in place, but the
    leaf's row indices are compacted with a sized ``nonzero`` and gathered
    into the smallest power-of-two buffer that fits (``lax.switch`` over
    log2(n) precompiled bucket sizes), so histogram cost follows the
    smaller child's size instead of the full dataset — preserving the
    O(n log L) total work of leaf-wise growth with histogram subtraction
    (serial_tree_learner.cpp:364-378).

    ``leaf_count`` is the number of rows in ``leaf`` (device scalar).
    """
    n = bins.shape[0]
    mask = (leaf_of_row == leaf)
    if row_mask is not None:
        mask = mask & row_mask

    # bucket sizes n, n/2, n/4, ..., >= min_bucket
    sizes = []
    s = _round_up(n, 128)
    while True:
        sizes.append(s)
        if s <= min_bucket:
            break
        s = _round_up((s + 1) // 2, 128)
    # branch index: largest j with sizes[j] >= count
    count = jnp.maximum(leaf_count.astype(jnp.int32), 1)
    j = jnp.int32(0)
    for k, sz in enumerate(sizes):
        j = jnp.where(count <= sz, jnp.int32(k), j)

    def make_branch(sz: int):
        def branch(operands):
            mask_, grad_, hess_ = operands
            idx = jnp.nonzero(mask_, size=sz, fill_value=n)[0]
            valid = (idx < n).astype(grad_.dtype)
            idxc = jnp.minimum(idx, n - 1)
            b_sub = bins[idxc]
            g_sub = grad_[idxc] * valid
            h_sub = hess_[idxc] * valid
            vals = jnp.stack([g_sub, h_sub, valid, jnp.zeros_like(valid)],
                             axis=1)
            return histogram_rows(b_sub, vals, n_bins=n_bins,
                                  rows_per_block=rows_per_block,
                                  hist_dtype=hist_dtype)
        return branch

    hist = lax.switch(j, [make_branch(sz) for sz in sizes],
                      (mask, grad, hess))
    return reduce_hist(hist, axis_name, overlap)


def root_histogram(bins_t: jax.Array, grad: jax.Array, hess: jax.Array,
                   row_mask: Optional[jax.Array] = None, *,
                   n_bins: int = 256, rows_per_block: int = 4096,
                   hist_dtype: str = "float32",
                   axis_name: Optional[str] = None,
                   hist_kernel: str = "auto",
                   bins_words_t: Optional[jax.Array] = None,
                   overlap: bool = False) -> jax.Array:
    """Root histogram from the TRANSPOSED [F, n] bin matrix."""
    hist_kernel = resolve_hist_kernel(hist_kernel)
    # a full pass over every row, under the same scopes as the full
    # branch of ``histogram_for_leaves_auto``
    with jax.named_scope("hist_rows_full"), jax.named_scope("hist_kernel"):
        if use_pallas() or _MODE_TEST_INTERPRET:
            # single-leaf delegation picks the mode kernel (radix single
            # under auto when bins allow, packed/radix2/flat otherwise)
            lor = jnp.zeros(grad.shape, jnp.int32)
            return histogram_for_leaf_masked(
                bins_t, grad, hess, lor, jnp.int32(0), row_mask,
                n_bins=n_bins, rows_per_block=rows_per_block,
                hist_dtype=hist_dtype, axis_name=axis_name,
                hist_kernel=hist_kernel, bins_words_t=bins_words_t,
                overlap=overlap)
        m = jnp.ones_like(grad) if row_mask is None \
            else row_mask.astype(grad.dtype)
        vals_t = jnp.stack([jnp.where(m > 0, grad, 0.0),
                            jnp.where(m > 0, hess, 0.0), m,
                            jnp.zeros_like(m)], axis=0)
        hist = histogram_rows_t(bins_t, vals_t, n_bins=n_bins,
                                rows_per_block=rows_per_block,
                                hist_dtype=hist_dtype)
        return reduce_hist(hist, axis_name, overlap)
