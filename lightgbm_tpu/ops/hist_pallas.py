"""Pallas TPU histogram kernel.

TPU-native equivalent of the reference's hot CUDA kernel (reference:
src/treelearner/cuda/cuda_histogram_constructor.cu:18
``CUDAConstructHistogramDenseKernel`` — per-block shared-memory atomic
scatter-add then global flush).  The TPU has no fast scatter, so the kernel
reformulates the histogram as MXU one-hot contractions with the one-hot
existing ONLY in VMEM (never materialized to HBM — the reason a plain XLA
einsum can't be used on the hot path):

  grid step = one row block; per feature chunk:
    onehot[fc*B, R] = (bins[fc, r] == iota_B)    built in VMEM, bf16
    out[C, fc*B]   += vals[C, R] @ onehot^T      MXU, f32 accumulation

Layouts put the row dimension last (lane dim, 128-aligned):
  bins_T [F, n] uint8, vals_T [C, n] f32, out [C, F*B] f32.
The sequential TPU grid revisits the same output block, giving cheap
cross-block accumulation (zeroed at step 0 via pl.when).

The contraction dtype defaults to float32 for split-decision parity with
the reference (its CUDA learner accumulates fp64 by default, config.h:1129
``gpu_use_dp``).  Set ``tpu_hist_dtype=bfloat16`` in the Config to run the
MXU contraction at ~8x rate: the one-hot stays exact and accumulation is
f32, only grad/hess suffer ~2^-9 relative input rounding — the count
channel stays exact since 1.0 is representable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_fc(num_f: int, requested: int = 0) -> int:
    """Feature-chunk size minimizing feature padding (0 = auto).

    The kernel pads F up to a multiple of the chunk; a chunk that divides F
    exactly (e.g. 14 for Higgs' 28 features instead of a fixed 8, which
    padded to 32) cuts ~15% of one-hot work — measured ~5.5 vs ~7.3 ms per
    full 1M-row pass (docs/PERF_NOTES.md).
    """
    if requested:
        return min(requested, num_f)
    if num_f <= 16:
        return num_f
    best, best_pad = 8, _round_up(num_f, 8)
    for fc in (16, 15, 14, 13, 12, 11, 10, 9, 8, 7, 6, 5, 4):
        pad = _round_up(num_f, fc)
        if pad < best_pad or (pad == best_pad and fc > best):
            best, best_pad = fc, pad
    return best


def _prec(compute_dtype):
    """MXU precision for the one-hot contraction.

    The TPU's default f32 matmul runs ONE bf16 pass (~2^-8 product
    rounding), which silently breaks the float32 split-parity contract
    (docs/PERF_NOTES.md).  HIGHEST makes products exact so f32 accumulation
    is the only rounding left (Mosaic supports only DEFAULT/HIGHEST).
    """
    return (lax.Precision.HIGHEST if jnp.dtype(compute_dtype) == jnp.float32
            else lax.Precision.DEFAULT)


def _oh_contract(vals, oh_b, compute_dtype):
    """vals [C, blk] (compute-dtype for float modes, int8 for int mode)
    x bool one-hot [M, blk] -> [C, M] in the ACCUMULATOR dtype
    (``_acc_dtype``): int32 for int8 mode, f32 otherwise.  The shared
    int8/float dot used by the flat masked, payload and plain kernels.

    int8 keeps the accumulator INTEGER end-to-end: f32 `+=` across row
    blocks rounds beyond 2^24 (at Higgs 10.5M rows the per-node error
    random-walks to ~1e2 level units and histogram SUBTRACTION hands
    that error to small children — measured as a 0.04 AUC drop at 10.5M
    x 500 iters, round 4); i32 is exact to 2^31 with ONE deterministic
    f32 rounding at kernel exit."""
    if _is_int8(compute_dtype):
        oh = oh_b.astype(jnp.int8)
        return lax.dot_general(
            vals, oh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
    oh = oh_b.astype(compute_dtype)
    return lax.dot_general(vals, oh, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=_prec(compute_dtype))


def _acc_dtype(compute_dtype):
    return jnp.int32 if _is_int8(compute_dtype) else jnp.float32


def _is_int8(compute_dtype) -> bool:
    """int8 MXU mode: quantized-gradient levels ride the int8 systolic
    path (~1.6x the bf16 rate measured on v5e, docs/PERF_NOTES.md round
    4).  Valid ONLY when grad/hess carry small-integer values (the
    ``use_quantized_grad`` contract, ops/quantize.py): products are
    exact int32 and the f32 accumulation bound matches the bf16 mode's.
    Mosaic legalizes bool->i8 and i32<->i8 casts and i8 dots on this
    toolchain (the round-3 note claiming otherwise predates it); i8
    elementwise multiplies still do NOT legalize, so masked values are
    built in i32 and cast to i8 just before the dot."""
    return jnp.dtype(compute_dtype) == jnp.int8


@functools.partial(jax.jit,
                   static_argnames=("n_bins", "rows_per_block",
                                    "feats_per_chunk", "compute_dtype",
                                    "interpret"))
def _histogram_leaves_impl(bins_t: jax.Array, grad: jax.Array,
                           hess: jax.Array, leaf_of_row: jax.Array,
                           leaves: jax.Array, *, n_bins: int,
                           rows_per_block: int = 2048,
                           feats_per_chunk: int = 0,
                           compute_dtype=jnp.bfloat16,
                           interpret: bool = False) -> jax.Array:
    """Fused masked multi-leaf histogram: f32 [K, F, n_bins, 4].

    Builds the per-leaf (grad, hess, count) value channels INSIDE the kernel
    (sel masks live only in VMEM), so K leaves cost one one-hot pass with no
    [3K, n] HBM materialization — the separate mask+stack stage measured
    ~12 ms/round at K=16 on 1M rows, ~2x the whole kernel (docs/PERF_NOTES.md).

    ``bins_t``: u8 [F, n] transposed (the resident training layout).
    grad/hess: f32 [n]; leaf_of_row: i32 [n] (-1 = excluded row, e.g.
    bagging); leaves: i32 [K] (dummy slots may repeat).  Channel 3 of the
    output is zero padding for API parity.
    """
    num_f, n = bins_t.shape
    K = leaves.shape[0]
    blk = min(rows_per_block, max(128, _round_up(n, 128)))
    n_pad = _round_up(max(n, 1), blk)
    with jax.named_scope("hist_compact"):
        if n_pad != n:
            bins_t = jnp.pad(bins_t, ((0, 0), (0, n_pad - n)))
            grad = jnp.pad(grad, (0, n_pad - n))
            hess = jnp.pad(hess, (0, n_pad - n))
            leaf_of_row = jnp.pad(leaf_of_row, (0, n_pad - n),
                                  constant_values=-1)
        fc = _pick_fc(num_f, feats_per_chunk)
        f_pad = _round_up(num_f, fc)
        if f_pad != num_f:
            bins_t = jnp.pad(bins_t, ((0, f_pad - num_f), (0, 0)))
    nb = n_pad // blk
    grad2 = grad[None, :]
    hess2 = hess[None, :]
    lor2 = leaf_of_row[None, :]
    leaves2 = leaves[None, :]

    def kernel(bins_ref, g_ref, h_ref, lor_ref, leaves_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        lor_b = lor_ref[0, :]                               # [blk] i32
        sel = lor_b[None, :] == leaves_ref[0, :][:, None]   # [K, blk]
        if _is_int8(compute_dtype):
            # integer masking by multiply is NaN-safe (0 * anything = 0
            # in int); levels are small ints so f32->i32 is exact
            seli = sel.astype(jnp.int32)
            gm = seli * g_ref[0, :][None, :].astype(jnp.int32)
            hm = seli * h_ref[0, :][None, :].astype(jnp.int32)
            vals = jnp.concatenate([gm, hm, seli], axis=0).astype(jnp.int8)
        else:
            m = sel.astype(jnp.float32)
            # where(), not multiply: 0 * NaN = NaN would let one bad row
            # (e.g. a custom objective emitting NaN on an excluded row)
            # poison sums
            gm = jnp.where(sel, g_ref[0, :][None, :], 0.0)  # [K, blk]
            hm = jnp.where(sel, h_ref[0, :][None, :], 0.0)
            vals = jnp.concatenate([gm, hm, m], axis=0).astype(compute_dtype)
        b_blk = bins_ref[:].astype(jnp.int32)
        iota = lax.iota(jnp.int32, n_bins)
        for f0 in range(0, f_pad, fc):
            chunk = b_blk[f0:f0 + fc]                       # [fc, blk]
            oh_b = (chunk[:, None, :] == iota[None, :, None]
                    ).reshape(fc * n_bins, blk)
            acc = _oh_contract(vals, oh_b, compute_dtype)      # [3K, fc*B]
            out_ref[:, f0 * n_bins:(f0 + fc) * n_bins] += acc

    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((f_pad, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((3 * K, f_pad * n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((3 * K, f_pad * n_bins),
                                       _acc_dtype(compute_dtype)),
        interpret=interpret,
    )(bins_t, grad2, hess2, lor2, leaves2)
    out = out.astype(jnp.float32)
    # [3K, F*B] -> [K, F, B, 3] -> pad channel dim to 4
    out = out.reshape(3, K, f_pad, n_bins)[:, :, :num_f]
    out = out.transpose(1, 2, 3, 0)
    return jnp.pad(out, ((0, 0), (0, 0), (0, 0), (0, 1)))


#: the public name; the jitted function keeps its own, which is how a
#: device trace and the ledger's ``device_ops`` know the flat kernel
histogram_leaves_pallas = _histogram_leaves_impl


@functools.partial(jax.jit,
                   static_argnames=("num_f", "n_bins", "rows_per_block",
                                    "compute_dtype", "interpret"))
def histogram_payload_pallas(payload: jax.Array, leaves: jax.Array,
                             cnt: jax.Array, *, num_f: int, n_bins: int,
                             rows_per_block: int = 1024,
                             compute_dtype=jnp.bfloat16,
                             interpret: bool = False) -> jax.Array:
    """Masked multi-leaf histogram CONSUMING the compaction payload
    directly: f32 [K, F, n_bins, 4] from i32 words.

    ``payload``: i32 [>= W+3, S] with W = ceil(num_f/4), the compacted
    positions on the lanes (``compact_payload_pallas``'s result, handed
    straight in): row j < W packs 4 bin bytes per position
    (little-endian: feature 4j+k is byte k), then one row of grad bits,
    one of hess bits and one of leaf ids; further rows are ignored.
    Positions >= ``cnt`` (i32 [1]) hold no selected row and are excluded
    in-kernel whatever they contain, and so is the tail of a last block
    that reaches past S (no operand is padded).

    The grid stops at the count: a step whose first position is at or
    past ``cnt`` runs no body, and its block index is clamped to the last
    block that holds a selected position, so it fetches nothing either.
    A pass costs what its ``cnt`` rows cost, whatever S the caller's
    bucket has; with ``cnt == 0`` the result is zeros.  The skipped
    positions added zeros before, so the sums are the same bit for bit.

    Equivalent to ``histogram_leaves_pallas`` on the unpacked, transposed
    operands; the contraction runs per word (fc = 4 features).
    """
    rows, S = payload.shape
    W = pl.cdiv(num_f, 4)
    assert rows >= W + 3
    K = leaves.shape[0]
    blk = min(rows_per_block, max(128, _round_up(S, 128)))
    f_pad = 4 * W

    def kernel(cnt_ref, payload_ref, leaves_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        @pl.when(step * blk < cnt_ref[0])
        def _():
            g = lax.bitcast_convert_type(payload_ref[W], jnp.float32)
            h = lax.bitcast_convert_type(payload_ref[W + 1], jnp.float32)
            lor_b = payload_ref[W + 2]
            iota_r = lax.iota(jnp.int32, blk)
            pos_ok = step * blk + iota_r < cnt_ref[0]       # [blk]
            sel = (lor_b[None, :] == leaves_ref[0, :][:, None]) \
                & pos_ok[None, :]                           # [K, blk]
            if _is_int8(compute_dtype):
                # int multiply masking is NaN-safe; levels fit int8
                seli = sel.astype(jnp.int32)
                gm = seli * g[None, :].astype(jnp.int32)
                hm = seli * h[None, :].astype(jnp.int32)
                vals = jnp.concatenate([gm, hm, seli],
                                       axis=0).astype(jnp.int8)
            else:
                m = sel.astype(jnp.float32)
                # where(), not multiply: positions past cnt can carry NaN
                gm = jnp.where(sel, g[None, :], 0.0)
                hm = jnp.where(sel, h[None, :], 0.0)
                vals = jnp.concatenate([gm, hm, m],
                                       axis=0).astype(compute_dtype)
            iota = lax.iota(jnp.int32, n_bins)
            # (a 4-words-per-dot widening was tried in round 4 and measured
            # neutral)
            for j in range(W):
                w = payload_ref[j]                          # [blk] i32
                chunk = jnp.stack([w & 255, (w >> 8) & 255, (w >> 16) & 255,
                                   (w >> 24) & 255])        # [4, blk]
                oh_b = (chunk[:, None, :] == iota[None, :, None]
                        ).reshape(4 * n_bins, blk)
                acc = _oh_contract(vals, oh_b, compute_dtype)  # [3K, 4B]
                out_ref[:, j * 4 * n_bins:(j + 1) * 4 * n_bins] += acc

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(S, blk),),
        in_specs=[
            # a step past the count keeps the last needed block: no DMA
            pl.BlockSpec((rows, blk), lambda i, c: (
                0, jnp.minimum(i, jnp.maximum(c[0] - 1, 0) // blk))),
            pl.BlockSpec((1, K), lambda i, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((3 * K, f_pad * n_bins), lambda i, c: (0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((3 * K, f_pad * n_bins),
                                       _acc_dtype(compute_dtype)),
        interpret=interpret,
    )(jnp.asarray(cnt, jnp.int32).reshape(1), payload, leaves[None, :])
    out = out.astype(jnp.float32)
    out = out.reshape(3, K, f_pad, n_bins)[:, :, :num_f]
    out = out.transpose(1, 2, 3, 0)
    return jnp.pad(out, ((0, 0), (0, 0), (0, 0), (0, 1)))


_GROUP = 32     # payload rows a byte-plane group holds: 4 x 32 MXU rows


@functools.partial(jax.jit,
                   static_argnames=("size", "rows_per_block",
                                    "lanes_per_dot", "interpret"))
def compact_payload_pallas(src: jax.Array, key: jax.Array, grad: jax.Array,
                           hess: jax.Array, leaf_of_row: jax.Array, *,
                           size: int, rows_per_block: int = 1024,
                           lanes_per_dot: int = 256,
                           interpret: bool = False) -> jax.Array:
    """Stream the selected rows into the lane-dense payload of
    ``histogram_payload_pallas``: i32 [round_up(W+3, 8), >= size], one
    pass over all n rows, each read once and contiguously.

    ``src``: the resident lane-dense bins, either the packed mirror
    ``words_t`` i32 [W, n] or ``bins_t`` u8 [F, n] (W = ceil(F/4));
    ``key`` i32 [n]: a row is selected when its key is below 2^30 (the
    fused partition kernel's sort keys); ``grad``/``hess`` f32 and
    ``leaf_of_row`` i32 [n] ride along as words of their bits.

    The selected row of rank r (ascending row order) lands in column r:
    columns [0, cnt) are bit for bit
    ``payload.T[:, sort(key)[:cnt] & (2^30 - 1)]`` of the row-major
    ``[n, W+3]`` payload; columns from cnt on hold zeros or nothing
    written at all, for the consumer's position guard.  Where more than
    the output's columns are selected the excess is dropped.

    Per row block: the rank inside the block is a prefix sum of the mask
    along the lanes (log2(blk) rolls), the running count across blocks a
    scalar in SMEM.  The block's payload words are split into their four
    BYTE planes (0..255: exact in bfloat16) and contracted on the MXU
    against ``onehot(target column)``, one non-zero term per output, then
    reassembled into words.  Only the ``lanes_per_dot``-wide column
    windows the block's rows fall in are contracted, so the work follows
    the number of selected rows; a block with every row selected fills
    two output blocks, one with none touches nothing.  The result gathers
    in a two-block VMEM ring and each full output block leaves by one DMA.

    A u8 ``src`` is first brought to the byte-plane order on the MXU too
    (feature 4j+k is byte k of word j: the same permutation for every
    128 features), so both sources give the same words.
    """
    n = key.shape[0]
    from_bytes = src.dtype == jnp.uint8
    F = src.shape[0]
    W = pl.cdiv(F, 4) if from_bytes else F
    G = pl.cdiv(W + 3, _GROUP)
    R = _GROUP * G              # payload rows, in whole groups
    r_out = _round_up(W + 3, 8)  # a DMA moves whole sublane tiles
    # the scratch grows with G (about 1.5 MB a group at 1024 rows): the
    # block shrinks with it, by powers of two so that ch divides it
    blk = min(rows_per_block, max(128, 1 << (4096 // G).bit_length() - 1),
              max(128, _round_up(n, 128)))
    ch = min(lanes_per_dot, blk)
    assert blk % ch == 0 and ch % 128 == 0, (blk, ch)
    nb = pl.cdiv(n, blk)
    nb_out = pl.cdiv(max(size, 1), blk)
    f_rows = 128 * pl.cdiv(F, 128)

    def byte_planes(x):
        # i32 [R, blk] -> bf16 [4R, blk]: per group, byte plane k of its
        # 32 rows at MXU rows [32k, 32k + 32)
        return jnp.concatenate(
            [((x[_GROUP * gi:_GROUP * (gi + 1)] >> (8 * k)) & 255)
             .astype(jnp.float32).astype(jnp.bfloat16)
             for gi in range(G) for k in range(4)], axis=0)

    def kernel(src_ref, key_ref, g_ref, h_ref, lor_ref, out_ref, x_ref,
               *scratch):
        bytes_ref = scratch[0] if from_bytes else None
        ring_ref, count_ref, sem = scratch[-3:]
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            x_ref[...] = jnp.zeros_like(x_ref)
            if from_bytes:
                bytes_ref[...] = jnp.zeros_like(bytes_ref)
            ring_ref[...] = jnp.zeros_like(ring_ref)
            count_ref[0] = 0        # selected rows before this block
            count_ref[1] = 0        # 1 while an output block's DMA runs

        base = count_ref[0]
        ob = base // blk            # the output block being filled
        off = base - ob * blk
        lane = lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        # the last block's tail is masked by row number: no operand is
        # padded
        m = (key_ref[...] < (1 << 30)) & (step * blk + lane < n)
        ones = m.astype(jnp.int32)
        c = jnp.sum(ones)
        rank, s = ones, 1
        while s < blk:              # inclusive prefix sum along the lanes
            rank = rank + jnp.where(lane >= s,
                                    pltpu.roll(rank, s, axis=1), 0)
            s *= 2
        # column in the ring, [0, 2 blk); -1 matches no column
        t = jnp.where(m, off + rank - 1, -1)                 # [1, blk]

        if not from_bytes:
            x_ref[0:W, :] = src_ref[...]
        x_ref[W:W + 1, :] = g_ref[...]
        x_ref[W + 1:W + 2, :] = h_ref[...]
        x_ref[W + 2:W + 3, :] = lor_ref[...]
        planes = byte_planes(x_ref[...])                     # [4R, blk]
        if from_bytes:
            bytes_ref[0:F, :] = src_ref[...].astype(jnp.int32).astype(
                jnp.float32)
            p_i = lax.broadcasted_iota(jnp.int32, (128, 128), 0)
            f_i = lax.broadcasted_iota(jnp.int32, (128, 128), 1)
            perm = (f_i == 4 * (p_i % _GROUP) + p_i // _GROUP).astype(
                jnp.float32).astype(jnp.bfloat16)
            moved = [lax.dot_general(
                perm, bytes_ref[128 * gi:128 * (gi + 1), :].astype(
                    jnp.bfloat16), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                if 128 * gi < F else jnp.zeros((128, blk), jnp.float32)
                for gi in range(G)]
            # the word rows of x_ref are zero here: the sum is a merge
            planes = (planes.astype(jnp.float32)
                      + jnp.concatenate(moved, axis=0)).astype(jnp.bfloat16)

        def flush(block):
            return pltpu.make_async_copy(
                ring_ref.at[block % 2, 0:r_out, :],
                out_ref.at[:, pl.ds(pl.multiple_of(block * blk, blk), blk)],
                sem)

        @pl.when(count_ref[1] == 1)
        def _():
            # block ob - 1 left in the step before: its half of the ring
            # is block ob + 1's from here on
            flush(ob - 1).wait()
            ring_ref[(ob + 1) % 2] = jnp.zeros((R, blk), jnp.int32)
            count_ref[1] = 0

        for q in range(2 * blk // ch):
            lo = q * ch

            @pl.when((c > 0) & (off + c > lo) & (off < lo + ch))
            def _():
                col = lax.broadcasted_iota(jnp.int32, (ch, blk), 0) + lo
                oh = (t == col).astype(jnp.float32).astype(jnp.bfloat16)
                d = lax.dot_general(
                    planes, oh, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32).astype(jnp.int32)
                def plane(gi, k):       # byte plane k of group gi, in place
                    at = (4 * gi + k) * _GROUP
                    return d[at:at + _GROUP] << (8 * k)

                words = jnp.concatenate(
                    [plane(gi, 0) | plane(gi, 1) | plane(gi, 2) | plane(gi, 3)
                     for gi in range(G)], axis=0)               # [R, ch]
                at = lo % blk
                ring_ref[(ob + lo // blk) % 2, :, at:at + ch] += words

        count_ref[0] = base + c
        filled = (base + c) // blk      # ob or ob + 1

        @pl.when((filled > ob) & (ob < nb_out))
        def _():
            flush(ob).start()
            count_ref[1] = 1

        @pl.when(step == nb - 1)
        def _():
            @pl.when(count_ref[1] == 1)
            def _():
                flush(ob).wait()

            @pl.when((base + c > filled * blk) & (filled < nb_out))
            def _():
                last = flush(filled)
                last.start()
                last.wait()

    def rows_block(r):
        return pl.BlockSpec((r, blk), lambda i: (0, i))

    scratch = [pltpu.VMEM((R, blk), jnp.int32)]
    if from_bytes:
        scratch.append(pltpu.VMEM((f_rows, blk), jnp.float32))
    scratch += [pltpu.VMEM((2, R, blk), jnp.int32),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SemaphoreType.DMA(())]
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[rows_block(F)] + [rows_block(1)] * 4,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((r_out, nb_out * blk), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(src, key[None, :],
      lax.bitcast_convert_type(grad, jnp.int32)[None, :],
      lax.bitcast_convert_type(hess, jnp.int32)[None, :],
      jnp.asarray(leaf_of_row, jnp.int32)[None, :])


def _swar_byte_eq_planes(word: jax.Array, iota_bins: jax.Array):
    """Per-byte equality one-hot planes from PACKED bin words.

    ``word``: i32 [blk], 4 feature bins per lane (little-endian);
    ``iota_bins``: i32 [B].  Returns i32 0/1 [4, B, blk] — plane k is the
    one-hot of feature k's bin.

    The round-5 floor analysis pinned the flat kernel at ~21% of int8
    peak because the one-hot BUILD runs 32-bit vector compares — one
    compare per (feature, bin, row) element, and v5e has no sub-32-bit
    vector cmp (round-4 probe: "Target does not support this
    comparison").  Packing 4 bins per lane makes each 32-bit op carry 4
    features: XOR against the replicated-bin pattern ``b * 0x01010101``
    and an exact SWAR zero-byte detect (the carry-free
    ``~(((x & 0x7f..) + 0x7f..) | x | 0x7f..)`` form — per-byte exact,
    unlike the borrow-propagating ``x - 0x01010101`` variant) compress
    the 4 compares into 2 lane ops; the per-feature bit extraction is
    shifts/masks, which the VPU issues independently of the compare
    port.  Compare-op count per (word, bin, row): 2 vs the flat
    kernel's 4 — the "packed" mode's throughput claim (chip A/B pends a
    device window; docs/PERF_NOTES.md round 6)."""
    rep = jnp.int32(0x01010101)
    low7 = jnp.int32(0x7F7F7F7F)
    x = word[None, :] ^ (iota_bins * rep)[:, None]          # [B, blk]
    z = ~(((x & low7) + low7) | x | low7)   # byte k high bit <=> byte k == 0
    planes = [((z >> (8 * k + 7)) & 1) for k in range(4)]   # i32 0/1 [B, blk]
    return jnp.stack(planes)                                # [4, B, blk]


@functools.partial(jax.jit,
                   static_argnames=("num_f", "n_bins", "rows_per_block",
                                    "compute_dtype", "interpret"))
def histogram_leaves_packed_pallas(words_t: jax.Array, grad: jax.Array,
                                   hess: jax.Array, leaf_of_row: jax.Array,
                                   leaves: jax.Array, *, num_f: int,
                                   n_bins: int, rows_per_block: int = 2048,
                                   compute_dtype=jnp.bfloat16,
                                   interpret: bool = False) -> jax.Array:
    """Masked multi-leaf histogram from the PACKED-word bin mirror:
    f32 [K, F, n_bins, 4].

    ``words_t``: i32 [W, n] transposed packed mirror (4 uint8 bins per
    word, little-endian — ``ops/histogram.bins_to_words(bins).T``; kept
    resident by the dataset/grower so no per-call bitcast happens).
    Equivalent to ``histogram_leaves_pallas`` on the unpacked operands —
    same masked value channels, same accumulator dtype contract — with
    the one-hot built 4-features-per-lane (``_swar_byte_eq_planes``).
    """
    W, n = words_t.shape
    assert 4 * W >= num_f
    K = leaves.shape[0]
    blk = min(rows_per_block, max(128, _round_up(n, 128)))
    n_pad = _round_up(max(n, 1), blk)
    with jax.named_scope("hist_compact"):
        if n_pad != n:
            # pad rows carry word 0 and lor -1: excluded by the sel mask
            words_t = jnp.pad(words_t, ((0, 0), (0, n_pad - n)))
            grad = jnp.pad(grad, (0, n_pad - n))
            hess = jnp.pad(hess, (0, n_pad - n))
            leaf_of_row = jnp.pad(leaf_of_row, (0, n_pad - n),
                                  constant_values=-1)
    nb = n_pad // blk
    f_pad = 4 * W

    def kernel(words_ref, g_ref, h_ref, lor_ref, leaves_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        lor_b = lor_ref[0, :]                               # [blk] i32
        sel = lor_b[None, :] == leaves_ref[0, :][:, None]   # [K, blk]
        if _is_int8(compute_dtype):
            # integer masking by multiply is NaN-safe post-cast
            seli = sel.astype(jnp.int32)
            gm = seli * g_ref[0, :][None, :].astype(jnp.int32)
            hm = seli * h_ref[0, :][None, :].astype(jnp.int32)
            vals = jnp.concatenate([gm, hm, seli], axis=0).astype(jnp.int8)
        else:
            m = sel.astype(jnp.float32)
            # where(), not multiply: 0 * NaN = NaN would poison sums
            gm = jnp.where(sel, g_ref[0, :][None, :], 0.0)
            hm = jnp.where(sel, h_ref[0, :][None, :], 0.0)
            vals = jnp.concatenate([gm, hm, m], axis=0).astype(compute_dtype)
        iota = lax.iota(jnp.int32, n_bins)
        for j in range(W):
            planes = _swar_byte_eq_planes(words_ref[j], iota)  # [4, B, blk]
            oh_i = planes.reshape(4 * n_bins, blk)
            if _is_int8(compute_dtype):
                oh = oh_i.astype(jnp.int8)
                acc = lax.dot_general(vals, oh, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.int32)
            else:
                oh = oh_i.astype(compute_dtype)
                acc = lax.dot_general(vals, oh, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=_prec(compute_dtype))
            out_ref[:, j * 4 * n_bins:(j + 1) * 4 * n_bins] += acc

    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((W, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((3 * K, f_pad * n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((3 * K, f_pad * n_bins),
                                       _acc_dtype(compute_dtype)),
        interpret=interpret,
    )(words_t, grad[None, :], hess[None, :], leaf_of_row[None, :],
      leaves[None, :])
    out = out.astype(jnp.float32)
    out = out.reshape(3, K, f_pad, n_bins)[:, :, :num_f]
    out = out.transpose(1, 2, 3, 0)
    return jnp.pad(out, ((0, 0), (0, 0), (0, 0), (0, 1)))


#: VMEM budget for the radix2 accumulator (f32/i32 [p*nhi, nch*3K*p*nlo]);
#: beyond it the dispatcher falls back to the flat kernel.  The flat
#: kernel's [3K, F*B] accumulator at the shipped K=42/255-bin config is
#: ~4 MB and already crowds double-buffering at blk=2048 (round-4 note);
#: radix2 multiplies that by its diagonal-waste factor p.
_RADIX2_ACC_BYTES = 8 << 20


def radix2_pick_p(num_f: int, K: int, n_bins: int) -> int:
    """Feature group width for the shared-radix kernel: largest p in
    (4, 2) whose accumulator fits ``_RADIX2_ACC_BYTES``; 0 = does not
    fit (caller falls back to the flat kernel)."""
    for p in (4, 2):
        f_pad = _round_up(num_f, p)
        if 3 * K * f_pad * n_bins * p * 4 <= _RADIX2_ACC_BYTES:
            return p
    return 0


@functools.partial(jax.jit,
                   static_argnames=("n_bins", "rows_per_block", "p",
                                    "compute_dtype", "interpret"))
def histogram_leaves_radix2_pallas(bins_t: jax.Array, grad: jax.Array,
                                   hess: jax.Array, leaf_of_row: jax.Array,
                                   leaves: jax.Array, *, n_bins: int,
                                   rows_per_block: int = 1024, p: int = 2,
                                   compute_dtype=jnp.bfloat16,
                                   interpret: bool = False) -> jax.Array:
    """SHARED-radix masked multi-leaf histogram: f32 [K, F, n_bins, 4].

    The flat masked kernel builds a B-wide one-hot per feature (the
    32-bit-compare floor, ~2 VPU ops per (feature, bin, row)); the joint
    radix kernel's (leaf, hi) build scales with K and loses above K=4
    (docs/PERF_NOTES.md round 3).  This kernel splits bin = 16*hi + lo
    and builds BOTH nibble one-hots ONCE per row block — nhi + nlo = 32
    compare elements per feature-row instead of 256, K-independent — then
    rides the K split-batch leaf channels on the rhs as value-masked lo
    planes:

        acc[(f, hi), (ch, f', lo)] = sum_r hi_oh[f,hi,r] * (vals[ch,r] * lo_oh[f',lo,r])

    keeping only the f == f' diagonal.  The p-fold off-diagonal waste is
    the price of full MXU tiles (same trade the single/joint radix
    kernels shipped); ``radix2_pick_p`` bounds the accumulator.  Bit
    contract identical to the flat kernel (int8 -> exact i32, float ->
    f32 accumulation over the same row axis).
    """
    num_f, n = bins_t.shape
    K = leaves.shape[0]
    nhi, nlo = n_bins // 16, 16
    M = p * nhi
    NW = 3 * K * p * nlo
    blk = min(rows_per_block, max(128, _round_up(n, 128)))
    n_pad = _round_up(max(n, 1), blk)
    with jax.named_scope("hist_compact"):
        if n_pad != n:
            bins_t = jnp.pad(bins_t, ((0, 0), (0, n_pad - n)))
            grad = jnp.pad(grad, (0, n_pad - n))
            hess = jnp.pad(hess, (0, n_pad - n))
            leaf_of_row = jnp.pad(leaf_of_row, (0, n_pad - n),
                                  constant_values=-1)
        f_pad = _round_up(num_f, p)
        if f_pad != num_f:
            bins_t = jnp.pad(bins_t, ((0, f_pad - num_f), (0, 0)))
    nch = f_pad // p
    nb = n_pad // blk
    prec = _prec(compute_dtype)

    def kernel(bins_ref, g_ref, h_ref, lor_ref, leaves_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        lor_b = lor_ref[0, :]
        sel = lor_b[None, :] == leaves_ref[0, :][:, None]   # [K, blk]
        int8_mode = _is_int8(compute_dtype)
        if int8_mode:
            seli = sel.astype(jnp.int32)
            gm = seli * g_ref[0, :][None, :].astype(jnp.int32)
            hm = seli * h_ref[0, :][None, :].astype(jnp.int32)
            vals = jnp.concatenate([gm, hm, seli], axis=0)  # [3K, blk] i32
        else:
            m = sel.astype(jnp.float32)
            gm = jnp.where(sel, g_ref[0, :][None, :], 0.0)
            hm = jnp.where(sel, h_ref[0, :][None, :], 0.0)
            vals = jnp.concatenate([gm, hm, m], axis=0) \
                .astype(compute_dtype)                      # [3K, blk]
        b_blk = bins_ref[:].astype(jnp.int32)
        iota_h = lax.iota(jnp.int32, nhi)
        iota_l = lax.iota(jnp.int32, nlo)
        for c0 in range(nch):
            chunk = b_blk[c0 * p:(c0 + 1) * p]              # [p, blk]
            hi = chunk >> 4
            lo = chunk & 15
            if int8_mode:
                # i8 elementwise multiplies don't legalize in Mosaic:
                # mask in i32, cast both dot operands to i8 pre-dot
                hi_oh = (hi[:, None, :] == iota_h[None, :, None]
                         ).astype(jnp.int8).reshape(M, blk)
                lo_ohi = (lo[:, None, :] == iota_l[None, :, None]
                          ).astype(jnp.int32).reshape(p * nlo, blk)
                vlo = (vals[:, None, :] * lo_ohi[None, :, :]
                       ).reshape(NW, blk).astype(jnp.int8)
                acc = lax.dot_general(hi_oh, vlo, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.int32)
            else:
                hi_oh = (hi[:, None, :] == iota_h[None, :, None]
                         ).astype(compute_dtype).reshape(M, blk)
                lo_oh = (lo[:, None, :] == iota_l[None, :, None]
                         ).astype(compute_dtype).reshape(p * nlo, blk)
                vlo = (vals[:, None, :] * lo_oh[None, :, :]).reshape(NW, blk)
                acc = lax.dot_general(hi_oh, vlo, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=prec)       # [M, NW]
            out_ref[:, c0 * NW:(c0 + 1) * NW] += acc

    out = pl.pallas_call(
        kernel, grid=(nb,),
        in_specs=[
            pl.BlockSpec((f_pad, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((M, nch * NW), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, nch * NW),
                                       _acc_dtype(compute_dtype)),
        interpret=interpret,
    )(bins_t, grad[None, :], hess[None, :], leaf_of_row[None, :],
      leaves[None, :])
    out = out.astype(jnp.float32)
    # rows (p_l, nhi); cols (nch, 3K-ch, p_r, nlo) — keep the f == f' diag
    out = out.reshape(p, nhi, nch, 3 * K, p, nlo)
    idx = jnp.arange(p)
    out = out[idx, :, :, :, idx]            # [p, nhi, nch, 3K, nlo]
    out = out.transpose(3, 2, 0, 1, 4)      # [3K, nch, p, nhi, nlo]
    out = out.reshape(3, K, f_pad, n_bins)[:, :, :num_f]
    out = out.transpose(1, 2, 3, 0)
    return jnp.pad(out, ((0, 0), (0, 0), (0, 0), (0, 1)))


def _radix_shapes(n_bins: int, p: int):
    """Radix split of the bin axis: bin = hi * nlo + lo with nlo = 16.

    Valid only when ``n_bins`` is a multiple of 16 (the production 256-bin
    layout); callers fall back to the flat kernels otherwise.
    """
    nlo = 16
    nhi = n_bins // nlo
    return nhi, nlo, p * nhi, 3 * p * nlo


def _radix_chunk_accum(chunk_i32, vals3, *, nhi, nlo, p, blk, compute_dtype,
                       prec):
    """One radix feature-chunk contraction: [p*nhi, 3*p*nlo] f32.

    The 256-wide one-hot of the flat kernel costs ~2 VPU ops per
    (feature, bin, row) element; splitting bin = 16*hi + lo builds two
    16-wide one-hots instead (32 elements per feature-row instead of 256)
    and recovers the joint histogram as an outer product ridden by one
    MXU contraction per chunk:

        acc[(f, hi), (c, f', lo)] = sum_r hi_oh[f,hi,r] * vals[c,r] * lo_oh[f',lo,r]

    Only the f == f' diagonal blocks are kept (callers extract them); the
    off-diagonal waste buys full 128-wide MXU tiles, which measured ~1.7x
    faster than both the flat kernel and per-feature small matmuls
    (docs/PERF_NOTES.md round-3 table).
    """
    hi = chunk_i32 >> 4                                     # [p, blk]
    lo = chunk_i32 & 15
    iota_h = lax.iota(jnp.int32, nhi)
    iota_l = lax.iota(jnp.int32, nlo)
    if _is_int8(compute_dtype):
        # i8 elementwise multiply doesn't legalize in Mosaic: build the
        # masked lo-side channels in i32 and cast both dot operands to i8
        # (values <= 127 by the quantized-levels contract)
        hi_oh = (hi[:, None, :] == iota_h[None, :, None]
                 ).astype(jnp.int8).reshape(p * nhi, blk)
        lo_ohi = (lo[:, None, :] == iota_l[None, :, None]
                  ).astype(jnp.int32).reshape(p * nlo, blk)
        vlo = jnp.concatenate([lo_ohi * vals3[0][None, :],
                               lo_ohi * vals3[1][None, :],
                               lo_ohi * vals3[2][None, :]],
                              axis=0).astype(jnp.int8)
        return lax.dot_general(hi_oh, vlo, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32
                               )                            # [p*nhi, 3*p*nlo]
    hi_oh = (hi[:, None, :] == iota_h[None, :, None]
             ).astype(compute_dtype).reshape(p * nhi, blk)
    lo_oh = (lo[:, None, :] == iota_l[None, :, None]
             ).astype(compute_dtype).reshape(p * nlo, blk)
    vlo = jnp.concatenate([lo_oh * vals3[0][None, :],
                           lo_oh * vals3[1][None, :],
                           lo_oh * vals3[2][None, :]], axis=0)
    return lax.dot_general(hi_oh, vlo, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32,
                           precision=prec)                  # [p*nhi, 3*p*nlo]


def _radix_unpack(out: jax.Array, *, n_groups, num_f, f_pad, p, nhi, nlo,
                  n_bins):
    """[G, p*nhi, nch*3*p*nlo] -> [G, F, n_bins, 4] diagonal extraction."""
    nch = f_pad // p
    out = out.reshape(n_groups, p, nhi, nch, 3, p, nlo)
    idx = jnp.arange(p)
    # diag p_lhs == p_rhs -> leading axis p (vmapped-gather semantics)
    out = out[:, idx, :, :, :, idx]          # [p, G, nhi, nch, 3, nlo]
    out = out.transpose(1, 3, 0, 2, 5, 4)    # [G, nch, p, nhi, nlo, 3]
    out = out.reshape(n_groups, f_pad, n_bins, 3)[:, :num_f]
    return jnp.pad(out, ((0, 0), (0, 0), (0, 0), (0, 1)))


@functools.partial(jax.jit,
                   static_argnames=("n_bins", "rows_per_block", "p",
                                    "compute_dtype", "interpret"))
def histogram_radix_single_pallas(bins_t: jax.Array, grad: jax.Array,
                                  hess: jax.Array, lor: jax.Array, *,
                                  n_bins: int, rows_per_block: int = 2048,
                                  p: int = 4, compute_dtype=jnp.bfloat16,
                                  interpret: bool = False) -> jax.Array:
    """Single-group full-data radix histogram: f32 [F, n_bins, 4].

    The root-pass kernel (reference cuda_histogram_constructor.cu:18 builds
    the root the same way it builds leaves; here the root gets the cheaper
    radix formulation since it has no grouping to steer).  ``lor`` < 0
    excludes a row (bagging mask); all other rows contribute.
    """
    num_f, n = bins_t.shape
    nhi, nlo, M, NW = _radix_shapes(n_bins, p)
    blk = min(rows_per_block, max(128, _round_up(n, 128)))
    n_pad = _round_up(max(n, 1), blk)
    with jax.named_scope("hist_compact"):
        if n_pad != n:
            bins_t = jnp.pad(bins_t, ((0, 0), (0, n_pad - n)))
            grad = jnp.pad(grad, (0, n_pad - n))
            hess = jnp.pad(hess, (0, n_pad - n))
            lor = jnp.pad(lor, (0, n_pad - n), constant_values=-1)
        f_pad = _round_up(num_f, p)
        if f_pad != num_f:
            bins_t = jnp.pad(bins_t, ((0, f_pad - num_f), (0, 0)))
    nch = f_pad // p
    nb = n_pad // blk
    prec = _prec(compute_dtype)

    def kernel(bins_ref, g_ref, h_ref, lor_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        valid = lor_ref[0, :] >= 0
        if _is_int8(compute_dtype):
            vi = valid.astype(jnp.int32)
            gm = vi * g_ref[0, :].astype(jnp.int32)
            hm = vi * h_ref[0, :].astype(jnp.int32)
            mm = vi
        else:
            gm = jnp.where(valid, g_ref[0, :], 0.0).astype(compute_dtype)
            hm = jnp.where(valid, h_ref[0, :], 0.0).astype(compute_dtype)
            mm = jnp.where(valid, 1.0, 0.0).astype(compute_dtype)
        b_blk = bins_ref[:].astype(jnp.int32)
        for c0 in range(nch):
            acc = _radix_chunk_accum(
                b_blk[c0 * p:(c0 + 1) * p], (gm, hm, mm), nhi=nhi, nlo=nlo,
                p=p, blk=blk, compute_dtype=compute_dtype, prec=prec)
            out_ref[:, c0 * NW:(c0 + 1) * NW] += acc

    out = pl.pallas_call(
        kernel, grid=(nb,),
        in_specs=[
            pl.BlockSpec((f_pad, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((M, nch * NW), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, nch * NW),
                                       _acc_dtype(compute_dtype)),
        interpret=interpret,
    )(bins_t, grad[None, :], hess[None, :], lor[None, :])
    out = out.astype(jnp.float32)
    return _radix_unpack(out[None], n_groups=1, num_f=num_f, f_pad=f_pad,
                         p=p, nhi=nhi, nlo=nlo, n_bins=n_bins)[0]


@functools.partial(jax.jit,
                   static_argnames=("n_bins", "rows_per_block", "p",
                                    "compute_dtype", "interpret"))
def histogram_radix_joint_pallas(bins_t: jax.Array, grad: jax.Array,
                                 hess: jax.Array, lor: jax.Array,
                                 leaves: jax.Array, *, n_bins: int,
                                 rows_per_block: int = 2048, p: int = 4,
                                 compute_dtype=jnp.bfloat16,
                                 interpret: bool = False) -> jax.Array:
    """Masked MULTI-leaf radix histogram: f32 [G, F, n_bins, 4], full-data
    pass, no compaction.

    The leaf dimension rides the matmul M side as a joint (leaf, hi)
    one-hot — lhs rows = G*p*nhi — while the rhs keeps the 3 value
    channels.  Profitable while G*p*nhi stays within a few MXU tiles
    (warmup rounds, G <= ~16); beyond that the flat masked kernel's
    K-independent cost wins.  ``leaves`` i32 [G]; duplicate slots receive
    identical histogram copies (same as the flat masked kernel).
    """
    num_f, n = bins_t.shape
    G = leaves.shape[0]
    nhi, nlo, M1, NW = _radix_shapes(n_bins, p)
    M = G * M1
    blk = min(rows_per_block, max(128, _round_up(n, 128)))
    n_pad = _round_up(max(n, 1), blk)
    with jax.named_scope("hist_compact"):
        if n_pad != n:
            bins_t = jnp.pad(bins_t, ((0, 0), (0, n_pad - n)))
            grad = jnp.pad(grad, (0, n_pad - n))
            hess = jnp.pad(hess, (0, n_pad - n))
            lor = jnp.pad(lor, (0, n_pad - n), constant_values=-1)
        f_pad = _round_up(num_f, p)
        if f_pad != num_f:
            bins_t = jnp.pad(bins_t, ((0, f_pad - num_f), (0, 0)))
    nch = f_pad // p
    nb = n_pad // blk
    prec = _prec(compute_dtype)

    def kernel(bins_ref, g_ref, h_ref, lor_ref, leaves_ref, out_ref):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        lor_b = lor_ref[0, :]
        lv = leaves_ref[0, :]
        eq = lor_b[None, :] == lv[:, None]                  # [G, blk]
        int8_mode = _is_int8(compute_dtype)
        if int8_mode:
            gohi = eq.astype(jnp.int32)                     # [G, blk]
            seli = jnp.sign(jnp.sum(gohi, axis=0))          # 0/1 [blk]
            gm = seli * g_ref[0, :].astype(jnp.int32)
            hm = seli * h_ref[0, :].astype(jnp.int32)
            mm = seli
        else:
            goh = eq.astype(compute_dtype)                  # [G, blk]
            sel = jnp.any(eq, axis=0)
            gm = jnp.where(sel, g_ref[0, :], 0.0).astype(compute_dtype)
            hm = jnp.where(sel, h_ref[0, :], 0.0).astype(compute_dtype)
            mm = jnp.where(sel, 1.0, 0.0).astype(compute_dtype)
        b_blk = bins_ref[:].astype(jnp.int32)
        iota_h = lax.iota(jnp.int32, nhi)
        iota_l = lax.iota(jnp.int32, nlo)
        for c0 in range(nch):
            chunk = b_blk[c0 * p:(c0 + 1) * p]
            if int8_mode:
                hi_ohi = ((chunk >> 4)[:, None, :] == iota_h[None, :, None]
                          ).astype(jnp.int32)               # [p, nhi, blk]
                lo_ohi = ((chunk & 15)[:, None, :] == iota_l[None, :, None]
                          ).astype(jnp.int32).reshape(p * nlo, blk)
                joint = (gohi[:, None, None, :] * hi_ohi[None, :, :, :]
                         ).reshape(M, blk).astype(jnp.int8)
                vlo = jnp.concatenate([lo_ohi * gm[None, :],
                                       lo_ohi * hm[None, :],
                                       lo_ohi * mm[None, :]],
                                      axis=0).astype(jnp.int8)
                acc = lax.dot_general(joint, vlo, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.int32)
            else:
                hi_oh = ((chunk >> 4)[:, None, :] == iota_h[None, :, None]
                         ).astype(compute_dtype)            # [p, nhi, blk]
                lo_oh = ((chunk & 15)[:, None, :] == iota_l[None, :, None]
                         ).astype(compute_dtype).reshape(p * nlo, blk)
                joint = (goh[:, None, None, :] * hi_oh[None, :, :, :]
                         ).reshape(M, blk)                  # [(G,p,hi), blk]
                vlo = jnp.concatenate([lo_oh * gm[None, :],
                                       lo_oh * hm[None, :],
                                       lo_oh * mm[None, :]], axis=0)
                acc = lax.dot_general(joint, vlo, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=prec)       # [M, NW]
            out_ref[:, c0 * NW:(c0 + 1) * NW] += acc

    out = pl.pallas_call(
        kernel, grid=(nb,),
        in_specs=[
            pl.BlockSpec((f_pad, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((1, G), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((M, nch * NW), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, nch * NW),
                                       _acc_dtype(compute_dtype)),
        interpret=interpret,
    )(bins_t, grad[None, :], hess[None, :], lor[None, :], leaves[None, :])
    out = out.astype(jnp.float32)
    # rows (G, p_l, nhi); cols (nch, 3c, p_r, nlo)
    out = out.reshape(G, M1, nch * NW)
    return _radix_unpack(out, n_groups=G, num_f=num_f, f_pad=f_pad, p=p,
                         nhi=nhi, nlo=nlo, n_bins=n_bins)
