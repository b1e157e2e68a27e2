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
cross-block accumulation (zeroed at a block's first row step via pl.when).

Every kernel's OUTPUT is blocked over the columns: the grid is (column
blocks, row blocks) with the row blocks innermost, the accumulator of one
column block resident while its rows stream and only that block's bins (or
word rows) read, each input byte once a pass.  ``col_blocks`` sizes the
block from the one VMEM budget (``VMEM_BUDGET_BYTES``), the number of leaf
channels, the bins and the accumulator's type, and bounds what a body
unrolls; each ``pallas_call`` tells the compiler what its blocks take
(``vmem_limit``).  Where the whole accumulator fits (every shape up to a
few hundred columns) there is one column block and the kernel is what it
was before there were blocks; at 2,000 columns every pass takes 63 blocks of
32 columns.

The contraction dtype defaults to float32 for split-decision parity with
the reference (its CUDA learner accumulates fp64 by default, config.h:1129
``gpu_use_dp``).  Set ``tpu_hist_dtype=bfloat16`` in the Config to run the
MXU contraction at ~8x rate: the one-hot stays exact and accumulation is
f32, only grad/hess suffer ~2^-9 relative input rounding — the count
channel stays exact since 1.0 is representable.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


#: The ONE VMEM budget: no Pallas kernel of the histogram or partition path
#: may hold more than this at once (72 of a v5e core's 128 MiB: XLA's
#: memory-space assignment keeps row vectors and small tables in the rest
#: around a kernel).  Every block size below follows from it and from the
#: shapes a kernel is given, and every ``pallas_call`` hands the compiler
#: what its blocks take of it (``vmem_limit``) instead of compiling under
#: Mosaic's 16 MiB default by the assignment's leave.
VMEM_BUDGET_BYTES = 72 << 20
#: the part of the budget left to a kernel's input windows (two buffers
#: each) and its body's temporaries (13.0 MiB in the K = 4 radix kernel's
#: float32 mode at 2,048 rows, the most any takes); what remains holds
#: output blocks
_VMEM_BODY_BYTES = 16 << 20
#: what Mosaic grants a kernel that states nothing
_VMEM_DEFAULT_BYTES = 16 << 20
#: a column block is whole tiles of the resident operands: 32 rows of the
#: u8 bins, 8 rows of their packed words
_COL_TILE = 32
#: chunks (contractions) a kernel body unrolls at most
_BLOCK_CHUNKS = 64


def col_blocks(num_cols: int, col_bytes: int, chunk_cols: int = 1):
    """``(cb, ncb)``: the columns of one output block and the number of
    blocks a histogram kernel's grid takes over ``num_cols`` columns whose
    accumulator is ``col_bytes`` a column, ``chunk_cols`` columns to one
    unrolled contraction.

    One block (the whole accumulator resident once, no operand blocked)
    where that fits the budget and the unroll; else blocks of ONE tile of
    columns, the last one ragged on the way in.  What a body unrolls is
    what the chip's compiler takes its time over, and more than in
    proportion (a kernel of 224 to 256 columns a block took it 14 to 29 s
    and the round program of 2,000 columns 141 s; at 32 columns 2 to 3 s
    a kernel and 56 s), while a pass gains nothing from a wider block:
    each input byte is read once whatever the width, and a grid step costs
    a third of a microsecond beside the tens its contractions take."""
    room = VMEM_BUDGET_BYTES - _VMEM_BODY_BYTES
    if num_cols * col_bytes <= room and num_cols <= _BLOCK_CHUNKS * chunk_cols:
        return num_cols, 1
    return _COL_TILE, pl.cdiv(num_cols, _COL_TILE)


def vmem_limit(block_bytes: int, blocks: int = 1) -> int:
    """What a kernel tells the compiler it holds: its output block (two
    buffers where the grid moves it) and the body's share, never under
    Mosaic's default nor over the budget."""
    held = block_bytes * (1 if blocks == 1 else 2) + _VMEM_BODY_BYTES
    return min(VMEM_BUDGET_BYTES, max(_VMEM_DEFAULT_BYTES, held))


def _leaf_col_bytes(K: int, n_bins: int, compute_dtype) -> int:
    """Accumulator bytes a column of a K-leaf pass: ``[3K, bins]``."""
    return 3 * K * n_bins * jnp.dtype(_acc_dtype(compute_dtype)).itemsize


def pass_col_blocks(num_f: int, K: int, n_bins: int, hist_dtype) -> int:
    """Column blocks of one compacted pass over ``K`` leaves (the payload
    kernel's grid; the flat kernel's differs by a tile at most): what the
    counter ``hist_col_blocks`` says of a job."""
    col_bytes = _leaf_col_bytes(K, n_bins, jnp.dtype(hist_dtype).type)
    return col_blocks(4 * pl.cdiv(num_f, 4), col_bytes, 4)[1]


def _col_grid(ncb: int, nb: int):
    """The grid over (column blocks, row blocks), rows innermost, and what
    adapts a kernel to it: ``at(fn)`` makes ``fn(j, i, *prefetch)`` an
    index map of the grid, ``axis`` is the row blocks' grid axis.  With one
    column block the grid is the row blocks alone, as before there were
    column blocks."""
    if ncb == 1:
        return (nb,), (lambda fn: lambda i, *s: fn(0, i, *s)), 0
    return (ncb, nb), (lambda fn: fn), 1


def _params(block_bytes: int, ncb: int):
    return pltpu.CompilerParams(
        vmem_limit_bytes=vmem_limit(block_bytes, ncb))


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
        acc_t = _acc_dtype(compute_dtype)
        col_bytes = _leaf_col_bytes(K, n_bins, compute_dtype)
        cb, ncb = col_blocks(_round_up(num_f, fc), col_bytes, fc)
        if ncb > 1:
            # column blocks of whole tiles, the last ragged on the way in:
            # a row of bins past F fills output columns past F alone
            fc = math.gcd(cb, feats_per_chunk or 16)
        elif cb != num_f:
            bins_t = jnp.pad(bins_t, ((0, cb - num_f), (0, 0)))
    nb = n_pad // blk
    grid, at, axis = _col_grid(ncb, nb)
    grad2 = grad[None, :]
    hess2 = hess[None, :]
    lor2 = leaf_of_row[None, :]
    leaves2 = leaves[None, :]

    def kernel(bins_ref, g_ref, h_ref, lor_ref, leaves_ref, out_ref):
        step = pl.program_id(axis)

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
        for f0 in range(0, cb, fc):     # the block's columns
            chunk = b_blk[f0:f0 + fc]                       # [fc, blk]
            oh_b = (chunk[:, None, :] == iota[None, :, None]
                    ).reshape(fc * n_bins, blk)
            acc = _oh_contract(vals, oh_b, compute_dtype)      # [3K, fc*B]
            out_ref[:, f0 * n_bins:(f0 + fc) * n_bins] += acc

    row = pl.BlockSpec((1, blk), at(lambda j, i: (0, i)))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((cb, blk), at(lambda j, i: (j, i))),
            row, row, row,
            pl.BlockSpec((1, K), at(lambda j, i: (0, 0))),
        ],
        out_specs=pl.BlockSpec((3 * K, cb * n_bins),
                               at(lambda j, i: (0, j))),
        out_shape=jax.ShapeDtypeStruct((3 * K, ncb * cb * n_bins), acc_t),
        compiler_params=_params(cb * col_bytes, ncb),
        interpret=interpret,
    )(bins_t, grad2, hess2, lor2, leaves2)
    out = out.astype(jnp.float32)
    # [3K, F*B] -> [K, F, B, 3] -> pad channel dim to 4
    out = out.reshape(3, K, ncb * cb, n_bins)[:, :, :num_f]
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
    acc_t = _acc_dtype(compute_dtype)
    col_bytes = _leaf_col_bytes(K, n_bins, compute_dtype)
    cb, ncb = col_blocks(4 * W, col_bytes, 4)
    wb = cb // 4                # the block's word rows
    grid, at, axis = _col_grid(ncb, pl.cdiv(S, blk))
    # one block takes every row of the payload, the riding words among
    # them; column blocks take their own word rows, and the three riding
    # rows come beside them as a slice of their own (12 bytes a position)
    ride = () if ncb == 1 else (lax.slice_in_dim(payload, W, W + 3),)

    def kernel(cnt_ref, payload_ref, *refs):
        *ride_ref, leaves_ref, out_ref = refs
        riding = lambda r: ride_ref[0][r] if ride_ref else payload_ref[W + r]
        step = pl.program_id(axis)

        @pl.when(step == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        @pl.when(step * blk < cnt_ref[0])
        def _():
            g = lax.bitcast_convert_type(riding(0), jnp.float32)
            h = lax.bitcast_convert_type(riding(1), jnp.float32)
            lor_b = riding(2)
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
            for j in range(wb):         # the block's words
                w = payload_ref[j]                          # [blk] i32
                chunk = jnp.stack([w & 255, (w >> 8) & 255, (w >> 16) & 255,
                                   (w >> 24) & 255])        # [4, blk]
                oh_b = (chunk[:, None, :] == iota[None, :, None]
                        ).reshape(4 * n_bins, blk)
                acc = _oh_contract(vals, oh_b, compute_dtype)  # [3K, 4B]
                out_ref[:, j * 4 * n_bins:(j + 1) * 4 * n_bins] += acc

    # a step past the count keeps the last needed block: no DMA
    last = lambda i, c: jnp.minimum(i, jnp.maximum(c[0] - 1, 0) // blk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows if ncb == 1 else wb, blk),
                         at(lambda j, i, c: (j, last(i, c)))),
            *[pl.BlockSpec((3, blk), at(lambda j, i, c: (0, last(i, c))))
              for _ in ride],
            pl.BlockSpec((1, K), at(lambda j, i, c: (0, 0))),
        ],
        out_specs=pl.BlockSpec((3 * K, cb * n_bins),
                               at(lambda j, i, c: (0, j))),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((3 * K, ncb * cb * n_bins), acc_t),
        compiler_params=_params(cb * col_bytes, ncb),
        interpret=interpret,
    )(jnp.asarray(cnt, jnp.int32).reshape(1), payload, *ride,
      leaves[None, :])
    out = out.astype(jnp.float32)
    out = out.reshape(3, K, ncb * cb, n_bins)[:, :, :num_f]
    out = out.transpose(1, 2, 3, 0)
    return jnp.pad(out, ((0, 0), (0, 0), (0, 0), (0, 1)))


_WIN = 128      # output columns one contraction fills: a lane tile


@functools.partial(jax.jit, static_argnames=("rows_per_block",
                                             "rows_per_dot"))
def compaction_ranks(key: jax.Array, *, rows_per_block: int,
                     rows_per_dot: int):
    """Where each selected row goes, for ``compact_payload_pallas``: the
    part of a compacted pass that does not need the rows' data, as a few
    XLA operations over the keys alone.

    ``key`` i32 [n] (selected when below 2^30).  Returns ``t`` i32
    [1, n_pad] with n_pad = n rounded up to ``rows_per_block``: the
    output column of each selected row (its rank among the selected, in
    row order) and -1 for the others and for the pad; and ``cum`` i32
    [n_pad / rows_per_dot + 1]: the number of selected rows before each
    run of ``rows_per_dot`` rows, the total last.

    The rank inside each run of 128 rows is one matrix product of the
    0/1 mask against a triangular ``[128, 128]`` (exact: integers up to
    128 in float32 sums); the runs' totals take a running sum 128 times
    shorter than the rows.
    """
    n = key.shape[0]
    n_pad = _round_up(max(n, 1), rows_per_block)
    sel = jnp.pad(key < (1 << 30), (0, n_pad - n)).reshape(-1, _WIN)
    i = lax.iota(jnp.int32, _WIN)
    tri = (i[:, None] <= i[None, :]).astype(jnp.bfloat16)
    rank = jnp.dot(sel.astype(jnp.bfloat16), tri,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    per_run = jnp.sum(sel, axis=1, dtype=jnp.int32)
    before = jnp.cumsum(per_run) - per_run
    t = jnp.where(sel, before[:, None] + rank - 1, -1).reshape(1, n_pad)
    cum = jnp.concatenate([before.reshape(-1, rows_per_dot // _WIN)[:, 0],
                           before[-1:] + per_run[-1:]])
    return t, cum


@functools.partial(jax.jit,
                   static_argnames=("size", "rows_per_block", "interpret"))
def compact_payload_pallas(src: jax.Array, key: jax.Array, grad: jax.Array,
                           hess: jax.Array, leaf_of_row: jax.Array, *,
                           size: int, rows_per_block: int = 4096,
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
    ``[n, W+3]`` payload; nothing is to be assumed of the columns from
    cnt on.  Where more than the output's columns are selected the
    excess is dropped.

    Everything that does not need the rows' data is done BEFORE the
    kernel by ``compaction_ranks`` (XLA, on the keys): each row's output
    column and the count of selected rows before every run of rows,
    which the kernel takes by scalar prefetch.  A grid step (eight runs,
    at most ``rows_per_block`` rows) therefore computes no rank, reduces
    nothing to a scalar and waits on no step before it.

    A run is the rows one contraction takes.  A window costs a fixed part
    and a part per row of the run, a run touches ``1 + selected rows /
    128`` windows, so the run that costs least shortens as the selected
    share grows: 256 rows where the bucket holds more than a third of the
    rows, 512 down to a sixth, 1024 below (PERF.md section 5 has the
    chip's numbers).

    Per run: its words (a u8 source's four feature rows ARE a word of
    the packed layout: a bitcast, no arithmetic) with the three riding
    words below them are split into their four BYTE planes (0..255: exact
    in bfloat16) and contracted on the MXU against ``onehot(output
    column)`` for each 128-column window the run's rows fall in, one
    non-zero term per output, then reassembled into words.  A step takes
    the number of windows that at least half of its runs need (none, one
    or two) for EVERY run in straight-line code, which the compiler
    overlaps from run to run (a window no row falls in goes to a spare
    place), and the windows beyond that in a loop per run.  The runs ADD
    into a VMEM ring of output blocks, in any order, each window zeroed
    in the step its first column falls in; each full block leaves by one
    DMA, awaited when its place in the ring is next needed.
    """
    n = key.shape[0]
    from_bytes = src.dtype == jnp.uint8
    F = src.shape[0]
    W = pl.cdiv(F, 4) if from_bytes else F
    R = _round_up(W + 3, 8)     # payload rows: whole sublane tiles
    # the scratch grows with R (1.1 MB at 24 rows and 2048 rows a step):
    # the block shrinks with it, by powers of two
    run = 256 if 3 * size > n else 512 if 6 * size > n else 1024
    blk = min(rows_per_block, 8 * run,
              max(128, 1 << (8192 // pl.cdiv(R, 32)).bit_length() - 1),
              max(128, 1 << (n - 1).bit_length()))
    sub = min(run, blk)
    fb = min(1024, blk)         # columns one DMA carries out
    assert blk % fb == 0 and fb % sub == 0 and sub % _WIN == 0, (blk, fb)
    nsub, wpb, own_max = blk // sub, fb // _WIN, blk // fb
    # output blocks the VMEM ring holds: those a step's rows can reach
    # (own_max + 1) and one whose DMA is still running
    n_ring = own_max + 2
    nb = pl.cdiv(n, blk)
    nb_out = pl.cdiv(max(size, 1), fb)
    # a u8 source comes in whole 32-row tiles, four rows to a word
    f_rows = _round_up(F, 32) if from_bytes else F
    w_rows = f_rows // 4 if from_bytes else W
    t, cum = compaction_ranks(key, rows_per_block=blk, rows_per_dot=sub)

    def byte_planes(x):
        # i32 [R, sub] -> bf16 [4R, sub]: byte plane k of the rows
        # [8a, 8a + 8) at MXU rows [8 (4a + k), 8 (4a + k) + 8)
        return jnp.concatenate(
            [((x[8 * a:8 * a + 8] >> (8 * k)) & 255).astype(jnp.float32)
             for a in range(R // 8) for k in range(4)],
            axis=0).astype(jnp.bfloat16)

    def words_of(d):
        # f32 [4R, 128] of byte values -> i32 [R, 128]: three planes add
        # up exactly below 2^24, the fourth is shifted in
        def tile(a):
            p = [d[8 * (4 * a + k):8 * (4 * a + k) + 8] for k in range(4)]
            low = (p[2] * 65536.0 + p[1] * 256.0 + p[0]).astype(jnp.int32)
            return low | (p[3].astype(jnp.int32) << 24)
        return jnp.concatenate([tile(a) for a in range(R // 8)], axis=0)

    def kernel(cum_ref, src_ref, t_ref, g_ref, h_ref, lor_ref, out_ref,
               x_ref, planes_ref, ring_ref, sem):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            x_ref[...] = jnp.zeros_like(x_ref)

        at0 = step * nsub
        los = [cum_ref[at0 + s] for s in range(nsub + 1)]
        lo_b, hi_b = los[0], los[nsub]
        # the windows each run's rows fall in: ``count`` from ``first`` on
        first = [lo // _WIN for lo in los[:-1]]
        count = [jnp.where(hi > lo, (hi + _WIN - 1) // _WIN - lo // _WIN, 0)
                 for lo, hi in zip(los[:-1], los[1:])]

        def flush(k):
            return pltpu.make_async_copy(
                ring_ref.at[k % n_ring],
                out_ref.at[:, pl.ds(pl.multiple_of(k * fb, fb), fb)],
                sem.at[k % n_ring])

        def started(k):
            return (k >= 0) & (k < nb_out)

        def columns(s):         # the run's rows; ``s`` may be traced
            if isinstance(s, int):
                return slice(s * sub, (s + 1) * sub)
            return pl.ds(pl.multiple_of(s * sub, sub), sub)

        def stack(s):
            # the run's words, the three riding words below them, and
            # their byte planes for the contractions
            cols = columns(s)
            if from_bytes:
                x_ref[0:w_rows, cols] = pltpu.bitcast(src_ref[:, cols],
                                                      jnp.int32)
                if F % 4:       # the last word's bytes past F
                    x_ref[W - 1:W, cols] = x_ref[W - 1:W, cols] & (
                        (1 << 8 * (F % 4)) - 1)
                if w_rows > W + 3:
                    x_ref[W + 3:w_rows, cols] = jnp.zeros(
                        (w_rows - W - 3, sub), jnp.int32)
            else:
                x_ref[0:W, cols] = src_ref[:, cols]
            x_ref[W:W + 1, cols] = lax.bitcast_convert_type(
                g_ref[:, cols], jnp.int32)
            x_ref[W + 1:W + 2, cols] = lax.bitcast_convert_type(
                h_ref[:, cols], jnp.int32)
            x_ref[W + 2:W + 3, cols] = lor_ref[:, cols]
            planes_ref[:, cols] = byte_planes(x_ref[:, cols])

        def window(s, w, wanted):
            # columns [128 w, 128 w + 128) of the output, from run s
            cols = columns(s)
            col = lax.broadcasted_iota(jnp.int32, (_WIN, sub), 0)
            oh = ((t_ref[:, cols] - w * _WIN) == col).astype(
                jnp.float32).astype(jnp.bfloat16)
            words = words_of(lax.dot_general(
                planes_ref[:, cols], oh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))            # [R, 128]
            # a window no row of the run falls in goes to the spare slot
            slot = jnp.where(wanted, (w // wpb) % n_ring, n_ring)
            at = pl.multiple_of(jnp.where(wanted, w % wpb, 0) * _WIN, _WIN)
            ring_ref[slot, :, pl.ds(at, _WIN)] += words

        def runs(always):
            # ``always`` windows of every run in straight-line code, then
            # run by run the windows beyond them
            if always:
                for s in range(nsub):
                    stack(s)
                    for q in range(always):
                        window(s, first[s] + q, q < count[s])

            def rest(s, carry):
                lo, hi = cum_ref[at0 + s], cum_ref[at0 + s + 1]
                begin = lo // _WIN + always
                end = (hi + _WIN - 1) // _WIN

                @pl.when((hi > lo) & (end > begin))
                def _():
                    if not always:
                        stack(s)

                    def more(w, c):
                        window(s, w, True)
                        return c

                    lax.fori_loop(begin, end, more, 0)

                return carry

            # (seldom: a run is as long as its bucket's share lets a run
            # touch one window or two)
            @pl.when(functools.reduce(jnp.maximum, count) > always)
            def _():
                lax.fori_loop(0, nsub, rest, 0)

        @pl.when(hi_b > lo_b)
        def _():
            # the output blocks that START in this step's rows take the
            # ring slots of blocks that left a step or more ago
            for j in range(own_max):
                k = (lo_b + fb - 1) // fb + j

                @pl.when((k * fb < hi_b) & started(k - n_ring))
                def _():
                    flush(k - n_ring).wait()

            # the windows that START in this step's rows: the runs add
            # into them in any order
            def clear(w, carry):
                ring_ref[(w // wpb) % n_ring, :, pl.ds(
                    pl.multiple_of(w % wpb * _WIN, _WIN), _WIN)] = jnp.zeros(
                        (R, _WIN), jnp.int32)
                return carry

            lax.fori_loop((lo_b + _WIN - 1) // _WIN,
                          (hi_b + _WIN - 1) // _WIN, clear, 0)

            need = [sum([(count[s] > q).astype(jnp.int32)
                         for s in range(nsub)]) for q in range(2)]
            pl.when(2 * need[1] >= nsub)(functools.partial(runs, 2))
            pl.when((2 * need[1] < nsub) & (2 * need[0] >= nsub))(
                functools.partial(runs, 1))
            pl.when(2 * need[0] < nsub)(functools.partial(runs, 0))

            for j in range(own_max):    # the blocks this step filled
                k = lo_b // fb + j

                @pl.when((k < hi_b // fb) & started(k))
                def _():
                    flush(k).start()

        @pl.when(step == nb - 1)
        def _():
            total = cum_ref[nb * nsub]
            full = total // fb
            for j in range(n_ring):     # the DMAs nothing has awaited
                k = full - 1 - j

                @pl.when(started(k) & ((k + n_ring) * fb >= total))
                def _():
                    flush(k).wait()

            @pl.when((total > full * fb) & started(full))
            def _():
                tail = flush(full)
                tail.start()
                tail.wait()

    def rows_block(r):
        return pl.BlockSpec((r, blk), lambda i, c: (0, i))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb,),
            in_specs=[rows_block(f_rows)] + [rows_block(1)] * 4,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((R, blk), jnp.int32),
                            pltpu.VMEM((4 * R, blk), jnp.bfloat16),
                            pltpu.VMEM((n_ring + 1, R, fb), jnp.int32),
                            pltpu.SemaphoreType.DMA((n_ring,))]),
        out_shape=jax.ShapeDtypeStruct((R, nb_out * fb), jnp.int32),
        # the scratch and the source's two windows; the rows move whole,
        # so it is the row block that shrank with the width, above
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit(
            4 * R * blk + 8 * R * blk + 4 * (n_ring + 1) * R * fb
            + 2 * src.dtype.itemsize * f_rows * blk)),
        interpret=interpret,
    )(cum, src, t, grad[None, :], hess[None, :],
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
    acc_t = _acc_dtype(compute_dtype)
    col_bytes = _leaf_col_bytes(K, n_bins, compute_dtype)
    cb, ncb = col_blocks(4 * W, col_bytes, 4)
    wb = cb // 4                # the block's word rows
    grid, at, axis = _col_grid(ncb, nb)

    def kernel(words_ref, g_ref, h_ref, lor_ref, leaves_ref, out_ref):
        step = pl.program_id(axis)

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
        for j in range(wb):             # the block's words
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

    row = pl.BlockSpec((1, blk), at(lambda j, i: (0, i)))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((wb, blk), at(lambda j, i: (j, i))),
            row, row, row,
            pl.BlockSpec((1, K), at(lambda j, i: (0, 0))),
        ],
        out_specs=pl.BlockSpec((3 * K, cb * n_bins),
                               at(lambda j, i: (0, j))),
        out_shape=jax.ShapeDtypeStruct((3 * K, ncb * cb * n_bins), acc_t),
        compiler_params=_params(cb * col_bytes, ncb),
        interpret=interpret,
    )(words_t, grad[None, :], hess[None, :], leaf_of_row[None, :],
      leaves[None, :])
    out = out.astype(jnp.float32)
    out = out.reshape(3, K, ncb * cb, n_bins)[:, :, :num_f]
    out = out.transpose(1, 2, 3, 0)
    return jnp.pad(out, ((0, 0), (0, 0), (0, 0), (0, 1)))


#: Largest radix2 accumulator (f32/i32 [p*nhi, nch*3K*p*nlo]) for which
#: the dispatcher picks the kernel; beyond it the flat kernel takes the
#: pass.  The flat kernel's [3K, F*B] accumulator at K=42/255 bins is
#: ~4 MB and already crowds double-buffering at blk=2048 (round-4 note);
#: radix2 multiplies that by its diagonal-waste factor p.
_RADIX2_ACC_BYTES = 8 << 20


def radix2_pick_p(num_f: int, K: int, n_bins: int) -> int:
    """Feature group width for the shared-radix kernel: largest p in
    (4, 2) whose accumulator over ``num_f`` columns is ONE column block of
    at most ``_RADIX2_ACC_BYTES``; 0 = it is not (caller falls back to the
    flat kernel, whose accumulator is p times smaller a column: at 2,000
    columns every K > 4 pass).  A dispatch rule, not a VMEM limit: the
    kernel's blocks follow from ``col_blocks`` like every other's."""
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
        acc_t = _acc_dtype(compute_dtype)
        cb, ncb = col_blocks(_round_up(num_f, p),
                             M * NW // p * jnp.dtype(acc_t).itemsize, p)
        if ncb == 1 and cb != num_f:
            bins_t = jnp.pad(bins_t, ((0, cb - num_f), (0, 0)))
    nch = cb // p               # the block's chunks
    nb = n_pad // blk
    grid, at, axis = _col_grid(ncb, nb)
    prec = _prec(compute_dtype)

    def kernel(bins_ref, g_ref, h_ref, lor_ref, leaves_ref, out_ref):
        step = pl.program_id(axis)

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

    out = _radix_call(kernel, grid, at, cb, ncb, blk, M, nch * NW, acc_t,
                      interpret, bins_t, grad, hess, leaf_of_row, leaves)
    # rows (p_l, nhi); cols (nch, 3K-ch, p_r, nlo) — keep the f == f' diag
    out = out.reshape(p, nhi, ncb * nch, 3 * K, p, nlo)
    idx = jnp.arange(p)
    out = out[idx, :, :, :, idx]            # [p, nhi, nch, 3K, nlo]
    out = out.transpose(3, 2, 0, 1, 4)      # [3K, nch, p, nhi, nlo]
    out = out.reshape(3, K, ncb * cb, n_bins)[:, :, :num_f]
    out = out.transpose(1, 2, 3, 0)
    return jnp.pad(out, ((0, 0), (0, 0), (0, 0), (0, 1)))


def _radix_call(kernel, grid, at, cb, ncb, blk, m, block_cols, acc_t,
                interpret, bins_t, grad, hess, lor, leaves=None):
    """The ``pallas_call`` the three radix kernels share: the u8 bins in
    column blocks ``[cb, blk]``, the three row vectors, the leaves where
    the kernel takes them; an accumulator block ``[m, block_cols]`` a
    column block.  Returns f32 ``[m, ncb * block_cols]``."""
    row = pl.BlockSpec((1, blk), at(lambda j, i: (0, i)))
    operands = [bins_t, grad[None, :], hess[None, :], lor[None, :]]
    specs = [pl.BlockSpec((cb, blk), at(lambda j, i: (j, i))), row, row, row]
    if leaves is not None:
        operands.append(leaves[None, :])
        specs.append(pl.BlockSpec((1, leaves.shape[0]),
                                  at(lambda j, i: (0, 0))))
    out = pl.pallas_call(
        kernel, grid=grid, in_specs=specs,
        out_specs=pl.BlockSpec((m, block_cols), at(lambda j, i: (0, j))),
        out_shape=jax.ShapeDtypeStruct((m, ncb * block_cols), acc_t),
        compiler_params=_params(
            m * block_cols * jnp.dtype(acc_t).itemsize, ncb),
        interpret=interpret,
    )(*operands)
    return out.astype(jnp.float32)


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
        acc_t = _acc_dtype(compute_dtype)
        cb, ncb = col_blocks(_round_up(num_f, p),
                             M * NW // p * jnp.dtype(acc_t).itemsize, p)
        if ncb == 1 and cb != num_f:
            bins_t = jnp.pad(bins_t, ((0, cb - num_f), (0, 0)))
    nch = cb // p               # the block's chunks
    nb = n_pad // blk
    grid, at, axis = _col_grid(ncb, nb)
    prec = _prec(compute_dtype)

    def kernel(bins_ref, g_ref, h_ref, lor_ref, out_ref):
        step = pl.program_id(axis)

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

    out = _radix_call(kernel, grid, at, cb, ncb, blk, M, nch * NW, acc_t,
                      interpret, bins_t, grad, hess, lor)
    return _radix_unpack(out[None], n_groups=1, num_f=num_f, f_pad=ncb * cb,
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
        acc_t = _acc_dtype(compute_dtype)
        cb, ncb = col_blocks(_round_up(num_f, p),
                             M * NW // p * jnp.dtype(acc_t).itemsize, p)
        if ncb == 1 and cb != num_f:
            bins_t = jnp.pad(bins_t, ((0, cb - num_f), (0, 0)))
    nch = cb // p               # the block's chunks
    nb = n_pad // blk
    grid, at, axis = _col_grid(ncb, nb)
    prec = _prec(compute_dtype)

    def kernel(bins_ref, g_ref, h_ref, lor_ref, leaves_ref, out_ref):
        step = pl.program_id(axis)

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

    out = _radix_call(kernel, grid, at, cb, ncb, blk, M, nch * NW, acc_t,
                      interpret, bins_t, grad, hess, lor, leaves)
    # rows (G, p_l, nhi); cols (nch, 3c, p_r, nlo)
    out = out.reshape(G, M1, ncb * nch * NW)
    return _radix_unpack(out, n_groups=G, num_f=num_f, f_pad=ncb * cb, p=p,
                         nhi=nhi, nlo=nlo, n_bins=n_bins)
