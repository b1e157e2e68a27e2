"""Small tables by row index, without lane-dim gathers or scatters.

A pair of kernels over one [n]-sized index vector and a table bounded by
``num_leaves`` (<= 2048 entries), one the transpose of the other:

``take_small_table``: ``table[idx]``, the lookup FROM the table.  It is
the slowest primitive on TPU (~8 ms per 1M rows through XLA's gather,
docs/PERF_NOTES.md) yet the GBDT score update needs exactly that:
``scores += lr * leaf_value[leaf_of_row]`` (reference
score_updater.hpp:21 AddScore).  Reformulated as a one-hot selection in
VMEM with the rows on the lanes throughout (``_take_pallas``).  What the
v5e read, kernel alone at 13,281,250 rows (the Criteo and Allstate cells'
row count; PERF.md section 6, PR 44): the kernel before PR 44, which
turned each index block to the sublanes and worked on 16 of a register's
128 lanes, 37.6 ms at 255 entries (2.8 ms per 1M rows; 38.4 at 31, 43.0
at 2,048); this one 1.30 ms at any size up to 2,048 entries, 0.3 of it
the pad and the slice around the call; at the ranking cell's 7,325,625
rows 20.8 -> 0.76.  (Compares and selects over the whole table, no MXU,
read 0.47 ms at 31 entries, 1.37 at 255 and 9.1 at 2,048: faster under
160 entries by half a millisecond at most, and not kept as a second
form.)

``sum_small_table``: ``zeros(T).at[idx].add(values)``, the sums INTO the
table, for two value vectors in one pass over the rows (leaf renewal's
per-leaf sums of the true gradients and hessians, ops/quantize.py
``renew_leaf_values``).  XLA's scatter-add ran at 0.9 GB/s on the v5e
(116 ms per 13.3M rows and vector, PERF.md PR 28); the same one-hot,
contracted over the rows instead of over the table, is one MXU pass.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.sharding import NamedSharding, PartitionSpec as P

from .compile_cache import get_or_build, mesh_signature
from .hist_pallas import _round_up

# the lookup's radix: 8 reads 0.72 ms a call at 13.28M rows up to 1,024 entries
# and 1.29 at 2,048, 16 reads 1.00 at every size, 32 1.83 (PERF.md, PR 44)
_TAKE_LO = 16
_PARTS = 3   # an f32 is the exact sum of three bfloat16 (3 x 8 significand bits)


def _bf16_parts(v):
    """``v`` f32 as three bfloat16 whose f32 sum is ``v`` exactly (round
    to nearest leaves a residual of at most 16, then 8, significant
    bits), returned widened to f32."""
    parts = []
    for _ in range(_PARTS):
        p = v.astype(jnp.bfloat16).astype(jnp.float32)
        parts.append(p)
        v = v - p
    return parts


def _row_blocks(n: int, rows_per_block: int, rows_per_dot: int):
    """Rows a grid step and rows a chunk of it (which bounds the one-hots'
    VMEM): whole lane tiles, a block whole chunks, neither past ``n``."""
    cap = max(128, _round_up(n, 128))
    sub = min(rows_per_dot, cap)
    return _round_up(min(rows_per_block, cap), sub), sub


@functools.partial(jax.jit, static_argnames=("rows_per_block", "rows_per_dot",
                                             "interpret"))
def _take_pallas(idx: jax.Array, table: jax.Array, *,
                 rows_per_block: int = 8192, rows_per_dot: int = 1024,
                 interpret: bool = False) -> jax.Array:
    """``table[idx]`` with the ROWS ON THE LANES from load to store and the
    table along the sublanes, as ``_sum_pallas`` lays the same index
    vector: the index block ``[1, rows]`` is never turned to sublanes.

    With ``idx = nlo * hi + lo``, the ``nlo`` candidates ``table[nlo * hi
    + (0 .. nlo)]`` of every row of a chunk come from ONE MXU product of
    the table's three exact bfloat16 parts ``[3 * nlo, nhi]`` with the
    one-hot of ``hi`` ``[nhi, rows]`` (a part times 0 or 1, one term a
    sum: exact in f32; the parts add up small first, which is exact
    too).  The row's own is then picked by ``lo``: one compare and one
    select a sublane tile into ``[8, rows]`` (a row matches at most once,
    so the chain's last word is the table's, bit for bit) and one sum
    over the sublanes, seven of whose terms are 0.  The time follows
    ``nlo`` (the candidate rows popped and added), not the table's size.
    An index outside ``[0, size)`` matches nothing and reads 0.0.  (A
    non-finite entry spoils every row, as it did in the one-hot product
    before PR 44.)

    The index is padded to whole blocks and the result sliced back, in
    the shapes the kernel before PR 44 had (blocks of 8,192 rows): two
    row-sized copies, 0.3 ms a call at 13.28M rows.  A ragged last block
    needs neither (built and measured in PR 44: PERF.md section 6), but
    with them gone XLA's memory-space assignment moved other row-sized
    buffers into VMEM in the ranking cell's round program (7,325,625
    rows: a vector is 28 MiB of the 128) and the flat histogram kernel
    there, whose 27 MiB output block fits only while XLA keeps it in
    VMEM, no longer compiled.  Until those kernels state the VMEM they
    need, the program around this call stays the one every cell compiled.
    """
    n, size = idx.shape[0], table.shape[0]
    blk, sub = _row_blocks(n, rows_per_block, rows_per_dot)
    n_pad = _round_up(max(n, 1), blk)
    idx = jnp.pad(idx, (0, n_pad - n))
    nlo, shift = _TAKE_LO, _TAKE_LO.bit_length() - 1
    nhi = _round_up(pl.cdiv(max(size, 1), nlo), 16)     # a bfloat16 tile
    tab = jnp.pad(table, (0, nlo * nhi - size)).reshape(nhi, nlo).T

    def kernel(idx_ref, tab_ref, out_ref):
        parts = jnp.concatenate(_bf16_parts(tab_ref[...]), axis=0
                                ).astype(jnp.bfloat16)       # [3 * nlo, nhi]
        iota_h = lax.broadcasted_iota(jnp.int32, (nhi, sub), 0)
        iota_8 = lax.broadcasted_iota(jnp.int32, (8, sub), 0)
        for c in range(blk // sub):
            cols = slice(c * sub, (c + 1) * sub)
            ix = idx_ref[:, cols]                            # [1, sub]
            oh_hi = ((ix >> shift) == iota_h).astype(jnp.bfloat16)
            cand = lax.dot_general(
                parts, oh_hi, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [3 * nlo, sub]
            cand = (cand[2 * nlo:] + cand[nlo:2 * nlo]) + cand[:nlo]
            at = (ix & (nlo - 1)) - iota_8                   # [8, sub]
            acc = jnp.zeros((8, sub), jnp.float32)
            for r in range(0, nlo, 8):
                acc = jnp.where(at == r, cand[r:r + 8], acc)
            out_ref[:, cols] = jnp.sum(acc, axis=0, keepdims=True)

    out = pl.pallas_call(
        kernel,
        grid=(n_pad // blk,),
        in_specs=[
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((nlo, nhi), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
    )(idx[None, :], tab)
    return out[0, :n]


def _kernel_rows(idx, size: int):
    """Over which rows a kernel of this module may run, by what the code
    can observe: ``"whole"`` on a TPU for a table of ``size <= 2048``
    when ``idx`` is traced or on one device; ``(mesh, spec)`` when its
    rows are split evenly over a one-axis device mesh (the leaf map a
    shard_map grower returns), for a run per shard; ``None`` for any
    other placement and off the TPU, where the XLA form answers (it can
    be partitioned, a Mosaic kernel cannot)."""
    if jax.default_backend() != "tpu" or size > 2048 or idx.ndim != 1:
        return None
    if isinstance(idx, jax.core.Tracer) or len(idx.devices()) == 1:
        return "whole"
    sh = idx.sharding
    if (isinstance(sh, NamedSharding) and len(sh.spec) == 1
            and isinstance(sh.spec[0], str)
            and idx.shape[0] % sh.mesh.shape[sh.spec[0]] == 0):
        return sh.mesh, sh.spec
    return None


def _take_per_shard(mesh, spec):
    """``_take_pallas`` over a row-sharded index: a Mosaic kernel cannot
    be partitioned automatically, and the lookup is per row, so each
    device runs it on its own rows (table replicated)."""
    return get_or_build(
        ("take_small_table_sharded", mesh_signature(mesh), spec),
        lambda: jax.jit(shard_map(
            _take_pallas, mesh=mesh, in_specs=(spec, P()),
            out_specs=spec, check_vma=False)))


def take_small_table(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for f32 ``table`` [T<=2048] and i32 ``idx`` [n].

    Out-of-range indices (e.g. -1) return 0.0.
    """
    rows = _kernel_rows(idx, table.shape[0])
    if rows is not None:
        idx = jnp.asarray(idx, jnp.int32)
        table = jnp.asarray(table, jnp.float32)
        if rows == "whole":
            return _take_pallas(idx, table)
        return _take_per_shard(*rows)(idx, table)
    safe = jnp.clip(idx, 0, table.shape[0] - 1)
    ok = (idx >= 0) & (idx < table.shape[0])
    return jnp.where(ok, table[safe], 0.0)


# ---------------------------------------------------------------- the sums
@functools.partial(jax.jit, static_argnames=("size", "rows_per_block",
                                             "rows_per_dot", "interpret"))
def _sum_pallas(idx: jax.Array, g: jax.Array, h: jax.Array,
                mask: Optional[jax.Array], *, size: int,
                rows_per_block: int = 16384, rows_per_dot: int = 1024,
                interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The transpose of ``_take_pallas``: f32 ``[size]`` sums of ``g`` and
    of ``h`` by ``idx``, one pass over the rows.

    Per row block the values are split into exact bfloat16 parts, spread
    over the high one-hot ``[parts * nhi, rows]`` and contracted over the
    rows against the low one-hot ``[nlo, rows]``: the products are a part
    times 0 or 1, exact, and the MXU accumulates them in f32.  The output
    block stays in VMEM across the grid.  Rows past ``n`` (the last
    block's tail; no operand is padded), rows whose ``mask`` is not
    positive and indices outside ``[0, size)`` add nothing.  A non-finite
    value spoils up to ``nlo`` sums (NaN x 0) where the scatter-add
    spoils one.
    """
    n = idx.shape[0]
    # idx = nlo * hi + lo: the low one-hot fills the MXU's 128 output
    # lanes, the high one (times the value parts) its streamed rows, in
    # whole f32 sublane tiles
    nlo, shift = 128, 7
    nhi = _round_up(pl.cdiv(max(size, 1), nlo), 8)
    rows = 2 * _PARTS * nhi
    blk, sub = _row_blocks(n, rows_per_block, rows_per_dot)
    operands = [jnp.asarray(idx, jnp.int32)[None, :],
                jnp.asarray(g, jnp.float32)[None, :],
                jnp.asarray(h, jnp.float32)[None, :]]
    if mask is not None:
        operands.append((mask > 0).astype(jnp.int32)[None, :])

    def kernel(*refs):
        idx_ref, g_ref, h_ref = refs[:3]
        m_ref = refs[3] if mask is not None else None
        out_ref = refs[-1]
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        iota_h = lax.broadcasted_iota(jnp.int32, (nhi, sub), 0)
        iota_l = lax.broadcasted_iota(jnp.int32, (nlo, sub), 0)
        lane = lax.broadcasted_iota(jnp.int32, (1, sub), 1)
        acc = jnp.zeros((rows, nlo), jnp.float32)
        for c in range(blk // sub):
            cols = slice(c * sub, (c + 1) * sub)
            ok = step * blk + c * sub + lane < n
            if m_ref is not None:
                ok &= m_ref[:, cols] > 0
            ix = jnp.where(ok, idx_ref[:, cols], -1)             # [1, sub]
            oh_hi = (ix >> shift) == iota_h                      # [nhi, sub]
            oh_lo = ((ix & (nlo - 1)) == iota_l).astype(jnp.bfloat16)
            spread = jnp.concatenate(
                [jnp.where(oh_hi, p, 0.0)
                 for ref in (g_ref, h_ref)
                 for p in _bf16_parts(ref[:, cols])], axis=0)    # [rows, sub]
            acc += lax.dot_general(
                spread.astype(jnp.bfloat16), oh_lo,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)              # [rows, nlo]
        out_ref[...] += acc

    row_block = pl.BlockSpec((1, blk), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, blk),),
        in_specs=[row_block] * len(operands),
        out_specs=pl.BlockSpec((rows, nlo), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, nlo), jnp.float32),
        interpret=interpret,
    )(*operands)
    # [(value, part, hi), lo] -> [value, entry], small parts first
    parts = out.reshape(2, _PARTS, nhi * nlo)
    sums = (parts[:, 2] + parts[:, 1]) + parts[:, 0]
    return sums[0, :size], sums[1, :size]


def _sum_per_shard(mesh, spec, masked: bool, size: int):
    """``_sum_pallas`` over row-sharded operands: each device sums its
    own rows (a Mosaic kernel cannot be partitioned automatically) and a
    ``psum`` adds the ``[size]`` partial sums."""
    def local(idx, g, h, mask=None):
        sums = _sum_pallas(idx, g, h, mask, size=size)
        with jax.named_scope("stats_allreduce"):
            return lax.psum(sums, spec[0])

    return get_or_build(
        ("sum_small_table_sharded", mesh_signature(mesh), spec, masked, size),
        lambda: jax.jit(shard_map(
            local, mesh=mesh, in_specs=(spec,) * (3 + masked),
            out_specs=P(), check_vma=False)))


@functools.partial(jax.jit, static_argnames=("size",))
def _sum_scatter(idx, g, h, mask, *, size: int):
    """The reference and the fallback: XLA's scatter-add, twice.  (A
    negative index wraps round here, as jnp's indexing does; the leaf
    map has none.)"""
    m = jnp.ones_like(g) if mask is None else mask.astype(g.dtype)
    gsum = jnp.zeros((size,), g.dtype).at[idx].add(jnp.where(m > 0, g, 0.0))
    hsum = jnp.zeros((size,), h.dtype).at[idx].add(jnp.where(m > 0, h, 0.0))
    return gsum, hsum


def sum_small_table(idx: jax.Array, g: jax.Array, h: jax.Array,
                    mask: Optional[jax.Array], size: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """``zeros(size).at[idx].add(g)`` and the same of ``h``, for i32
    ``idx`` [n] and f32 ``g``, ``h`` [n]; rows whose ``mask`` is not
    positive and indices outside ``[0, size)`` add nothing.

    Routed as ``take_small_table`` is (``_kernel_rows``): the kernel,
    the kernel per shard plus a ``psum``, or XLA's scatter-add.
    """
    rows = _kernel_rows(idx, size)
    if rows is None:
        return _sum_scatter(idx, g, h, mask, size=size)
    if rows == "whole":
        return _sum_pallas(idx, g, h, mask, size=size)
    args = (idx, g, h) if mask is None else (idx, g, h, mask)
    return _sum_per_shard(*rows, mask is not None, size)(*args)
