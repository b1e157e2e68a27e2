"""Small-table row lookups without lane-dim gathers.

``table[idx]`` for a [n]-sized index vector is the slowest primitive on TPU
(~8 ms per 1M rows through XLA's gather, docs/PERF_NOTES.md) yet the GBDT
score update needs exactly that: ``scores += lr * leaf_value[leaf_of_row]``
(reference score_updater.hpp:21 AddScore).  For tables bounded by num_leaves
(<= a few hundred) the lookup is reformulated as a VMEM one-hot contraction:
per row block, onehot(idx) @ table rides the MXU and costs ~0.3 ms/1M —
~25x faster than the gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.sharding import NamedSharding, PartitionSpec as P

from .compile_cache import get_or_build, mesh_signature
from .hist_pallas import _round_up

# keep the in-kernel one-hot under ~4 MB so the scoped-VMEM budget holds at
# any admitted table size
_ONEHOT_BUDGET = 1 << 20  # f32 elements


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def _take_pallas(idx: jax.Array, table: jax.Array, *,
                 rows_per_block: int = 8192,
                 interpret: bool = False) -> jax.Array:
    n = idx.shape[0]
    t = table.shape[0]
    t_pad = _round_up(max(t, 1), 128)
    if t_pad != t:
        table = jnp.pad(table, (0, t_pad - t))
    blk = min(rows_per_block, max(128, _ONEHOT_BUDGET // t_pad // 128 * 128))
    blk = min(blk, max(128, _round_up(n, 128)))
    n_pad = _round_up(max(n, 1), blk)
    if n_pad != n:
        idx = jnp.pad(idx, (0, n_pad - n))
    nb = n_pad // blk
    idx2 = idx[None, :]
    table2 = table.reshape(t_pad // 16, 16)   # radix rows (hi, lo)

    nhi = t_pad // 16

    def kernel(idx_ref, tab_ref, out_ref):
        ix = idx_ref[0, :]                                   # [blk] i32
        # radix-split lookup: idx = 16*hi + lo.  tmp = oh_hi @ TAB[nhi, 16]
        # then a 16-wide elementwise select on lo — 2*(nhi+16) one-hot
        # elements per row instead of t_pad (same trick as the histogram
        # radix kernels; measured ~5x on the 1M-row score update).
        # HIGHEST precision: the one-hot payload must come through exact.
        hi = ix >> 4
        lo = ix & 15
        iota_h = lax.iota(jnp.int32, nhi)
        oh_hi = (hi[:, None] == iota_h[None, :]).astype(jnp.float32)
        tmp = lax.dot_general(
            oh_hi, tab_ref[:, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST)                 # [blk, 16]
        iota_l = lax.iota(jnp.int32, 16)
        sel = (lo[:, None] == iota_l[None, :]).astype(jnp.float32)
        out_ref[0, :] = jnp.sum(tmp * sel, axis=1)

    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, blk), lambda i: (0, i)),
            pl.BlockSpec((t_pad // 16, 16), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
    )(idx2, table2)
    return out[0, :n]


def _even_row_sharding(idx):
    """``(mesh, spec)`` when ``idx``'s rows are split evenly over a
    one-axis device mesh (the leaf map a shard_map grower returns),
    else ``None``."""
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
    if (jax.default_backend() == "tpu"
            and table.shape[0] <= 2048 and idx.ndim == 1):
        idx = jnp.asarray(idx, jnp.int32)
        table = jnp.asarray(table, jnp.float32)
        if isinstance(idx, jax.core.Tracer) or len(idx.devices()) == 1:
            return _take_pallas(idx, table)
        rows = _even_row_sharding(idx)
        if rows is not None:
            return _take_per_shard(*rows)(idx, table)
        # any other multi-device placement: the XLA lookup below can be
        # partitioned, the kernel cannot
    safe = jnp.clip(idx, 0, table.shape[0] - 1)
    ok = (idx >= 0) & (idx < table.shape[0])
    return jnp.where(ok, table[safe], 0.0)
