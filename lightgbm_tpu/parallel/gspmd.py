"""GSPMD entry: row-sharded arrays into the jitted fused scan, XLA
inserts the collectives.

The second entry style the module docstring of data_parallel.py has
promised since round 1 (and SNIPPETS.md's pjit/``paranum`` excerpts
exemplify): instead of an explicit ``shard_map`` + hand-placed ``psum``,
the SERIAL fused round program — gradients -> batched tree -> score
update, ``num_rounds`` rounds in one ``lax.scan`` — is jitted with
sharding *constraints* over arrays whose ``NamedSharding`` splits rows
across the data mesh.  The GSPMD partitioner then materialises the same
ReduceScatter/AllReduce dataflow the explicit path spells out, but with
a compiler-chosen schedule (it may fuse, reorder, or overlap the
collectives — exactly the latitude ISSUE 7's overlap work grants the
explicit path by hand).

Selected via ``tree_learner=data_gspmd`` (boosting/gbdt.py): the booster
then device_puts its bins/scores row-sharded and runs the ordinary
serial code paths unchanged — no row padding needed (GSPMD tolerates
uneven shards), no per-mode grower dispatch.  This module provides the
standalone fused-scan runner (mirroring ``train_fused_sharded``'s
``local`` program) plus the placement helpers the booster uses.

Equivalence to the explicit path is exact on integer-valued fixtures:
both reduce the same per-row contributions, and with quantized
(integer-level) gradients every histogram sum is exact regardless of
reduction order (tests/test_parallel.py).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..learner.grower import TreeArrays
from ..ops.compile_cache import get_or_build, mesh_signature, sig
from ..ops.split import SplitHyper
from ..ops.table import take_small_table
from .mesh import DATA_AXIS, make_mesh


def row_sharded(mesh: Mesh, x):
    """Place ``x`` with dim-0 split over the data axis (None passes).

    jax's ``device_put`` (unlike the GSPMD partitioner itself) refuses
    uneven shards, so a dim 0 that does not divide the mesh falls back
    to REPLICATED placement: the program still runs — unpartitioned —
    and stays correct, it just forgoes the distribution win.  The
    booster warns once at setup when this happens (boosting/gbdt.py);
    the explicit shard_map modes handle uneven n by padding + row
    masks, machinery the serial-program gspmd path deliberately lacks.
    """
    if x is None:
        return None
    n_dev = int(mesh.devices.size)
    if x.ndim >= 1 and int(x.shape[0]) % n_dev == 0:
        return jax.device_put(x, NamedSharding(mesh, P(DATA_AXIS)))
    return jax.device_put(x, NamedSharding(mesh, P()))


def replicated(mesh: Mesh, x):
    """Place ``x`` replicated on every device of ``mesh``."""
    if x is None:
        return None
    return jax.device_put(x, NamedSharding(mesh, P()))


def train_fused_gspmd(mesh: Optional[Mesh], bins: jax.Array,
                      scores: jax.Array,
                      label: jax.Array, num_bins: jax.Array,
                      nan_bin: jax.Array, is_cat: jax.Array,
                      hp: SplitHyper, *, num_rounds: int,
                      learning_rate: float = 0.1, batch: int = 8,
                      objective: str = "binary",
                      quantize: bool = False, seed: int = 0,
                      metrics=None) -> Tuple[TreeArrays, jax.Array]:
    """``train_fused_sharded``'s program as a GSPMD-partitioned plain jit.

    Same operands and return contract (stacked replicated TreeArrays,
    row-sharded scores); the difference is WHO places the collectives:
    here the body calls the serial grower (``axis_name=None``) over the
    logically-global arrays, with ``with_sharding_constraint`` pinning
    the row-sharded layout, and the GSPMD partitioner inserts the
    histogram reductions.  Routed through the process compile cache
    (ops/compile_cache.py) like every other round-body entry.

    ``quantize`` is exact here too: the serial level-discretizer's
    gradient max IS the global max (it sees the whole array), matching
    the explicit path's ``pmax`` of per-shard maxes bit-for-bit.
    """
    from ..learner.batch_grower import grow_tree_batched
    if quantize:
        from ..ops.quantize import discretize_gradients_levels
    # mesh=None resolves to the active (possibly survivor-restricted)
    # mesh, matching the explicit shard_map entries' elastic contract
    if mesh is None:
        mesh = make_mesh()
    # uneven rows: skip the constraints entirely (with_sharding_constraint
    # would silently relax them to replicated anyway) — see row_sharded
    even = int(bins.shape[0]) % int(mesh.devices.size) == 0
    rs = NamedSharding(mesh, P(DATA_AXIS) if even else P())

    def build():
        def run(b, sc, y, nb, nanb, cat):
            b = jax.lax.with_sharding_constraint(b, rs)
            sc = jax.lax.with_sharding_constraint(sc, rs)
            y = jax.lax.with_sharding_constraint(y, rs)

            def step(sc, i):
                if objective == "binary":
                    sign = jnp.where(y > 0, 1.0, -1.0)
                    resp = -sign / (1.0 + jnp.exp(sign * sc))
                    g = resp
                    h = jnp.abs(resp) * (1.0 - jnp.abs(resp))
                else:  # l2
                    g = sc - y
                    h = jnp.ones_like(sc)
                hist_scale = None
                if quantize:
                    key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
                    g, h, gs, hs = discretize_gradients_levels(
                        g, h, key, n_levels=4, stochastic=False)
                    hist_scale = jnp.stack([gs, hs])
                tree, lor = grow_tree_batched(
                    b, g, h, None, nb, nanb, cat, None, hp, batch=batch,
                    hist_scale=hist_scale)
                sc2 = sc + learning_rate * take_small_table(tree.leaf_value,
                                                            lor)
                return jax.lax.with_sharding_constraint(sc2, rs), tree

            sc, trees = jax.lax.scan(step, sc, jnp.arange(num_rounds))
            return trees, sc

        return jax.jit(run)

    key = ("train_fused_gspmd", mesh_signature(mesh), hp, num_rounds,
           learning_rate, batch, objective, quantize, seed,
           sig((bins, scores, label, num_bins, nan_bin, is_cat)))
    fn = get_or_build(key, build, metrics=metrics)
    from ..utils.timer import phase
    with phase("gspmd_fused_dispatch", rounds=int(num_rounds),
               devices=int(mesh.devices.size)):
        return fn(row_sharded(mesh, bins), row_sharded(mesh, scores),
                  row_sharded(mesh, label), replicated(mesh, num_bins),
                  replicated(mesh, nan_bin), replicated(mesh, is_cat))
