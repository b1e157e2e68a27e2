"""Data-parallel tree learning over a device mesh.

TPU-native re-design of the reference distributed tree learner (reference:
src/treelearner/data_parallel_tree_learner.cpp — row shards per rank,
ReduceScatter of histograms :281-296, Allreduce of leaf sums :159-219 and of
the serialized best split :441).  Here the SAME ``grow_tree`` kernel runs
under ``shard_map`` with an ``axis_name``: each device histograms its row
shard, one ``psum`` makes every device hold the global histogram, after
which split finding, partitioning and tree updates are replicated —
byte-identical decisions on every device with no best-split sync step at
all.  The reference's per-tree feature->rank ownership (its ReduceScatter
layout, :124-157) is an optimization of the same dataflow; ``psum`` lets
XLA choose the reduction schedule over ICI.

Unlike the reference, this composes with the device-resident learner: the
reference's CUDA learner is single-GPU only (tree_learner.cpp:46-53) while
``device_type=cuda`` forbids distributed; here the whole point is
device-loop + collectives simultaneously (SURVEY.md §2.7 item 6).

Two entry styles:
  * ``grow_tree_sharded`` — explicit shard_map + psum (used by
    dryrun_multichip and multi-host).
  * GSPMD: pass row-sharded arrays straight into the jitted single-device
    path and let XLA insert the collectives (same math, compiler-chosen
    schedule).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..learner.grower import TreeArrays, grow_tree
from ..ops.compile_cache import get_or_build, mesh_signature, sig
from ..ops.split import SplitHyper
from .mesh import DATA_AXIS, make_mesh
from ..ops.table import take_small_table


def _cached_shard_map(entry: str, mesh: Mesh, local, in_specs, out_specs,
                      key_extra, metrics=None):
    """jit-wrapped ``shard_map`` program, reused across calls.

    Every entry here used to rebuild ``shard_map(local, ...)`` per call
    — per TREE from the booster loop — re-running Python tracing for a
    program whose compiled executable already existed (ISSUE 7).  The
    process-level compile cache (ops/compile_cache.py) keys on (entry
    name, mesh signature, argument shape signatures, statics): ``local``
    closes over statics only (hp, mode flags, scalars — all in the key),
    never over arrays, so a key hit is a program hit and no anchors are
    needed.  The ``jax.jit`` wrapper is what makes the cached object
    carry the compiled program (a bare shard_map call re-traces)."""
    key = (entry, mesh_signature(mesh), key_extra)

    def build():
        return jax.jit(shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))

    return get_or_build(key, build, metrics=metrics)


def grow_tree_sharded(mesh: Optional[Mesh], bins: jax.Array, grad: jax.Array,
                      hess: jax.Array, row_mask: Optional[jax.Array],
                      num_bins: jax.Array, nan_bin: jax.Array,
                      is_cat: jax.Array, feature_mask: Optional[jax.Array],
                      hp: SplitHyper,
                      bundle=None, parallel_mode: str = "data",
                      top_k: int = 20, monotone=None, rng_key=None,
                      interaction_sets=None, forced=None,
                      hist_scale=None,
                      metrics=None) -> Tuple[TreeArrays, jax.Array]:
    """Grow one tree with rows sharded over ``mesh``'s data axis.

    bins [n, F] uint8, grad/hess [n] — n must divide the mesh size (pad +
    mask otherwise).  ``bundle``: replicated EFB tables (DeviceBundle).
    ``parallel_mode``: "data" (full-histogram psum) or "voting" (PV-Tree
    top-k vote, voting_parallel_tree_learner.cpp — psums only the voted
    features' histogram slices).  Returns (replicated TreeArrays,
    row-sharded leaf_of_row).  ``mesh=None`` resolves to the ACTIVE
    device mesh (parallel/mesh.py) — after an elastic eviction that is
    the survivor window, so recovery needs no mesh plumbing here.
    """
    if mesh is None:
        mesh = make_mesh()

    def rep(x):
        return None if x is None else jax.tree.map(lambda _: P(), x)

    in_specs = (
        P(DATA_AXIS),                       # bins
        P(DATA_AXIS),                       # grad
        P(DATA_AXIS),                       # hess
        P(DATA_AXIS) if row_mask is not None else None,  # row_mask
        P(),                                # num_bins
        P(),                                # nan_bin
        P(),                                # is_cat
        P() if feature_mask is not None else None,
        rep(bundle),
        rep(monotone),
        rep(rng_key),
        rep(interaction_sets),
        rep(forced),
        rep(hist_scale),
    )
    out_specs = (
        jax.tree.map(lambda _: P(), TreeArrays(*[0] * len(TreeArrays._fields))),
        P(DATA_AXIS),                       # leaf_of_row
    )

    def local(b, g, h, m, nb, nanb, cat, fm, bd, mono, key, isets, fsp, hs):
        return grow_tree(b, g, h, m, nb, nanb, cat, fm, hp,
                         axis_name=DATA_AXIS, bundle=bd, monotone=mono,
                         rng_key=key, interaction_sets=isets, forced=fsp,
                         parallel_mode=parallel_mode, top_k=top_k,
                         num_shards=mesh.devices.size, hist_scale=hs)

    fn = _cached_shard_map(
        "grow_tree_sharded", mesh, local, tuple(s for s in in_specs),
        out_specs,
        (hp, parallel_mode, top_k,
         sig((bins, grad, hess, row_mask, num_bins, nan_bin, is_cat,
              feature_mask, bundle, monotone, rng_key, interaction_sets,
              forced, hist_scale))),
        metrics=metrics)
    return fn(bins, grad, hess, row_mask, num_bins, nan_bin, is_cat,
              feature_mask, bundle, monotone, rng_key, interaction_sets,
              forced, hist_scale)


def train_step_sharded(mesh: Optional[Mesh], bins: jax.Array,
                       scores: jax.Array,
                       label: jax.Array, row_mask: Optional[jax.Array],
                       num_bins: jax.Array, nan_bin: jax.Array,
                       is_cat: jax.Array, hp: SplitHyper, *,
                       learning_rate: float = 0.1,
                       objective: str = "binary",
                       metrics=None) -> Tuple[TreeArrays, jax.Array]:
    """One FULL boosting step (gradients -> tree -> score update), rows
    sharded — the unit the driver dry-runs multi-chip.  Gradient math is
    elementwise (trivially shards); the tree grower psums histograms/stats.
    ``mesh=None`` resolves to the active (possibly survivor-restricted)
    mesh.
    """
    if mesh is None:
        mesh = make_mesh()
    in_specs = (P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                P(DATA_AXIS) if row_mask is not None else None,
                P(), P(), P())
    out_specs = (
        jax.tree.map(lambda _: P(), TreeArrays(*[0] * len(TreeArrays._fields))),
        P(DATA_AXIS),
    )

    def local(b, sc, y, m, nb, nanb, cat):
        if objective == "binary":
            sign = jnp.where(y > 0, 1.0, -1.0)
            resp = -sign / (1.0 + jnp.exp(sign * sc))
            g = resp
            h = jnp.abs(resp) * (1.0 - jnp.abs(resp))
        else:  # l2
            g = sc - y
            h = jnp.ones_like(sc)
        tree, leaf_of_row = grow_tree(b, g, h, m, nb, nanb, cat, None, hp,
                                      axis_name=DATA_AXIS)
        new_scores = sc + learning_rate * take_small_table(tree.leaf_value,
                                                           leaf_of_row)
        return tree, new_scores

    fn = _cached_shard_map(
        "train_step_sharded", mesh, local, in_specs, out_specs,
        (hp, learning_rate, objective,
         sig((bins, scores, label, row_mask, num_bins, nan_bin, is_cat))),
        metrics=metrics)
    return fn(bins, scores, label, row_mask, num_bins, nan_bin, is_cat)


def train_fused_sharded(mesh: Optional[Mesh], bins: jax.Array,
                        scores: jax.Array,
                        label: jax.Array, num_bins: jax.Array,
                        nan_bin: jax.Array, is_cat: jax.Array,
                        hp: SplitHyper, *, num_rounds: int,
                        learning_rate: float = 0.1, batch: int = 8,
                        objective: str = "binary",
                        quantize: bool = False, seed: int = 0,
                        metrics=None) -> Tuple[TreeArrays, jax.Array]:
    """The flagship FUSED round scan (GBDT.train_fused's inner program:
    gradients -> batched tree -> score update, ``num_rounds`` rounds in
    one ``lax.scan``) composed with the data mesh — every round's
    histogram/leaf-stat psums ride the 'data' axis INSIDE the scan, so a
    whole multi-chip training run is one dispatch (VERDICT r4 next-round
    #4: the fused path and shard_map had never met).

    bins [n, F] u8 / scores / label row-sharded; returns (replicated
    stacked TreeArrays with leading [num_rounds] axis, sharded scores).
    ``quantize`` mirrors the production int8 path: in-jit level
    discretization with globally psum-maxed scales and DETERMINISTIC
    rounding (stochastic rounding is off here — a per-shard stochastic
    draw from the same fold would correlate noise across shards; fold
    the shard index into the key before enabling it).  ``mesh=None``
    resolves to the active (possibly survivor-restricted) mesh."""
    from jax import lax
    from ..learner.batch_grower import grow_tree_batched
    if quantize:
        from ..ops.quantize import discretize_gradients_levels
    if mesh is None:
        mesh = make_mesh()

    in_specs = (P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P(), P())
    out_specs = (
        jax.tree.map(lambda _: P(), TreeArrays(*[0] * len(TreeArrays._fields))),
        P(DATA_AXIS),
    )

    def local(b, sc, y, nb, nanb, cat):
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
                    g, h, key, n_levels=4, stochastic=False,
                    axis_name=DATA_AXIS)
                hist_scale = jnp.stack([gs, hs])
            tree, lor = grow_tree_batched(
                b, g, h, None, nb, nanb, cat, None, hp, batch=batch,
                axis_name=DATA_AXIS, hist_scale=hist_scale)
            sc = sc + learning_rate * take_small_table(tree.leaf_value, lor)
            return sc, tree
        sc, trees = jax.lax.scan(step, sc, jnp.arange(num_rounds))
        return trees, sc

    fn = _cached_shard_map(
        "train_fused_sharded", mesh, local, in_specs, out_specs,
        (hp, num_rounds, learning_rate, batch, objective, quantize, seed,
         sig((bins, scores, label, num_bins, nan_bin, is_cat))),
        metrics=metrics)
    return fn(bins, scores, label, num_bins, nan_bin, is_cat)


def grow_tree_batched_sharded(mesh: Optional[Mesh], bins: jax.Array,
                              grad: jax.Array,
                              hess: jax.Array,
                              row_mask: Optional[jax.Array],
                              num_bins: jax.Array, nan_bin: jax.Array,
                              is_cat: jax.Array,
                              feature_mask: Optional[jax.Array],
                              hp: SplitHyper, batch: int,
                              bundle=None,
                              monotone: Optional[jax.Array] = None,
                              hist_scale: Optional[jax.Array] = None,
                              interaction_sets: Optional[jax.Array] = None,
                              parallel_mode: str = "data",
                              top_k: int = 20, metrics=None
                              ) -> Tuple[TreeArrays, jax.Array]:
    """Batched-round grower (learner/batch_grower.py) under the data mesh:
    K splits per psum-ed widened histogram pass ("data"), or per LOCAL
    pass with PV-Tree voted slice reduction ("voting").  ``mesh=None``
    resolves to the active (possibly survivor-restricted) mesh."""
    from ..learner.batch_grower import grow_tree_batched
    if mesh is None:
        mesh = make_mesh()

    def rep(x):
        return None if x is None else jax.tree.map(lambda _: P(), x)

    in_specs = (
        P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
        P(DATA_AXIS) if row_mask is not None else None,
        P(), P(), P(),
        P() if feature_mask is not None else None,
        rep(bundle),
        P() if monotone is not None else None,
        P() if hist_scale is not None else None,
        P() if interaction_sets is not None else None,
    )
    out_specs = (
        jax.tree.map(lambda _: P(), TreeArrays(*[0] * len(TreeArrays._fields))),
        P(DATA_AXIS),
    )

    def local(b, g, h, m, nb, nanb, cat, fm, bd, mono, hs, isets):
        return grow_tree_batched(b, g, h, m, nb, nanb, cat, fm, hp,
                                 batch=batch, bundle=bd, monotone=mono,
                                 axis_name=DATA_AXIS, hist_scale=hs,
                                 interaction_sets=isets,
                                 parallel_mode=parallel_mode, top_k=top_k,
                                 num_shards=mesh.devices.size)

    fn = _cached_shard_map(
        "grow_tree_batched_sharded", mesh, local, in_specs, out_specs,
        (hp, batch, parallel_mode, top_k,
         sig((bins, grad, hess, row_mask, num_bins, nan_bin, is_cat,
              feature_mask, bundle, monotone, hist_scale,
              interaction_sets))),
        metrics=metrics)
    return fn(bins, grad, hess, row_mask, num_bins, nan_bin, is_cat,
              feature_mask, bundle, monotone, hist_scale, interaction_sets)
