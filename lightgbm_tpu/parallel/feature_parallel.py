"""Feature-parallel tree learning over a device mesh.

TPU-native re-design of the reference feature-parallel learner (reference:
src/treelearner/feature_parallel_tree_learner.cpp — every rank holds all
rows, the FEATURE set is divided; each rank finds its local best split and
``SyncUpGlobalBestSplit`` (an Allreduce over serialized SplitInfo, :62-79)
picks the winner; no training data moves).

Here the bin matrix is column-sharded over the mesh's feature axis under
``shard_map``: each device histograms only its feature block, local best
splits are arg-maxed with one ``all_gather`` of a packed 12-float SplitInfo
(the reference's serialized sync), and the winning shard broadcasts its
go-left partition vector with one [n] psum.  Communication per split is
O(devices·12 + n) — independent of feature count, matching the regime the
reference targets (many features, moderate rows).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..learner.grower import TreeArrays, grow_tree
from ..ops.split import SplitHyper

FEATURE_AXIS = "feature"


def grow_tree_feature_parallel(mesh: Mesh, bins: jax.Array, grad: jax.Array,
                               hess: jax.Array,
                               row_mask: Optional[jax.Array],
                               num_bins: jax.Array, nan_bin: jax.Array,
                               is_cat: jax.Array,
                               feature_mask: Optional[jax.Array],
                               hp: SplitHyper
                               ) -> Tuple[TreeArrays, jax.Array]:
    """Grow one tree with FEATURES sharded over ``mesh`` (axis "feature").

    bins [n, F] uint8 (F must divide the mesh size; pad with trivial
    columns otherwise); grad/hess [n] replicated.  The returned tree's
    ``split_feature`` uses GLOBAL feature indices; ``leaf_of_row`` is
    replicated (every shard partitions identically).
    """
    n_dev = mesh.devices.size

    in_specs = (
        P(None, FEATURE_AXIS),              # bins: column shard
        P(),                                # grad (all rows everywhere)
        P(),                                # hess
        P() if row_mask is not None else None,
        P(FEATURE_AXIS),                    # num_bins
        P(FEATURE_AXIS),                    # nan_bin
        P(FEATURE_AXIS),                    # is_cat
        P(FEATURE_AXIS) if feature_mask is not None else None,
    )
    out_specs = (
        jax.tree.map(lambda _: P(), TreeArrays(*[0] * len(TreeArrays._fields))),
        P(),                                # leaf_of_row (replicated)
    )

    def local(b, g, h, m, nb, nanb, cat, fm):
        return grow_tree(b, g, h, m, nb, nanb, cat, fm, hp,
                         axis_name=FEATURE_AXIS, parallel_mode="feature",
                         num_shards=n_dev)

    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return fn(bins, grad, hess, row_mask, num_bins, nan_bin, is_cat,
              feature_mask)
