"""GBDT boosting driver.

TPU-native re-design of the reference boosting layer (reference:
src/boosting/gbdt.cpp — ``Init`` :53, ``TrainOneIter`` :344-452,
``Boosting()`` gradient step :220, score updating, boost-from-average
:308-342, train continuation).  One iteration = gradients (jitted XLA on
device, the CUDA-objective "boosting_on_gpu" path gbdt.cpp:104) → sampling
mask → one ``grow_tree`` per class (whole tree inside one jit) → shrinkage →
score update.  The train-score update is a pure gather through the returned
``leaf_of_row`` (the reference's DataPartition shortcut,
score_updater.hpp:21); valid scores update via the frontier traversal in
models/predict.py.

Boost-from-average folds the initial score into the first iteration's trees
via ``AddBias`` exactly like gbdt.cpp:404-420 (shrinkage first, bias after),
so saved models are self-contained.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from ..callback import EarlyStopException
from ..config import Config
from ..io.dataset import Dataset
from ..learner.grower import (BundleSearch, CegbInput, DeviceBundle,
                              TreeArrays, grow_tree,
                              searches_in_bundle_space)
from ..learner.linear import fit_linear_leaves, linear_leaf_scores
from ..metrics import Metric, create_metrics
from ..models.predict import predict_bins_leaf, predict_bins_tree
from ..models.tree import Tree
from ..objectives import ObjectiveFunction, create_objective
from ..ops.compile_cache import get_or_build as cc_get_or_build, sig as cc_sig
from ..ops.quantize import (discretize_gradients_levels,
                            renew_leaf_values)
from ..ops.split import SplitHyper
from ..obs import count_event
from ..obs.metrics import MetricsRegistry
from ..utils import log
from ..utils.timer import PhaseTimer, global_timer, phase
from .sample_strategy import create_sample_strategy
from ..ops.table import take_small_table

GradFn = Callable[[np.ndarray, Any], Tuple[np.ndarray, np.ndarray]]

#: a valid set's transposed bins larger than this ride the fused round
#: program as an argument; smaller ones stay literals of its HLO, which
#: is how every job before the ranking cell's 688 MB compiled them.  A
#: literal is stored with the executable about three times over: the
#: Epsilon job's 200 MB made a 647 MB entry, which the persistent compile
#: cache refused (192 MiB an entry on the chip tool's machines), so every
#: process compiled the round program again (PERF.md section 6, PR 45)
_LITERAL_MAX_BYTES = 128 << 20


@functools.lru_cache(maxsize=None)
def _valid_mirror_program(sharding):
    """The program that turns a valid set's placed ``[n, F]`` bins into
    the ``[F, n]`` mirror, one a placement: ``sharding`` is the mesh's
    replicated ``NamedSharding`` that ``_place_whole`` committed the bins
    to, stated for the result too, or None for the uncommitted array of
    the other modes, whose result stays uncommitted.  Kept by the
    process, so that a second booster compiles nothing."""
    def valid_mirror(bins):
        return bins.T
    return jax.jit(valid_mirror, out_shardings=sharding)


def _resolve_hist_dtype(cfg: Config) -> str:
    """Histogram contraction dtype with validity gating.

    ``deterministic=true`` pins exact float32.  ``int8`` (the v5e int8
    MXU path, ~1.6x the bf16 rate) is only meaningful when grad/hess
    carry small-integer quantized levels — real-valued gradients would be
    truncated — so without ``use_quantized_grad`` (or with a level count
    that cannot fit int8) it degrades to bfloat16 with a warning."""
    if cfg.deterministic:
        return "float32"
    dt = str(cfg.tpu_hist_dtype)
    if dt == "int8":
        if not bool(cfg.use_quantized_grad):
            log.warning("tpu_hist_dtype=int8 requires use_quantized_grad="
                        "true (integer gradient levels); using bfloat16")
            return "bfloat16"
        if int(cfg.num_grad_quant_bins) > 127:
            log.warning("tpu_hist_dtype=int8 needs num_grad_quant_bins "
                        "<= 127; using bfloat16")
            return "bfloat16"
    return dt


def _resolve_hist_kernel_cfg(cfg: Config) -> str:
    """Histogram-build formulation (ops/histogram.py HIST_KERNELS).  All
    modes are bit-identical, so no validity gating beyond the name check
    — the dispatcher itself falls back to the flat kernel where a
    forced mode's shape constraints don't hold."""
    from ..ops.histogram import resolve_hist_kernel
    return resolve_hist_kernel(cfg.hist_kernel)


def _bundle_search(train_set: Dataset) -> Optional[BundleSearch]:
    """The bundle plan's ranges on device, with each position's member's
    ``skip`` and last threshold looked up on the host, once."""
    r = train_set.device_bundle_ranges()
    if r is None:
        return None
    member = np.maximum(r.feat_of, 0)
    return BundleSearch(*(jnp.asarray(a) for a in (
        r.lo, r.hi, r.skip, r.feat_of, r.vbin_of, r.skip[member],
        train_set.num_bins_array()[member] - 1, r.off, r.roff)))


def _hp_from_config(cfg: Config, n_bins: int) -> SplitHyper:
    return SplitHyper(
        num_leaves=max(2, int(cfg.num_leaves)),
        max_depth=int(cfg.max_depth),
        lambda_l1=float(cfg.lambda_l1),
        lambda_l2=float(cfg.lambda_l2),
        min_data_in_leaf=int(cfg.min_data_in_leaf),
        min_sum_hessian_in_leaf=float(cfg.min_sum_hessian_in_leaf),
        min_gain_to_split=float(cfg.min_gain_to_split),
        max_delta_step=float(cfg.max_delta_step),
        cat_l2=float(cfg.cat_l2),
        cat_smooth=float(cfg.cat_smooth),
        max_cat_threshold=int(cfg.max_cat_threshold),
        max_cat_to_onehot=int(cfg.max_cat_to_onehot),
        min_data_per_group=int(cfg.min_data_per_group),
        n_bins=n_bins,
        rows_per_block=int(cfg.tpu_rows_per_block),
        path_smooth=float(cfg.path_smooth),
        # deterministic=true pins the exact-parity contraction regardless of
        # the user's tpu_hist_dtype (ADVICE r1: bfloat16 silently broke the
        # deterministic contract)
        hist_dtype=_resolve_hist_dtype(cfg),
        hist_kernel=_resolve_hist_kernel_cfg(cfg),
        extra_trees=bool(cfg.extra_trees),
        feature_fraction_bynode=float(cfg.feature_fraction_bynode),
    )


def _parse_forced_splits(filename: str, dataset: Dataset, num_leaves: int):
    """forcedsplits_filename JSON -> (leaf, feature, bin_thr) i32 arrays in
    BFS order (reference serial_tree_learner.cpp:620 ForceSplits BFS; node
    format {"feature": orig_idx, "threshold": value, "left": ..., "right":
    ...}).  Leaf numbering matches the grower: at BFS step i the right child
    becomes leaf i+1."""
    import json
    with open(filename) as fh:
        root = json.load(fh)
    if not root:
        return None
    orig_to_packed = {int(o): p for p, o in enumerate(dataset.used_feature_idx)}
    K = num_leaves - 1
    f_leaf = np.full(K, -1, np.int32)
    f_feat = np.zeros(K, np.int32)
    f_thr = np.zeros(K, np.int32)
    queue = [(root, 0)]
    i = 0
    while queue and i < K:
        node, leaf = queue.pop(0)
        p = orig_to_packed.get(int(node["feature"]))
        if p is None:
            log.warning("forced split on unused feature %s ignored; "
                        "aborting remaining forced splits" % node["feature"])
            break
        mapper = dataset.mappers[int(node["feature"])]
        thr_bin = int(mapper.values_to_bins(
            np.array([float(node["threshold"])], np.float64))[0])
        f_leaf[i], f_feat[i], f_thr[i] = leaf, p, thr_bin
        if node.get("left"):
            queue.append((node["left"], leaf))
        if node.get("right"):
            queue.append((node["right"], i + 1))
        i += 1
    if i == 0:
        return None
    return (jnp.asarray(f_leaf), jnp.asarray(f_feat), jnp.asarray(f_thr))


def _parse_interaction_sets(spec, used_feature_idx) -> Optional[np.ndarray]:
    """interaction_constraints "[0,1,2],[2,3]" -> bool [S, F_packed]
    (reference config interaction_constraints_vector; col_sampler.hpp)."""
    if not spec:
        return None
    if isinstance(spec, str):
        import json
        sets = json.loads("[" + spec + "]")
    else:
        sets = [list(s) for s in spec]
    if not sets:
        return None
    orig_to_packed = {int(o): p for p, o in enumerate(used_feature_idx)}
    out = np.zeros((len(sets), len(used_feature_idx)), bool)
    for si, s in enumerate(sets):
        for f in s:
            p = orig_to_packed.get(int(f))
            if p is not None:
                out[si, p] = True
    return out


def _hist_rows_selected(arrays, n_rows: int) -> int:
    """Rows one tree's histogram passes had to read: ``n_rows`` for the
    root pass plus, per split, the smaller child's row count, from the
    counts the tree arrays bring to the host (``internal_count``,
    ``leaf_count``).  Those are float32 sums of ones, exact below 2**24
    rows a shard."""
    splits = int(arrays.num_leaves) - 1
    if splits <= 0:
        return n_rows
    parent = np.asarray(arrays.internal_count[:splits], np.float64)
    lc = np.asarray(arrays.left_child[:splits])
    left = np.where(lc < 0,
                    np.asarray(arrays.leaf_count, np.float64)[-lc - 1],
                    parent[np.maximum(lc, 0)])
    return n_rows + int(np.minimum(left, parent - left).sum())


class GBDT:
    """Training driver (reference gbdt.h/gbdt.cpp ``GBDT``)."""

    def __init__(self, config: Config, train_set: Dataset,
                 objective: Optional[ObjectiveFunction] = None,
                 metrics: Optional[List[Metric]] = None):
        self.config = config
        self.train_set = train_set
        self.objective = objective if objective is not None else \
            create_objective(config)
        # the labels' statistics and the metrics' (0.34-0.43 s of a
        # 13.28M-row binary job's ``booster_init``: PERF.md section 5), a
        # ranking job's bucket plan: before this booster's timer exists,
        # so in no table
        with phase("objective_init"):
            if self.objective is not None:
                self.objective.init(train_set.metadata, train_set.num_data)
            self.train_metrics = metrics if metrics is not None else \
                create_metrics(config)
            for m in self.train_metrics:
                m.init(train_set.metadata, train_set.num_data)

        # reference USE_TIMETAG phase table (utils/common.h Timer).  Each
        # booster owns its OWN accumulator so concurrently alive boosters
        # never clobber each other's tables; the process-global timer
        # remains the CLI default and is managed through the enable/
        # disable API (set unconditionally so a later non-verbose run
        # disables it again, and reset so its table covers only the most
        # recent training run).
        self.timer = PhaseTimer()
        self.metrics = MetricsRegistry()
        if self.objective is not None:
            # objective.init ran before this registry existed; attach it
            # now so rank compile-cache bumps land dual-scope and the
            # bucket-plan gauges mirror into Booster.telemetry()
            self.objective.attach_booster_metrics(self.metrics)
        #: the training-side watchtower (rollups + SLOs + anomaly
        #: detection) — attached by engine.train() only when slo_config/
        #: anomaly_detection is configured; None is the all-off default
        self.watchtower = None
        want_timing = (int(config.verbosity) >= 2
                       or bool(str(config.trace_output or ""))
                       or bool(str(config.telemetry_output or "")))
        if want_timing:
            self.timer.enable()
        if int(config.verbosity) >= 2:
            global_timer.enable()
        else:
            global_timer.disable()
        global_timer.reset()
        self.num_class = max(1, int(config.num_class))
        self.num_tree_per_iteration = (
            self.objective.num_model_per_iteration
            if self.objective is not None else self.num_class)
        self.shrinkage_rate = float(config.learning_rate)
        self.models: List[Tree] = []          # iter-major, one per class
        self.iter_ = 0
        self.num_init_iteration = 0
        self.best_iteration = -1

        # device operands; the bins go up below, once the tree learner
        # says where (a row-sharded learner takes each shard from the host)
        self.bins = None
        self.num_bins_arr = jnp.asarray(train_set.num_bins_array())
        self.nan_bin_arr = jnp.asarray(train_set.nan_bin_array())
        self.is_cat_arr = jnp.asarray(train_set.categorical_array())
        self.num_features = train_set.num_features
        ba = train_set.device_bundle_arrays()
        self.bundle = None if ba is None else \
            DeviceBundle(*(jnp.asarray(a) for a in ba),
                         search=_bundle_search(train_set))
        if self.bundle is not None:
            # this booster's own registry; the process-wide one counted
            # them when the Dataset was constructed (io/dataset.py)
            self.metrics.inc("efb_bundles", train_set.bundle_plan.num_bundles)
            self.metrics.inc("efb_features", self.num_features)
        if bool(train_set.categorical_array().any()):
            # as above: the process-wide registry counted them when the
            # Dataset was constructed
            counts = train_set.categorical_counts()
            self.metrics.inc("cat_features", counts["cat_features"])
            self.metrics.inc("cat_subset_features",
                             counts["cat_subset_features"])
            self.metrics.inc("cat_levels_kept", counts["cat_levels_kept"])
            self.metrics.inc("cat_other_rows", counts["cat_other_rows"])

        # distributed tree learner over all visible devices
        # (reference tree_learner=serial/data/feature/voting,
        # tree_learner.cpp:15-57; here = shard_map over a device mesh)
        self.parallel_mode: Optional[str] = None
        self.mesh = None
        self._pad_rows = 0
        self._pad_cols = 0
        tl = {"data_parallel": "data", "voting_parallel": "voting",
              "feature_parallel": "feature",
              "gspmd": "data_gspmd"}.get(str(config.tree_learner),
                                         str(config.tree_learner))
        # active_devices(), not jax.devices(): after an elastic eviction
        # (robustness/elastic.py) the survivor window restricts every
        # fresh mesh — a resumed booster re-pads and re-shards its rows
        # over the reduced set through this one site
        from ..parallel.mesh import active_devices
        n_dev = len(active_devices())
        if tl in ("data", "voting", "feature", "data_gspmd") and n_dev > 1:
            from jax.sharding import Mesh
            from ..parallel.feature_parallel import FEATURE_AXIS
            from ..parallel.mesh import DATA_AXIS
            axis = FEATURE_AXIS if tl == "feature" else DATA_AXIS
            self.mesh = Mesh(np.array(active_devices()), (axis,))
            self.parallel_mode = tl
            if tl == "feature":
                if self.bundle is not None:
                    log.fatal("tree_learner=feature is incompatible with "
                              "enable_bundle=true (set enable_bundle=false)")
                if bool(config.use_quantized_grad):
                    log.fatal("use_quantized_grad does not compose with "
                              "tree_learner=feature (no level-scale "
                              "plumbing in that mode)")
                # unsupported-feature conflicts fail loudly (reference
                # CheckParamConflict style) instead of silently dropping
                if any(int(m) != 0 for m in (config.monotone_constraints
                                             or [])):
                    log.fatal("tree_learner=feature does not support "
                              "monotone_constraints")
                if config.forcedsplits_filename:
                    log.fatal("tree_learner=feature does not support "
                              "forcedsplits_filename")
                if config.interaction_constraints:
                    log.fatal("tree_learner=feature does not support "
                              "interaction_constraints")
                if bool(config.extra_trees) or \
                        float(config.feature_fraction_bynode) < 1.0:
                    log.warning("extra_trees/feature_fraction_bynode under "
                                "tree_learner=feature sample per feature "
                                "shard, not globally")
                # pad feature columns so F divides the mesh (trivial
                # single-bin columns can never be chosen for a split)
                self.bins = jnp.asarray(train_set.bins)
                pad_f = (-self.bins.shape[1]) % n_dev
                self._pad_cols = pad_f
                if pad_f:
                    self.bins = jnp.pad(self.bins, ((0, 0), (0, pad_f)))
                    self.num_bins_arr = jnp.pad(self.num_bins_arr,
                                                (0, pad_f),
                                                constant_values=1)
                    self.nan_bin_arr = jnp.pad(self.nan_bin_arr, (0, pad_f),
                                               constant_values=-1)
                    self.is_cat_arr = jnp.pad(self.is_cat_arr, (0, pad_f))
            elif tl == "data_gspmd":
                # GSPMD: no explicit shard_map — the ordinary serial code
                # paths run over row-sharded arrays and XLA's partitioner
                # inserts the collectives (parallel/gspmd.py).  No row
                # padding and no per-mode grower dispatch; when n does
                # not divide the mesh, placement falls back to
                # replicated (device_put refuses uneven shards) and the
                # program runs unpartitioned but correct.
                if train_set.num_data % n_dev:
                    log.warning(
                        f"tree_learner=data_gspmd: {train_set.num_data} "
                        f"rows do not divide the {n_dev}-device mesh; "
                        "arrays stay replicated (unpartitioned). Use "
                        "tree_learner=data for padded sharding of "
                        "uneven row counts.")
                self.bins = self._place_rows(train_set.bins)
            else:
                # pad rows so n divides the mesh (padded rows masked out).
                # Rows that divide it go from the host straight to their
                # shards: through the first device the whole matrix
                # (3.6 GB at 53M x 67) would sit there first
                self._pad_rows = (-train_set.num_data) % n_dev
                self.bins = self._place_rows(
                    jnp.pad(jnp.asarray(train_set.bins),
                            ((0, self._pad_rows), (0, 0)))
                    if self._pad_rows else train_set.bins)
        elif tl not in ("serial",):
            log.warning(f"tree_learner={tl} requested but only {n_dev} "
                        "device(s) visible; using serial")
        if self.bins is None:
            self.bins = jnp.asarray(train_set.bins)

        # linear leaves (linear_tree=true): raw feature values on device
        # (reference LinearTreeLearner keeps Dataset raw_data_)
        self.linear = bool(config.linear_tree) and train_set.raw is not None
        self.raw_dev = jnp.asarray(train_set.raw) if self.linear else None
        self._valid_raw: List[Optional[jnp.ndarray]] = []

        # hp + constraint arrays, shared with reset_config (ADVICE r3: the
        # reference's GBDT::ResetConfig re-derives these too)
        self._derive_learner_state(config)

        n = train_set.num_data
        k = self.num_tree_per_iteration
        self.scores = self._place_rows(jnp.zeros((n, k), jnp.float32))
        if self.objective is not None:
            self.objective.place_rows(self._place_rows)
        self.init_scores = np.zeros(k)
        self._init_base_score()

        self.sample_strategy = create_sample_strategy(config, n)
        self._rng = np.random.default_rng(
            config.seed if config.seed is not None else config.data_random_seed)

        # validation sets
        self.valid_sets: List[Dataset] = []
        self.valid_names: List[str] = []
        self.valid_scores: List[jnp.ndarray] = []
        self.valid_metrics: List[List[Metric]] = []
        self._valid_bins: List[jnp.ndarray] = []
        self._valid_bins_t: List[Optional[jnp.ndarray]] = []

    # ------------------------------------------------------------- helpers
    def _phase(self, name: str, **counts):
        """One span (utils/timer.py ``phase``): timed into this
        booster's table and the process-global table, emitted to the
        active recorder and to an open profiler session."""
        return phase(name, self.timer, global_timer, **counts)

    def _dispatch_done(self, fin: Dict[str, int]) -> None:
        """Close one fused dispatch: the counter ``hist_rows_selected``
        and the span ``dispatch_done``, which carries what the dispatch
        finalized as counts (an annotation takes its counts when it
        opens, so it opens here, at the end)."""
        counts = {"rounds": fin["rounds"], "trees": fin["trees"]}
        if fin["rows"]:
            self._count("hist_rows_selected", fin["rows"])
            counts["hist_rows_selected"] = fin["rows"]
            # the histograms those rows went into: a root's and one smaller
            # child's a split (what a pass has to write, whatever it writes)
            counts["hist_leaves_built"] = fin["hists"]
        if self.hp.has_categorical:
            self._count("cat_splits", fin["cat_splits"])
            self._count("cat_subset_splits", fin["cat_subset_splits"])
            self._count("cat_left_levels", fin["cat_left_levels"])
            counts.update({k: fin[k] for k in (
                "splits", "cat_splits", "cat_subset_splits",
                "cat_left_levels")})
        if fin["declined"]:
            self._count("fused_partition_declined", fin["declined"])
        with self._phase("dispatch_done", **counts):
            pass

    def _count(self, name: str, value: float = 1) -> None:
        """Bump a telemetry counter in this booster's registry and the
        process-global one (obs/metrics.py)."""
        count_event(name, value, self.metrics)

    def _place_rows(self, x):
        """Place ``x`` with dim 0 sharded over the data mesh in the
        row-sharded modes.  Under ``tree_learner=data_gspmd`` the
        partitioner keys off input shardings (parallel/gspmd.py).  Under
        the shard_map modes (data, voting) it keeps each shard's rows
        resident on its own device — left on the first device, every
        tree's dispatch would scatter the whole matrix again; a dim 0
        that does not divide the mesh stays where it is (those modes pad
        per tree).  Identity in serial and feature-parallel mode.  A
        host array is taken as it is: each shard goes to its device."""
        if x is None:
            return x
        with self._place(x, "rows"):
            if self.mesh is not None:
                from ..parallel.gspmd import row_sharded
                if self.parallel_mode == "data_gspmd":
                    return row_sharded(self.mesh, x)
                if self.parallel_mode in ("data", "voting") and \
                        int(x.shape[0]) % int(self.mesh.devices.size) == 0:
                    return row_sharded(self.mesh, x)
            return jnp.asarray(x)

    def _place_whole(self, x):
        """Place ``x`` whole on every device of the mesh under the
        shard_map modes (data, voting): what every shard's program reads
        in full and the booster keeps, the valid sets' bins.  Left
        uncommitted on the first device, every call that mixes it with
        the mesh's arrays copies it to the other devices again."""
        with self._place(x, "whole"):
            if self.mesh is not None and \
                    self.parallel_mode in ("data", "voting"):
                from ..parallel.gspmd import replicated
                return replicated(self.mesh, x)
            return jnp.asarray(x)

    def _place(self, x, what: str):
        """The span ``place`` around one placement (``_place_rows``,
        ``_place_whole``: the one place bins, words, scores and the
        objective's row arrays go to the device) with the array's
        ``bytes`` and ``what`` (``rows`` / ``whole``) as counts.  The
        span holds the ENQUEUE of a host array's copy, not its
        arrival."""
        return self._phase("place", bytes=int(getattr(x, "nbytes", 0)),
                           what=what)

    def _config_signature(self):
        """Canonical-config signature for process compile-cache keys:
        every registered parameter's repr, sorted.  Conservatively
        over-keyed — any config difference forces a fresh cache entry,
        which is always correct: the fused runner closes over booster
        state derived from (config, datasets) only, and the datasets
        enter the key as anchors (ops/compile_cache.py)."""
        from ..config import _CANONICAL
        c = self.config
        return tuple((name, repr(getattr(c, name, None)))
                     for name in sorted(_CANONICAL))

    def _collective_bytes_per_tree(self, splits: Optional[int] = None) -> int:
        """Analytic estimate of the bytes all-reduced growing ONE tree of
        ``splits`` splits (default: a full tree) in the active parallel
        mode (psums run inside jit; XLA's actual schedule may
        reduce-scatter, so this is the logical payload, not wire
        traffic).  Data mode psums f32 histograms of ``C = 4`` channels
        (g, h, count and a spare): the root's ``[F, B, C]`` and, per pass
        of the batched grower, ALL its leaves' ``[K, F, B, C]`` (the
        operand a trace shows: ``f32[42,67,256,4]``), ``K`` the pass's
        width: the warm-up widths 1, 4, 16 where the ladder runs, then
        the split batch until the splits are done (a lower bound: a pass
        whose frontier holds fewer than K splittable leaves is not in
        it).  The strict learner psums one ``[F, B, C]`` a split.
        Voting psums each shard's 2·top_k voted [B, 3] slices per split;
        feature mode all-gathers a 12-float SplitInfo per device plus
        one [n] partition psum per split."""
        if self.parallel_mode is None or self.mesh is None:
            return 0
        if splits is None:
            splits = self.hp.num_leaves - 1
        splits = max(1, int(splits))
        B = self.hp.n_bins
        F = self.bins.shape[1]
        if self.parallel_mode in ("data", "data_gspmd"):
            # data_gspmd reduces the same logical histogram payload; the
            # partitioner, not shard_map, chooses the wire schedule
            one = F * B * 4 * 4
            return one * (1 + sum(self._pass_widths(splits)))
        if self.parallel_mode == "voting":
            return splits * 2 * int(self.config.top_k) * B * 3 * 4
        if self.parallel_mode == "feature":
            n_dev = int(self.mesh.devices.size)
            return splits * (n_dev * 12 * 4 + self.bins.shape[0] * 4)
        return 0

    def _pass_widths(self, splits: int) -> List[int]:
        """Leaves per histogram pass after the root's, for a tree of
        ``splits`` splits: one a split under the strict learner; under
        the batched grower its warm-up widths (by the rows a shard
        holds), then the split batch."""
        if not self._use_batched_grower():
            return [1] * splits
        from ..learner.batch_grower import warmup_widths
        K = min(max(1, int(self.config.tpu_split_batch)),
                self.hp.num_leaves - 1)
        rows = self.bins.shape[0]
        if self.parallel_mode != "data_gspmd":
            rows //= int(self.mesh.devices.size)
        widths = []
        for kw in warmup_widths(rows, K, self.hp, self.forced_splits):
            if splits <= 0:
                break
            widths.append(kw)
            splits -= kw
        return widths + [K] * -(-max(splits, 0) // K)

    def telemetry(self) -> Dict[str, Any]:
        """This booster's telemetry snapshot: counters/gauges, the phase
        table, and a current memory sample (surfaced publicly as
        ``Booster.telemetry()``)."""
        from ..obs import compile_events, memory as obs_memory
        snap = self.metrics.snapshot()
        return {"counters": snap["counters"], "gauges": snap["gauges"],
                "phases": self.timer.as_dict(),
                "compile_table": compile_events.table(),
                "memory": obs_memory.memory_snapshot()}

    def prometheus_text(self) -> str:
        """Training-side Prometheus exposition (obs/prom.py): telemetry
        counters/gauges, the watchtower's latest rollup gauges, and SLO
        state — the same format the serving tier scrapes, so one
        dashboard covers both halves."""
        from ..obs import prom
        snap = self.metrics.snapshot()
        rollup_gauges = None
        slo_state = None
        tower = self.watchtower
        if tower is not None:
            rollup_gauges = tower.rollup.latest_gauges()
            slo_state = tower.slo_state()
        return prom.training_text(snap["counters"], snap["gauges"],
                                  rollup_gauges, slo_state)

    def _resolve_auto_params(self, config: Config) -> None:
        """Fast-by-default policy (VERDICT r3 #3): at scale, a plain
        ``train()`` gets the batched grower and the exact quantized-grad
        bf16 kernel path without opting in — the configuration the
        benchmark's cells run.  Decision-identity of that path vs the f32
        kernel is proven (ops/quantize.py, tests/test_quantized.py); leaf
        values are renewed from true gradients.  Small runs keep the exact-f32 strict
        path: there the extra kernel compilations dominate and exactness
        is free.  Any explicit user setting, ``deterministic=true``,
        feature-parallel (no level-scale plumbing) win over the whole
        policy; linear trees opt out of the int8 half only (ridge fits
        need true gradients) and DO get the auto split batch."""
        at_scale = self.train_set.num_data >= 100_000
        # only auto-batch configurations the batched grower supports
        # (linear trees, CEGB and advanced monotone joined in round 4;
        # advanced-under-voting is downgraded to intermediate before
        # growth, so no monotone config blocks batching)
        # voting x categorical joined the batched grower in round 5 (the
        # winner's histogram column psums for the bitset)
        batchable = self.parallel_mode in (None, "data", "voting")
        if not config.is_explicit("tpu_split_batch"):
            if at_scale and batchable and int(config.num_leaves) >= 8:
                # 42: the flat kernel's 3K=126 channels still fit one MXU
                # tile and fewer rounds beat finer width-matching
                # (round-4 int8 sweep: K=28 83.2, K=42 76.9 ms/tree)
                config.tpu_split_batch = min(42, int(config.num_leaves) - 1)
        if (at_scale and not config.deterministic
                and self.parallel_mode != "feature"
                and not bool(config.linear_tree)
                and not config.is_explicit("tpu_hist_dtype")
                and not config.is_explicit("use_quantized_grad")):
            # int8: quantized levels on the int8 MXU path — exact like
            # the bf16-levels mode and ~12% faster end-to-end (round-4
            # sweep: 82 vs 93 ms/tree); off-TPU both fall back to the
            # exact f32 XLA contraction, so the choice is TPU-only
            config.tpu_hist_dtype = "int8"
            config.use_quantized_grad = True
            if not config.is_explicit("quant_train_renew_leaf"):
                config.quant_train_renew_leaf = True
            log.info("auto speed mode: tpu_split_batch=%d, exact "
                     "quantized-grad int8 kernels (set "
                     "tpu_hist_dtype=float32 or deterministic=true to "
                     "opt out)" % int(config.tpu_split_batch))

    def _derive_learner_state(self, config: Config) -> None:
        """Derive ``hp`` and the constraint/penalty device arrays from a
        config.  Called from ``__init__`` AND ``reset_config`` so a
        parameter reset re-applies the histogram-pool translation and
        refreshes monotone/interaction/forced/CEGB arrays exactly like the
        reference's ``GBDT::ResetConfig`` -> ``TreeLearner::ResetConfig``
        (gbdt.cpp, serial_tree_learner.cpp).  Requires ``parallel_mode``
        and the device bins to be set already."""
        train_set = self.train_set
        self._fused_cache = {}   # compiled fused-round runners (train_fused)
        self._batched_decision = None   # memoized _use_batched_grower
        # numeric guard policy (robustness/guards.py); validated by
        # Config.check_param_conflict, re-derived on reset_config
        self.nan_policy = str(config.nan_policy or "none")
        self._resolve_auto_params(config)
        self.hp = _hp_from_config(config, train_set.device_n_bins())
        if bool(train_set.categorical_array().any()):
            # what is static at trace time about the categorical columns:
            # that there are some, and which take the subset scan (not
            # known to a feature-parallel shard, which holds a slice)
            self.hp = dataclasses.replace(
                self.hp, has_categorical=True,
                cat_subset_cols=None if self.parallel_mode == "feature"
                else train_set.cat_subset_columns())

        # monotone constraints: per-ORIGINAL-feature directions from config,
        # remapped to packed (used) features; categorical features forced 0
        self.monotone_arr = None
        mono_cfg = list(config.monotone_constraints or [])
        if any(int(m) != 0 for m in mono_cfg):
            full = np.zeros(train_set.num_total_features, np.int32)
            full[:len(mono_cfg)] = np.asarray(mono_cfg, np.int32)[
                :train_set.num_total_features]
            packed = full[np.asarray(train_set.used_feature_idx)]
            packed[np.asarray(train_set.categorical_array())] = 0
            self.monotone_arr = jnp.asarray(packed)
            method = str(config.monotone_constraints_method)
            if method not in ("basic", "intermediate", "advanced"):
                log.fatal("unknown monotone_constraints_method=%r (expected "
                          "basic/intermediate/advanced)" % method)
            if method == "advanced" and self.parallel_mode in ("voting",
                                                               "feature"):
                # the per-threshold bound arrays are not plumbed through the
                # voted-subset / cross-shard split sync; intermediate is the
                # sound conservative superset there
                log.warning("monotone_constraints_method=advanced is not "
                            "supported with voting/feature parallel modes; "
                            "using 'intermediate'")
                method = "intermediate"
            self.hp = dataclasses.replace(
                self.hp, use_monotone=True, monotone_method=method,
                monotone_penalty=float(config.monotone_penalty))

        isets = _parse_interaction_sets(config.interaction_constraints,
                                        train_set.used_feature_idx)
        self.interaction_sets = None if isets is None else jnp.asarray(isets)
        self._needs_node_rng = (self.hp.extra_trees
                                or self.hp.feature_fraction_bynode < 1.0)
        self.forced_splits = None
        if config.forcedsplits_filename:
            self.forced_splits = _parse_forced_splits(
                config.forcedsplits_filename, train_set, self.hp.num_leaves)

        # CEGB penalties (cost_effective_gradient_boosting.hpp): acquisition
        # state persists across ALL trees like the reference learner's (and
        # resets on reset_config, like its ResetConfig recreating CEGB)
        self.cegb: Optional[CegbInput] = None
        if (float(config.cegb_penalty_split) > 0.0
                or list(config.cegb_penalty_feature_lazy or [])
                or list(config.cegb_penalty_feature_coupled or [])):
            if self.parallel_mode is not None:
                log.fatal("cegb_* penalties are supported with "
                          "tree_learner=serial only")
            tr = float(config.cegb_tradeoff)

            def _vec(lst):
                full = np.zeros(train_set.num_total_features, np.float64)
                a = np.asarray(list(lst or []), np.float64)
                full[:len(a)] = a[:train_set.num_total_features]
                return full[np.asarray(train_set.used_feature_idx)] * tr

            lazy = _vec(config.cegb_penalty_feature_lazy)
            self.cegb = CegbInput(
                split_pen=jnp.float32(tr * float(config.cegb_penalty_split)),
                coupled_pen=jnp.asarray(
                    _vec(config.cegb_penalty_feature_coupled), jnp.float32),
                lazy_pen=jnp.asarray(lazy, jnp.float32),
                feature_used=jnp.zeros(self.num_features, bool),
                used_rows=jnp.zeros((train_set.num_data, self.num_features),
                                    bool) if (lazy != 0).any() else None)

        # whether this job's split search stays on the physical bundle
        # columns (counter bundle_space_search_rounds); voting votes on
        # virtual features and expands
        self._bundle_space = self.parallel_mode != "voting" and \
            searches_in_bundle_space(self.bundle, self.hp,
                                     penalised=self.cegb is not None)
        # bounded histogram pool (reference histogram_pool_size MB,
        # serial_tree_learner.cpp:36-47): translate the MB budget into
        # batched-grower pool slots; evicted parents re-histogram both
        # children directly (learner/batch_grower.py).  Composes with
        # categorical splits (cached winner bitsets) and with the strict
        # order via a batch=1 batched-grower route (_use_batched_grower);
        # derived LAST so the strict-only feature checks see final state.
        pool_mb = float(config.histogram_pool_size)
        n_cols = train_set.bins.shape[1]
        bytes_per_leaf = n_cols * self.hp.n_bins * 4 * 4
        full_state = bytes_per_leaf * self.hp.num_leaves
        if pool_mb <= 0 and not config.is_explicit("histogram_pool_size") \
                and full_state > (4 << 30) and self.parallel_mode is None:
            # wide-data guard: the reference's default (-1) keeps every
            # leaf's histogram resident, but [L, F, B, 4] f32 on an
            # Allstate-wide bundled matrix can exceed HBM before the
            # first tree finishes; cap the resident state at ~1 GB unless
            # the user explicitly asked for unlimited
            pool_mb = 1024.0
            log.info("histogram state would be %.1f GB; engaging the "
                     "bounded pool at 1 GB (set histogram_pool_size=-1 "
                     "to keep all leaves resident)"
                     % (full_state / (1 << 30)))
        if pool_mb > 0:
            slots = int(pool_mb * (1 << 20) // max(bytes_per_leaf, 1))
            kbatch = max(1, int(config.tpu_split_batch))
            slots = max(slots, 3 * kbatch + 2)
            if slots < self.hp.num_leaves:
                if self.parallel_mode == "feature":
                    # feature-parallel shards columns, not rows; its
                    # strict learner keeps full per-shard histograms
                    log.warning("histogram_pool_size ignored under "
                                "tree_learner=feature")
                    self._count("hist_pool_fallbacks")
                else:
                    # cegb / linear_tree / advanced monotone composed in
                    # round 4; forced splits joined in round 6 (the
                    # batched forced phase derives evicted leaves'
                    # columns directly — batch_grower.forced_col_hist)
                    self.hp = dataclasses.replace(
                        self.hp, hist_pool_slots=slots)

        # what the histogram kernels and their state take at this width,
        # once a booster (docs/OBSERVABILITY.md): the column blocks of a
        # compacted pass over the split batch's leaves, the per-leaf state
        # the grower carries, the VMEM budget the blocks follow from
        from ..ops import hist_pallas
        self._count("hist_col_blocks", hist_pallas.pass_col_blocks(
            n_cols, max(1, int(config.tpu_split_batch)), self.hp.n_bins,
            self.hp.hist_dtype))
        self._count("hist_state_bytes", bytes_per_leaf * (
            self.hp.hist_pool_slots or self.hp.num_leaves))
        self._count("hist_vmem_budget_bytes", hist_pallas.VMEM_BUDGET_BYTES)

        # packed-word mirror (round-6 packed histogram mode): ship the
        # dataset's construction-time mirror ONCE per booster instead of
        # re-deriving the word view inside every traced tree; the
        # distributed modes pad rows/columns after construction, so they
        # keep the in-jit derivation
        self.bins_words = None
        if self.parallel_mode in (None, "data_gspmd"):
            # data_gspmd qualifies too: it never pads rows, so the
            # construction-time mirror stays valid (sharded like bins)
            from ..ops.histogram import hist_dispatch
            if hist_dispatch(self.hp.hist_kernel, self.hp.n_bins).mirror:
                self.bins_words = self._place_rows(
                    jnp.asarray(train_set.packed_mirror()))

    def _init_base_score(self) -> None:
        has_init_score = self.train_set.metadata.init_score is not None
        if self.objective is None or has_init_score:
            # reference gbdt.cpp:308 — no boost-from-average when the
            # dataset carries init scores (e.g. train continuation)
            init = np.zeros(self.num_tree_per_iteration)
        elif self.config.boost_from_average or \
                self.objective.NAME in ("mape",):
            init = np.array([self.objective.boost_from_score(k)
                             for k in range(self.num_tree_per_iteration)])
        else:
            init = np.zeros(self.num_tree_per_iteration)
        # boost_from_average only for supported objectives (ref gbdt.cpp:308)
        if self.objective is not None and self.objective.NAME in (
                "lambdarank", "rank_xendcg", "multiclass", "multiclassova"):
            init = np.zeros(self.num_tree_per_iteration)
        self.init_scores = init
        if np.any(init != 0):
            self.scores = self.scores + jnp.asarray(init, jnp.float32)[None, :]
        md = self.train_set.metadata
        if md.init_score is not None:
            isc = md.init_score.reshape(-1, self.num_tree_per_iteration, order="F") \
                if md.init_score.size != md.num_data else \
                md.init_score.reshape(-1, 1)
            self.scores = self.scores + jnp.asarray(isc, jnp.float32)

    def merge_from(self, trees: List[Tree]) -> None:
        """Seed this booster with an init model's trees (reference
        gbdt.h:70 ``MergeFrom``; train continuation).  The init model's
        predictions are already in ``scores`` via the dataset init_score,
        so only the model list and iteration counters move."""
        import copy
        k = self.num_tree_per_iteration
        if len(trees) % k != 0:
            log.fatal("init model has %d trees, not divisible by "
                      "num_tree_per_iteration=%d" % (len(trees), k))
        self.models = [copy.deepcopy(t) for t in trees] + self.models
        self.num_init_iteration = len(trees) // k
        self.iter_ = self.num_init_iteration

    def append_models(self, trees: List[Tree]) -> None:
        """Append another model's trees (reference LGBM_BoosterMerge ->
        GBDT::MergeFrom at the tail).  Score caches go stale and are
        rebuilt from the model list."""
        import copy
        k = self.num_tree_per_iteration
        if len(trees) % k != 0:
            log.fatal("merged model has %d trees, not divisible by "
                      "num_tree_per_iteration=%d" % (len(trees), k))
        self.models = self.models + [copy.deepcopy(t) for t in trees]
        self.iter_ = len(self.models) // k
        self.invalidate_score_cache()

    def invalidate_score_cache(self,
                               only_valid_index: Optional[int] = None
                               ) -> None:
        """Rebuild cached train/valid scores from the current model list
        (after leaf edits, merges or shuffles — the reference's
        ScoreUpdater is re-driven the same way on BoosterSetLeafValue).
        Linear-leaf trees contribute const + coeff·raw, not the plain leaf
        constant (ADVICE r3: the reference replays Tree::Predict, which
        takes the is_linear_ branch, tree.h:587).  ``only_valid_index``
        rebuilds a single valid set's scores (a late-added eval set),
        leaving the train/other caches untouched."""
        k = self.num_tree_per_iteration
        any_linear = any(t.is_linear for t in self.models)
        o2p = {int(o): p
               for p, o in enumerate(self.train_set.used_feature_idx)}

        def linear_adjust(t, arrs, bins_d, n, raw, base):
            """Replace the plain leaf constants with the linear-leaf
            output (host mirror of models/tree.py Tree.predict linear
            branch, on packed raw columns)."""
            leaf = np.asarray(predict_bins_leaf(
                arrs, bins_d, self.nan_bin_arr, self.bundle,
                self.hp.has_categorical))[:n]
            out = (t.leaf_const[leaf] - t.bias).astype(np.float32)
            nan_bad = np.zeros(n, bool)
            for l in range(t.num_leaves):
                feats = t.leaf_features[l]
                if not feats:
                    continue
                rows = leaf == l
                if not rows.any():
                    continue
                cols = [o2p[f] for f in feats]
                vals = raw[np.ix_(rows, cols)]
                nan_bad[rows] = np.isnan(vals).any(axis=1)
                out[rows] += (np.nan_to_num(vals)
                              @ np.asarray(t.leaf_coeff[l])).astype(
                                  np.float32)
            return np.where(nan_bad, base, out)

        def rebuild(n, bins_d, init_score, raw):
            sc = np.zeros((n, k), np.float32) + self.init_scores[None, :]
            if init_score is not None:
                sc += init_score.reshape(sc.shape, order="F") \
                    if init_score.size == sc.size else \
                    init_score.reshape(-1, 1)
            for i, t in enumerate(self.models):
                arrs = _tree_to_arrays_stub(t, self.train_set,
                                            exclude_bias=True)
                contrib = np.asarray(predict_bins_tree(
                    arrs, bins_d, self.nan_bin_arr, self.bundle,
                    self.hp.has_categorical), np.float32)[:n]
                if t.is_linear:
                    if raw is None:
                        log.fatal("score-cache rebuild for a linear_tree "
                                  "model needs the dataset's raw feature "
                                  "matrix (construct with linear_tree "
                                  "enabled)")
                    contrib = linear_adjust(t, arrs, bins_d, n, raw, contrib)
                sc[:, i % k] += contrib
            return jnp.asarray(sc)

        if only_valid_index is None:
            train_raw = self.train_set.raw if any_linear else None
            self.scores = rebuild(self.train_set.num_data, self.bins,
                                  self.train_set.metadata.init_score,
                                  train_raw)
            targets = range(len(self.valid_sets))
        else:
            targets = [only_valid_index]
        for vi in targets:
            vs = self.valid_sets[vi]
            self.valid_scores[vi] = rebuild(
                vs.num_data, self._valid_bins[vi], vs.metadata.init_score,
                vs.raw if any_linear else None)

    def reset_config(self, config: Config) -> None:
        """Swap learning-control parameters on the live booster
        (reference GBDT::ResetConfig gbdt.cpp): learner hyperparameters,
        pool translation, constraint arrays, shrinkage and the sampling
        strategy follow the new config; objective/metrics/dataset stay."""
        if bool(config.linear_tree) != bool(self.config.linear_tree):
            log.warning("linear_tree cannot be changed on a live booster; "
                        "keeping linear_tree=%s" % self.config.linear_tree)
            config.linear_tree = self.config.linear_tree
        self.config = config
        self.shrinkage_rate = float(config.learning_rate)
        self._derive_learner_state(config)
        self.sample_strategy = create_sample_strategy(
            config, self.train_set.num_data)

    def reset_training_data(self, train_set: Dataset) -> None:
        """Point the live booster at a new training set (reference
        GBDT::ResetTrainingData gbdt.cpp); existing trees are kept and
        their predictions rebuilt into the score cache.

        The new dataset must be BIN-ALIGNED with the current one (same
        mappers — construct it with ``create_valid``/``subset`` or from
        the serialized reference); the reference's CheckAlign enforces the
        same."""
        if train_set.num_features != self.num_features:
            log.fatal("new training data has %d features, model needs %d"
                      % (train_set.num_features, self.num_features))
        new_nb = np.asarray(train_set.num_bins_array())
        new_nan = np.asarray(train_set.nan_bin_array())
        new_cat = np.asarray(train_set.categorical_array())
        old_nb = np.asarray(self.num_bins_arr)[:len(new_nb)]
        old_nan = np.asarray(self.nan_bin_arr)[:len(new_nan)]
        old_cat = np.asarray(self.is_cat_arr)[:len(new_cat)]
        if not (np.array_equal(new_nb, old_nb)
                and np.array_equal(new_nan, old_nan)
                and np.array_equal(new_cat, old_cat)):
            log.fatal("reset_training_data: the new dataset's bin mappers "
                      "differ from the model's (construct it against the "
                      "same reference binning)")
        if self._pad_rows or self._pad_cols:
            log.fatal("reset_training_data is not supported in distributed "
                      "padded mode")
        self.train_set = train_set
        if self.objective is not None:
            self.objective.init(train_set.metadata, train_set.num_data)
            self.objective.attach_booster_metrics(self.metrics)
            self.objective.place_rows(self._place_rows)
        for m in self.train_metrics:
            m.init(train_set.metadata, train_set.num_data)
        self.bins = self._place_rows(train_set.bins)
        if getattr(self, "bins_words", None) is not None:
            self.bins_words = self._place_rows(
                jnp.asarray(train_set.packed_mirror()))
        self.sample_strategy = create_sample_strategy(
            self.config, train_set.num_data)
        n = train_set.num_data
        k = self.num_tree_per_iteration
        self.scores = self._place_rows(jnp.zeros((n, k), jnp.float32))
        self._init_base_score()
        self.invalidate_score_cache()

    def add_valid(self, valid_set: Dataset, name: str) -> None:
        """reference GBDT::AddValidDataset (gbdt.cpp:184)."""
        self.valid_sets.append(valid_set)
        self.valid_names.append(name)
        ms = create_metrics(self.config)
        for m in ms:
            m.init(valid_set.metadata, valid_set.num_data)
        self.valid_metrics.append(ms)
        vsc = np.zeros((valid_set.num_data, self.num_tree_per_iteration),
                       np.float32) + self.init_scores[None, :]
        isc = valid_set.metadata.init_score
        if isc is not None:
            vsc += isc.reshape(vsc.shape, order="F") \
                if isc.size == vsc.size else isc.reshape(-1, 1)
        self.valid_scores.append(self._place_whole(vsc))
        # transposed mirror for the matmul valid scorer (round 6) and the
        # fused round program: the per-tree path-aggregation wants rows
        # on lanes.  Made for every booster, only for model classes the
        # matmul path serves, ON THE DEVICE from the placed bins: the
        # host makes no [F, n] array and copies the bins over once (a
        # numpy transpose of the ranking cell's 688 MB took 4.2 s a
        # booster; PERF.md section 6, PR 39)
        with self._phase("valid_mirror"):
            bins = self._place_whole(valid_set.bins)
            mirror = None
            if self._matmul_valid_ok():
                mirror = _valid_mirror_program(
                    bins.sharding if bins.committed else None)(bins)
                self._count("valid_mirror_device_bytes", mirror.nbytes)
                # what a mirror transposed on the host and copied over
                # would count: none is
                self._count("valid_mirror_host_bytes", 0)
        self._valid_bins.append(bins)
        self._valid_bins_t.append(mirror)
        self._valid_raw.append(jnp.asarray(valid_set.raw)
                               if self.linear and valid_set.raw is not None
                               else None)

    def _matmul_valid_ok(self) -> bool:
        """True when per-tree valid scoring can take the matmul
        path-aggregation (models/predict.py predict_bins_tree_matmul)
        instead of the frontier walk: non-linear models whose splits
        are range predicates on the physical column (unbundled, or an EFB
        plan with ranges; learner/grower.py ``split_ranges``) or sets of
        its bins (categorical columns) — the inverse table of a plan
        without ranges is a per-row lookup the matmul formulation has no
        cheap equivalent for, and linear leaves score through their own
        raw-feature path."""
        return ((self.bundle is None or self.bundle.search is not None)
                and not self.linear)

    def _valid_tree_scores(self, arrays: TreeArrays, vi: int) -> jax.Array:
        """One tree's contribution to valid set ``vi``'s scores (leaf
        values must already be shrunk).  Matmul path aggregation where
        eligible (bit-identical to the walk — exactly one leaf matches
        per row); frontier walk otherwise."""
        if self._matmul_valid_ok() and self._valid_bins_t[vi] is not None:
            from ..models.predict import predict_bins_tree_matmul
            return predict_bins_tree_matmul(
                arrays, self._valid_bins_t[vi], self.nan_bin_arr,
                self.bundle, n_bins=self.hp.n_bins,
                has_categorical=self.hp.has_categorical)
        return predict_bins_tree(arrays, self._valid_bins[vi],
                                 self.nan_bin_arr, self.bundle,
                                 self.hp.has_categorical)

    # ------------------------------------------------------------ training
    def boosting_gradients(self) -> Tuple[jax.Array, jax.Array]:
        """reference GBDT::Boosting (gbdt.cpp:220).  Gradients run under
        one jit where the objective is pure (jitted_gradients) — the
        eager per-op dispatch of a large gradient graph (lambdarank's
        pairwise sort) otherwise dominates the iteration."""
        if self.objective is None:
            log.fatal("No objective; pass grad/hess to train_one_iter")
        if self.num_tree_per_iteration == 1:
            g, h = self.objective.jitted_gradients(self.scores[:, 0])
            return g[:, None], h[:, None]
        return self.objective.jitted_gradients(self.scores)

    def _debug_check_tree(self, arrays, leaf_of_row, row_mask) -> None:
        """Per-tree invariant checks (reference cuda_single_gpu_tree_learner
        DEBUG CheckSplitValid :571 and host/device cross-checks :93-95):
        leaf assignment bounds, leaf-count bookkeeping vs the actual
        partition, and child-pointer sanity.  Enabled by
        ``tpu_debug_checks=true``; costs one device->host sync per tree."""
        nl = int(arrays.num_leaves)
        lor = np.asarray(leaf_of_row)
        if lor.min() < 0 or lor.max() >= nl:
            log.fatal("debug check: leaf_of_row out of range [0, %d): "
                      "min=%d max=%d" % (nl, lor.min(), lor.max()))
        mask = np.ones(lor.shape[0], bool) if row_mask is None \
            else np.asarray(row_mask)
        counts = np.bincount(lor[mask], minlength=self.hp.num_leaves)
        stored = np.asarray(arrays.leaf_count)
        # rtol guards against f32-accumulated counts drifting by >0.5 on
        # very large leaves (>2^24 rows) — ADVICE r1
        if not np.allclose(counts[:nl], stored[:nl], rtol=1e-6, atol=0.5):
            bad = np.nonzero(~np.isclose(counts[:nl], stored[:nl],
                                         rtol=1e-6, atol=0.5))[0]
            log.fatal("debug check: leaf_count mismatch at leaves %s "
                      "(partition %s vs stored %s)"
                      % (bad[:5], counts[bad[:5]], stored[bad[:5]]))
        lc = np.asarray(arrays.left_child)[:nl - 1]
        rc = np.asarray(arrays.right_child)[:nl - 1]
        for side, arr in (("left", lc), ("right", rc)):
            # child encoding: negative = leaf (-(leaf+1)), positive = node
            if (arr >= nl - 1).any():
                log.fatal("debug check: %s child node index out of range"
                          % side)
            if (-arr - 1 >= nl).any():
                log.fatal("debug check: %s child leaf index out of range"
                          % side)

    def _final_leaf_values(self, arrays, leaf_of_row, cls_idx: int,
                           g_true, h_true, row_mask):
        """One grown tree's final (unshrunk) leaf values: renewed from
        the true gradients under quantized training, renewed by the
        objective (l1/quantile), and the per-leaf ridge fit of a linear
        tree (``lin``: its constants and coefficients, else None)."""
        if bool(self.config.use_quantized_grad) and \
                bool(self.config.quant_train_renew_leaf):
            renewed = renew_leaf_values(
                leaf_of_row, g_true[:, cls_idx], h_true[:, cls_idx],
                row_mask, num_leaves=self.hp.num_leaves,
                lambda_l1=self.hp.lambda_l1, lambda_l2=self.hp.lambda_l2)
            # stump (no split found): keep the original leaf value
            arrays = arrays._replace(leaf_value=jnp.where(
                arrays.num_leaves > 1, renewed, arrays.leaf_value))
        arrays = self._renew_leaves(arrays, leaf_of_row, cls_idx)
        lin = None
        if self.linear and int(arrays.num_leaves) > 1:
            # per-leaf ridge fit on the leaf's numeric path features
            # (reference LinearTreeLearner::CalculateLinear); TRUE
            # gradients, not quantized levels — the ridge solution is
            # not scale-invariant across g/h
            lin = fit_linear_leaves(
                self.raw_dev, leaf_of_row, arrays.leaf_path,
                ~self.is_cat_arr, g_true[:, cls_idx], h_true[:, cls_idx],
                row_mask, arrays.leaf_value,
                float(self.config.linear_lambda))
        return arrays, lin

    def _add_linear_scores(self, arrays, leaf_of_row, cls_idx: int,
                           lin) -> None:
        """A linear tree's contribution to the training scores and to
        every valid set's."""
        const, coeff = lin
        contrib = linear_leaf_scores(self.raw_dev, leaf_of_row, const,
                                     coeff, arrays.leaf_value)
        self.scores = self.scores.at[:, cls_idx].add(
            self.shrinkage_rate * contrib)
        for vi in range(len(self.valid_sets)):
            leaf_v = predict_bins_leaf(arrays, self._valid_bins[vi],
                                       self.nan_bin_arr, self.bundle,
                                       self.hp.has_categorical)
            vraw = self._valid_raw[vi]
            vc = linear_leaf_scores(vraw, leaf_v, const, coeff,
                                    arrays.leaf_value) \
                if vraw is not None else arrays.leaf_value[leaf_v]
            self.valid_scores[vi] = self.valid_scores[vi] \
                .at[:, cls_idx].add(self.shrinkage_rate * vc)

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (reference gbdt.cpp:344 TrainOneIter).
        Returns True when no tree could be grown (early finish)."""
        n = self.train_set.num_data
        k = self.num_tree_per_iteration
        if grad is None or hess is None:
            with self._phase("boosting_gradients"):
                g, h = self.boosting_gradients()
        else:
            g = jnp.asarray(np.asarray(grad, np.float32).reshape(n, k, order="F"))
            h = jnp.asarray(np.asarray(hess, np.float32).reshape(n, k, order="F"))

        if self.nan_policy != "none":
            # one fused isfinite-reduction over (g, h, scores); raises for
            # nan_policy=raise/halt_and_keep_best, True = skip this round
            from ..robustness.guards import enforce_nan_policy
            if enforce_nan_policy(self, g, h):
                self.iter_ += 1
                return False

        row_mask, g, h = self.sample_strategy.sample(self.iter_, g, h, self._rng,
                                                     self.train_set.metadata)
        feature_mask = self._feature_mask_for_tree()

        # gradient quantization (gradient_discretizer.cpp): tree STRUCTURE
        # is found on the discretized grid; leaf values optionally renewed
        # from the true gradients below
        g_true, h_true = g, h
        hist_scales = [None] * k
        if bool(self.config.use_quantized_grad):
            # integer-LEVEL quantization (ops/quantize.py): levels are
            # exact in the bf16 histogram kernel, so the fast kernel's
            # sums become bit-deterministic; the grower multiplies the
            # scales back in after each histogram pass
            with self._phase("quantize"):
                qkey = jax.random.PRNGKey(
                    (self.config.seed or 0) * 7919 + self.iter_)
                gq, hq = [], []
                for c in range(k):
                    gc, hc, gs, hs = discretize_gradients_levels(
                        g[:, c], h[:, c], jax.random.fold_in(qkey, c),
                        n_levels=int(self.config.num_grad_quant_bins),
                        stochastic=bool(self.config.stochastic_rounding),
                        constant_hessian=bool(
                            self.objective is not None
                            and self.objective.is_constant_hessian))
                    gq.append(gc)
                    hq.append(hc)
                    hist_scales[c] = jnp.stack([gs, hs])
                g = jnp.stack(gq, axis=1)
                h = jnp.stack(hq, axis=1)

        finished = True
        count_rows = self._use_batched_grower() and not \
            0 < self.hp.hist_pool_slots < self.hp.num_leaves
        for cls_idx in range(k):
            node_key = None
            if self._needs_node_rng:
                node_key = jax.random.PRNGKey(
                    int(self.config.extra_seed) * 1000003
                    + self.iter_ * k + cls_idx)
            with self._phase("tree_growth"):
                arrays, leaf_of_row = self._grow(g[:, cls_idx],
                                                 h[:, cls_idx], row_mask,
                                                 feature_mask, node_key,
                                                 hist_scales[cls_idx])
            # no int(arrays.num_leaves) here: that scalar read blocks on
            # the whole grow computation once per iteration; `finished`
            # is derived from the host tree after from_arrays' single
            # batched transfer, and the renew gate moves device-side.
            # Paths that genuinely need the host int early (debug checks,
            # linear trees) keep their own sync.
            if bool(self.config.tpu_debug_checks):
                self._debug_check_tree(arrays, leaf_of_row, row_mask)
            # leaf values, then the scores: span ``score_update`` (the
            # linear branch scores its valid sets inside it); scoring the
            # valid sets with the new tree: span ``valid_eval``
            with self._phase("score_update"):
                arrays, lin = self._final_leaf_values(
                    arrays, leaf_of_row, cls_idx, g_true, h_true, row_mask)
                if lin is not None:
                    self._add_linear_scores(arrays, leaf_of_row, cls_idx, lin)
                else:
                    shrunk = arrays.leaf_value * self.shrinkage_rate
                    # train score update: one-hot contraction beats the [n]
                    # table gather ~25x on TPU (ops/table.py)
                    self.scores = self.scores.at[:, cls_idx].add(
                        take_small_table(shrunk, leaf_of_row))
            if lin is None:
                # valid scores: matmul path aggregation where eligible,
                # frontier traversal otherwise (shrunk values either way)
                with self._phase("valid_eval"):
                    arrays_shrunk = arrays._replace(leaf_value=shrunk)
                    for vi in range(len(self.valid_sets)):
                        contrib = self._valid_tree_scores(arrays_shrunk, vi)
                        self.valid_scores[vi] = \
                            self.valid_scores[vi].at[:, cls_idx].add(contrib)
            with self._phase("tree_finalize"):
                tree = Tree.from_arrays(arrays, self.train_set)
            if tree.num_leaves > 1:
                finished = False
            if self.parallel_mode is not None:
                # what the tree that came back had all-reduced, and under
                # the batched data learner the rows its passes read, from
                # the host tree's own counts (no second transfer)
                self._count("collective_bytes",
                            self._collective_bytes_per_tree(
                                tree.num_leaves - 1))
                if self.parallel_mode == "data" and count_rows:
                    self._count("hist_rows_selected",
                                _hist_rows_selected(tree, n))
            if lin is not None:
                tree.set_linear(np.asarray(lin[0], np.float64),
                                np.asarray(lin[1], np.float64),
                                self.train_set.used_feature_idx,
                                ~np.asarray(self.is_cat_arr))
            tree.apply_shrinkage(self.shrinkage_rate)
            if self.iter_ == 0 and abs(self.init_scores[cls_idx]) > 1e-10:
                tree.add_bias(self.init_scores[cls_idx])
            self.models.append(tree)
        self.iter_ += 1
        self._count("iterations")
        self._count("strict_rounds")
        if self._bundle_space:
            self._count("bundle_space_search_rounds")
        if self.parallel_mode is not None:
            self._count("sharded_rounds")
        self._count("trees_grown", k)
        return finished

    # ------------------------------------------------- fused iterations
    def supports_fused(self) -> bool:
        """True when whole boosting ROUNDS can run inside one jit
        (``train_fused``).  The fused path must be a pure device program:
        anything that reads or writes host state per iteration — custom
        objectives, l1/quantile leaf renewal, position-debias bias
        vectors, by-query bagging's host expansion, CEGB acquisition
        state, linear fits, DART drops — keeps the classic loop.  Since
        round 5, plain/pos-neg bagging and GOSS run in-jit (their masks
        derive from ``fold_in(PRNGKey(bagging_seed), iter)`` in BOTH
        paths — sample_strategy.py ``device_sample_fn``), and registered
        valid sets ride the scan when every valid metric has a device
        evaluation (``fused_valid_ok``)."""
        c = self.config
        return (type(self) is GBDT
                and self.objective is not None
                and not self.objective.need_renew_tree_output
                # the fused chunk jit-traces get_gradients; objectives
                # with per-call mutable state (rank_xendcg's RNG split,
                # lambdarank position-bias Newton updates) must stay on
                # the eager per-iteration loop — jit_safe is the single
                # source of that contract
                and self.objective.jit_safe
                # data_gspmd runs the fused scan over sharded inputs —
                # same serial program, partitioner-inserted collectives
                and self.parallel_mode in (None, "data_gspmd")
                and not self.linear
                and self.cegb is None
                # the per-round numeric guard is a host-side check; the
                # fused scan cannot surface a mid-chunk trip
                and self.nan_policy == "none"
                and not bool(c.tpu_debug_checks)
                and (not self.valid_sets or self.fused_valid_ok())
                and (self._sampling_is_noop()
                     or self._device_sample_fn() is not None)
                and self._use_batched_grower())

    def _device_sample_fn(self):
        """The sampling strategy's pure in-jit twin, or None (see
        sample_strategy.py ``device_sample_fn``)."""
        return self.sample_strategy.device_sample_fn(
            self.train_set.metadata)

    def fused_valid_ok(self) -> bool:
        """Valid sets can ride the fused scan when every registered valid
        metric has a traceable device evaluation (metrics.py
        ``eval_device_traced``).  Multiclass rides too (round 6 — the
        in-scan eval hands multi-output metrics the full [n, k] score
        matrix; multi_logloss / multi_error carry device kernels)."""
        from ..metrics import Metric as _MetricBase
        if bool(self.config.deterministic) or \
                not bool(self.config.tpu_device_eval):
            return False
        for ms in self.valid_metrics:
            if not ms:
                return False
            for m in ms:
                has_traced = (type(m).eval_device_traced
                              is not _MetricBase.eval_device_traced
                              or m._DEV_KIND is not None)
                if not has_traced:
                    return False
                if self.num_tree_per_iteration != 1 and not m._DEV_MULTI:
                    # the in-scan eval hands multiclass runs the full
                    # [n, k] matrix; single-column device kernels (l2,
                    # auc, ...) can't consume it
                    return False
        return True

    def _fused_operands(self):
        """What the fused round program takes as ARGUMENTS besides the
        scores and the training bins: ``(objective's, [per valid set
        [per metric]], [per valid set the transposed bins or None])``.
        Whatever is None here the program closes over, and a closed-over
        array is a literal of its HLO: serialized with it, hashed for the
        cache key, stored in the cache entry.  The objective and the
        metrics say what of theirs is large (a ranking job's slot
        matrices); a valid set's bins go in from ``_LITERAL_MAX_BYTES``."""
        bins_t = [b if b is not None and b.nbytes > _LITERAL_MAX_BYTES
                  else None for b in self._valid_bins_t]
        return (self.objective.fused_operands(),
                [[m.fused_operands() for m in ms]
                 for ms in self.valid_metrics], bins_t)

    def _sampling_is_noop(self) -> bool:
        """No per-iteration row sampling: the default
        BaggingSampleStrategy no-ops unless bagging is actually
        configured (bagging.hpp's own is_use_subset gate)."""
        c = self.config
        if str(c.data_sample_strategy) == "goss":
            return False
        return (float(c.bagging_fraction) >= 1.0
                and float(c.pos_bagging_fraction) >= 1.0
                and float(c.neg_bagging_fraction) >= 1.0) \
            or int(c.bagging_freq) <= 0

    @staticmethod
    def fused_chunk_for(num_rounds: int) -> int:
        """Chunk length for ``train_fused``: the largest c <= 40 that
        divides ``num_rounds`` (>= 8), so the whole run reuses ONE
        compiled scan; 32 + a ragged tail otherwise."""
        for c in range(40, 7, -1):
            if num_rounds % c == 0:
                return c
        return 32

    @classmethod
    def fused_chunks(cls, num_rounds: int):
        """The exact scan-length sequence ``train_fused`` will run —
        the single source of truth shared with warm-up code (the
        benchmark's harness) that precompiles each length."""
        c = cls.fused_chunk_for(num_rounds)
        out, done = [], 0
        while done < num_rounds:
            t = min(c, num_rounds - done)
            out.append(t)
            done += t
        return out

    def _fused_metric_layout(self):
        """Static (set_name, display_name, bigger) rows matching the
        concatenation order of the in-scan metric eval."""
        rows = []
        for vi, ms in enumerate(self.valid_metrics):
            for m in ms:
                for disp in m.display_names():
                    rows.append((self.valid_names[vi], disp,
                                 bool(m.bigger_is_better)))
        return rows

    def train_fused(self, num_rounds: int, chunk: int = 0,
                    cb_driver=None, es_params=None) -> bool:
        """Run ``num_rounds`` boosting iterations with the gradient step,
        row sampling, tree growth, score update, valid-set scoring and
        metric eval of every round inside ONE compiled scan (chunked so
        two compilations cover any round count).

        The classic loop pays a host/device round trip per iteration.
        The reference amortizes per-iteration launch overhead the same
        way on CUDA by keeping the whole iteration on-device
        (gbdt.cpp boosting_on_gpu / cuda gbdt path); here the rounds
        themselves fuse.  Trees materialize on the host from ONE stacked
        transfer per chunk.  Returns True if growth finished early (a
        stump round).

        ``cb_driver(iteration, evals)`` — optional host hook run once per
        round with the device-evaluated metric list (engine.py feeds the
        REAL callbacks through it, so early_stopping/log_evaluation/
        record_evaluation semantics are bit-for-bit the classic loop's).
        An EarlyStopException from it truncates this booster to the
        detection round (score caches rebuilt) and re-raises.

        ``es_params`` — optional (stopping_rounds, first_metric_only,
        min_delta) mirror of the early_stopping callback, enabling the
        IN-JIT stop flag: once the flag trips, remaining rounds in the
        chunk skip growth entirely (lax.cond), so a stopped run pays no
        overshoot compute.  Enabled only at min_delta == 0, where the
        in-jit f32 comparisons provably agree with the host callback's
        f64 comparisons of the same f32 values (strict >/< of identical
        floats); the host decision stays authoritative either way."""
        from ..learner.batch_grower import grow_tree_batched

        if chunk <= 0:
            chunk = self.fused_chunk_for(num_rounds)
        quant = bool(self.config.use_quantized_grad)
        renew = quant and bool(self.config.quant_train_renew_leaf)
        n_levels = int(self.config.num_grad_quant_bins)
        stoch = bool(self.config.stochastic_rounding)
        const_hess = bool(self.objective is not None
                          and self.objective.is_constant_hessian)
        seed_q = (self.config.seed or 0) * 7919
        seed_node = int(self.config.extra_seed) * 1000003
        shrink = self.shrinkage_rate
        frac = float(self.config.feature_fraction)
        if not hasattr(self, "_fused_cache"):
            self._fused_cache = {}

        k = self.num_tree_per_iteration
        nvalid = len(self.valid_sets)
        mrows = self._fused_metric_layout() if nvalid else []
        use_es = (es_params is not None and cb_driver is not None
                  and nvalid > 0 and float(es_params[2]) == 0.0)
        if use_es:
            es_rounds, es_first, _ = int(es_params[0]), bool(es_params[1]), 0
            bigger_arr = jnp.asarray([r[2] for r in mrows])
            if es_first:
                fam0 = mrows[0][1].split("@")[0]
                consider = jnp.asarray(
                    [r[1].split("@")[0] == fam0 for r in mrows])
            else:
                consider = jnp.ones((len(mrows),), bool)

        def make_runner(T: int, has_fm: bool):
            dev_sample = self._device_sample_fn() \
                if not self._sampling_is_noop() else None

            def eval_valid_traced(vsc, metric_ops):
                parts = []
                for vi, ms in enumerate(self.valid_metrics):
                    # single-output metrics see the [n] column, multi-
                    # output metrics the full [n, k] matrix (round 6)
                    sc = vsc[vi][:, 0] if k == 1 else vsc[vi]
                    for m, ops in zip(ms, metric_ops[vi]):
                        extra = () if ops is None else (ops,)
                        parts.append(jnp.asarray(
                            m.eval_device_traced(sc, self.objective, *extra),
                            jnp.float32))
                return jnp.concatenate(parts) if parts else \
                    jnp.zeros((0,), jnp.float32)

            def run(scores, bins, bwords, qkeys, nkeys, fmasks, iters,
                    vscores, es0, operands):
                objective_ops, metric_ops, valid_bins_t = operands
                grad_extra = () if objective_ops is None \
                    else (objective_ops,)

                def valid_tree_scores(arrays_s, vi):
                    # a valid set whose transposed bins came in as an
                    # argument is scored from them (``_fused_operands``
                    # hands over only what ``_valid_tree_scores`` would
                    # take the matmul path with)
                    if valid_bins_t[vi] is None:
                        return self._valid_tree_scores(arrays_s, vi)
                    from ..models.predict import predict_bins_tree_matmul
                    return predict_bins_tree_matmul(
                        arrays_s, valid_bins_t[vi], self.nan_bin_arr,
                        self.bundle, n_bins=self.hp.n_bins,
                        has_categorical=self.hp.has_categorical)

                def round_real(carry, qkey_raw, node_keys, fm, it):
                    sc, vsc, es = carry
                    # sc: [n, k].  One gradient evaluation per round,
                    # then k per-class trees (one-vs-all, exactly the
                    # classic loop's class order) — all in this jit.
                    with jax.named_scope("gradients"):
                        if k == 1:
                            g2, h2 = self.objective.get_gradients(
                                sc[:, 0], *grad_extra)
                            g2, h2 = g2[:, None], h2[:, None]
                        else:
                            g2, h2 = self.objective.get_gradients(
                                sc, *grad_extra)
                        if dev_sample is not None:
                            # in-jit bagging/GOSS draw — same key
                            # derivation as the classic loop
                            # (sample_strategy.py)
                            rmask, g2, h2 = dev_sample(it, g2, h2)
                        else:
                            rmask = None

                    def class_body(cs, xs):
                        # one-vs-all tree for one class — a lax.scan
                        # iteration, NOT a python unroll: the grower
                        # program compiles ONCE however large num_class
                        # is (an unrolled loop multiplied compile time
                        # and executable size by k)
                        sc_c, vsc_c = cs
                        g, h, nkey, cls = xs
                        g_t, h_t = g, h
                        hist_scale = None
                        if quant:
                            from ..ops.quantize import (
                                discretize_gradients_levels)
                            # per-class fold on the raw key words — the
                            # classic loop's fold_in(qkey, cls), in-jit
                            with jax.named_scope("quantize"):
                                qkey = jax.random.fold_in(qkey_raw, cls)
                                g, h, gs, hs = discretize_gradients_levels(
                                    g, h, qkey, n_levels=n_levels,
                                    stochastic=stoch,
                                    constant_hessian=const_hess)
                                hist_scale = jnp.stack([gs, hs])
                        arrays, lor = grow_tree_batched(
                            bins, g, h, rmask, self.num_bins_arr,
                            self.nan_bin_arr, self.is_cat_arr, fm, self.hp,
                            batch=int(self.config.tpu_split_batch),
                            bundle=self.bundle, monotone=self.monotone_arr,
                            hist_scale=hist_scale,
                            interaction_sets=self.interaction_sets,
                            rng_key=nkey, forced=self.forced_splits,
                            bins_words=bwords)
                        if renew:
                            with jax.named_scope("leaf_renew"):
                                renewed = renew_leaf_values(
                                    lor, g_t, h_t, rmask,
                                    num_leaves=self.hp.num_leaves,
                                    lambda_l1=self.hp.lambda_l1,
                                    lambda_l2=self.hp.lambda_l2)
                                arrays = arrays._replace(
                                    leaf_value=jnp.where(
                                        arrays.num_leaves > 1, renewed,
                                        arrays.leaf_value))
                        # shrink BEFORE the gather, exactly like the
                        # classic loop (train_one_iter: shrunk =
                        # leaf_value * rate, then take_small_table) — the
                        # other order differs by an ulp and cascades
                        # through the quantization grid
                        with jax.named_scope("score_update"):
                            shrunk = arrays.leaf_value * shrink
                            sc_c = sc_c.at[:, cls].add(take_small_table(
                                shrunk, lor))
                        if nvalid:
                            # matmul path aggregation replaces the
                            # per-round frontier walk (round 6 — the walk
                            # cost ~107 ms/iter at 1M/200k, VERDICT r5 #4)
                            with jax.named_scope("valid_score"):
                                arrays_s = arrays._replace(
                                    leaf_value=shrunk)
                                vsc_c = tuple(
                                    v.at[:, cls].add(
                                        valid_tree_scores(arrays_s, vi))
                                    for vi, v in enumerate(vsc_c))
                        return (sc_c, vsc_c), arrays

                    (sc, vsc), stacked_cls = jax.lax.scan(
                        class_body, (sc, vsc),
                        (g2.T, h2.T, node_keys,
                         lax.iota(jnp.int32, k)))        # [k, ...] ys
                    with jax.named_scope("valid_metric"):
                        mvals = eval_valid_traced(vsc, metric_ops) \
                            if nvalid else \
                            jnp.zeros((0,), jnp.float32)
                        if use_es:
                            best, best_it, seen, stopped = es
                            # a first evaluation ALWAYS improves (the
                            # host callback's `best is None` bootstrap —
                            # also the NaN case, where a float compare
                            # would say no)
                            improved = (jnp.where(bigger_arr, mvals > best,
                                                  mvals < best) | ~seen) \
                                & consider
                            best = jnp.where(improved, mvals, best)
                            # best_it carries ABSOLUTE iteration indices
                            # and is always set from a real round before
                            # the stall test can trip (seen gate), so
                            # continued training (iter_ > 0 at entry)
                            # counts correctly
                            best_it = jnp.where(improved, it, best_it)
                            seen = seen | consider
                            trip = consider & seen & ~improved & \
                                (it - best_it >= es_rounds)
                            es = (best, best_it, seen,
                                  stopped | jnp.any(trip))
                    return (sc, vsc, es), (stacked_cls, mvals)

                def body(carry, xs):
                    if has_fm:
                        qkey_raw, node_keys, fm, it = xs
                    else:
                        (qkey_raw, node_keys, it), fm = xs, None

                    def real(c):
                        return round_real(c, qkey_raw, node_keys, fm, it)

                    if not use_es:
                        return real(carry)
                    # stop flag tripped: skip growth, emit zero ys (the
                    # host truncates at the detection round and never
                    # reads them)
                    ys_shape = jax.eval_shape(real, carry)[1]
                    zeros = jax.tree.map(
                        lambda s: jnp.zeros(s.shape, s.dtype), ys_shape)
                    return lax.cond(carry[2][3],
                                    lambda c: (c, zeros), real, carry)

                xs = (qkeys, nkeys, fmasks, iters) if has_fm else \
                    (qkeys, nkeys, iters)
                return jax.lax.scan(body, (scores, vscores, es0), xs)
            # donate the train/valid score buffers (args 0 and 7): both
            # are reassigned from the runner's outputs at the call site,
            # so the old buffers are dead the moment the call returns —
            # donation lets XLA update them in place instead of holding
            # two [n, k] copies live.  CPU buffers cannot be donated
            # (jax warns and ignores), so gate on accelerator backends.
            donate = (0, 7) if jax.default_backend() in ("tpu", "gpu") \
                else ()
            return jax.jit(run, donate_argnums=donate)

        finished = False
        done = 0
        has_fm = frac < 1.0
        # callbacks see RELATIVE round indices (the classic loop passes
        # `it` from range(num_boost_round)); continued training starts
        # iter_ at num_init_iteration, so the offset matters
        begin_iter = self.iter_
        # in-jit early-stop state persists ACROSS chunks (one callback
        # state machine per train() run, like the classic loop's)
        if use_es:
            M = len(mrows)
            es_host = (jnp.where(bigger_arr, -jnp.inf, jnp.inf),
                       jnp.zeros((M,), jnp.int32),
                       jnp.zeros((M,), bool), jnp.bool_(False))
        else:
            es_host = ()
        self._last_fused_evals = []
        # rows the histogram passes had to read are counted only where
        # the tree's own counts say them exactly: the serial learner
        # without the bounded pool (which rebuilds evicted parents)
        n_rows = int(self.train_set.num_data)
        count_rows = self.parallel_mode is None and not \
            0 < self.hp.hist_pool_slots < self.hp.num_leaves
        with self._phase("fused_operands"):
            operands = self._fused_operands()
        while done < num_rounds and not finished:
            T = min(chunk, num_rounds - done)
            with self._phase("fused_prepare"):
                # es window parameters are baked into the runner's closure —
                # they must key the cache or a later train_fused call with a
                # different stopping window would reuse a stale in-jit flag
                key = (T, has_fm, nvalid,
                       (es_rounds, es_first) if use_es else None)
                if key not in self._fused_cache:
                    # the booster dict is only a per-train view now; the
                    # compiled runner itself lives in the PROCESS cache, so
                    # a new booster (or reset_config re-derivation) over the
                    # same datasets + config reuses the compiled program
                    # instead of paying XLA again (ISSUE 7 satellite fix).
                    # Keyed on the full config signature + array geometry;
                    # the datasets enter as ANCHORS: their tokens extend the
                    # key (a different dataset with identical shapes cannot
                    # reuse a closure over the old one's device arrays) and
                    # bound the entry's lifetime (no pinned dead HBM).
                    fsig = None if self.forced_splits is None else tuple(
                        np.asarray(a).tobytes() for a in self.forced_splits)
                    cc_key = ("train_fused", key, k, self._config_signature(),
                              fsig,
                              cc_sig((self.scores, self.bins, self.bins_words,
                                      tuple(self.valid_scores), operands)))
                    built = []

                    def _build():
                        built.append(True)
                        return make_runner(T, has_fm)

                    self._fused_cache[key] = cc_get_or_build(
                        cc_key, _build,
                        anchors=(self.train_set, *self.valid_sets),
                        metrics=self.metrics)
                    if built:
                        self._count("fused_runner_cache_misses")
                    else:
                        self._count("fused_runner_cache_hits")
                else:
                    self._count("fused_runner_cache_hits")
                fmasks = None
                if has_fm:
                    # per-ROUND masks: the seed is feature_fraction_seed +
                    # iteration (matching the classic loop, where iter_
                    # advances between draws) — drawing T masks at the same
                    # iter_ would freeze the subset for the whole chunk
                    fmasks = jnp.stack([
                        self._feature_mask_for_tree(self.iter_ + t)
                        for t in range(T)])
                # per-round PRNG keys: python-int seed arithmetic (no
                # traced-int32 overflow for large seeds) rendered straight
                # to threefry key words in numpy — PRNGKey(s) is exactly
                # [s >> 32, s & 0xffffffff] — so a chunk ships ONE [T, 2]
                # array instead of ~3T tiny per-round device dispatches;
                # the class fold_in(., 0) runs inside the jitted body
                def _key_words(vals):
                    return np.array(
                        [[v >> 32 & 0xffffffff, v & 0xffffffff]
                         for v in vals], np.uint32)
                qkeys = jnp.asarray(_key_words(
                    [seed_q + self.iter_ + t for t in range(T)]))
                # node keys per (round, class): the classic loop's
                # PRNGKey(extra_seed * 1000003 + iter * k + cls)
                nkeys = jnp.asarray(_key_words(
                    [seed_node + (self.iter_ + t) * k + cls
                     for t in range(T) for cls in range(k)])
                ).reshape(T, k, 2)
                iters = jnp.arange(self.iter_, self.iter_ + T, dtype=jnp.int32)
            with self._phase("fused_round_scan"):
                (scores, vscores, es_host), (stacked, mvals) = \
                    self._fused_cache[key](
                        self.scores, self.bins, self.bins_words, qkeys,
                        nkeys, fmasks, iters,
                        tuple(self.valid_scores), es_host, operands)
            self.scores = scores
            for vi in range(nvalid):
                self.valid_scores[vi] = vscores[vi]
            with self._phase("fused_chunk_transfer"):
                host = jax.device_get(stacked)  # ONE transfer per chunk
                mhost = np.asarray(jax.device_get(mvals)) \
                    if nvalid else None
            # what this dispatch finalized, for its closing span
            fin = {"rounds": 0, "trees": 0, "rows": 0, "hists": 0,
                   "splits": 0, "cat_splits": 0, "cat_subset_splits": 0,
                   "cat_left_levels": 0, "declined": 0}
            from ..learner.batch_grower import fuses_partition
            declined = self._use_batched_grower() and \
                not fuses_partition(self.bundle)
            if self.hp.has_categorical:
                subset_col = np.zeros(self.num_features, bool)
                subset_col[list(self.train_set.cat_subset_columns())] = True
            try:
                for t in range(T):
                    stumps = 0
                    for cls in range(k):
                        arrays_tc = jax.tree.map(lambda a: a[t, cls], host)
                        with self._phase("tree_finalize"):
                            tree = Tree.from_arrays(arrays_tc, self.train_set)
                        fin["trees"] += 1
                        if count_rows:
                            fin["rows"] += _hist_rows_selected(arrays_tc, n_rows)
                            fin["hists"] += max(int(arrays_tc.num_leaves), 1)
                        if self.hp.has_categorical:
                            ni = int(arrays_tc.num_leaves) - 1
                            cat = np.asarray(arrays_tc.split_cat[:ni], bool)
                            by_set = cat & subset_col[np.asarray(
                                arrays_tc.split_feature[:ni])]
                            fin["splits"] += ni
                            fin["cat_splits"] += int(cat.sum())
                            fin["cat_subset_splits"] += int(by_set.sum())
                            fin["cat_left_levels"] += int(np.asarray(
                                arrays_tc.cat_bitset[:ni])[by_set].sum())
                        tree.apply_shrinkage(self.shrinkage_rate)
                        if self.iter_ == 0 and \
                                abs(self.init_scores[cls]) > 1e-10:
                            tree.add_bias(self.init_scores[cls])
                        self.models.append(tree)
                        if tree.num_leaves <= 1:
                            stumps += 1
                    self.iter_ += 1
                    done += 1
                    fin["rounds"] += 1
                    self._count("iterations")
                    self._count("fused_rounds")
                    fin["declined"] += int(declined)
                    if self._bundle_space:
                        self._count("bundle_space_search_rounds")
                    self._count("trees_grown", k)
                    if nvalid:
                        self._last_fused_evals = [
                            (mrows[j][0], mrows[j][1], float(mhost[t, j]),
                             mrows[j][2]) for j in range(len(mrows))]
                    if cb_driver is not None:
                        try:
                            # feed the REAL callbacks this round's
                            # device-evaluated metrics — identical state
                            # machine to the classic loop's post-iteration
                            # callback pass; iteration is RELATIVE to this
                            # train() run, like the classic loop's range()
                            with self._phase("callbacks"):
                                cb_driver(self.iter_ - 1 - begin_iter,
                                          self._last_fused_evals)
                        except EarlyStopException:
                            # models stop at the detection round (later
                            # rounds were never materialized); the device
                            # advanced the score caches by the whole chunk —
                            # rebuild from the kept models unless the stop
                            # landed exactly on the chunk's last round
                            if t + 1 < T:
                                self.invalidate_score_cache()
                            raise
                    if stumps == k:
                        # the classic loop would have stopped here; drop any
                        # overrun rounds and rebuild scores without them
                        finished = True
                        if t + 1 < T:
                            self.invalidate_score_cache()
                        break
            finally:
                # also on an early stop raised by a callback: the
                # trees finalized so far are in the model
                self._dispatch_done(fin)
        return finished

    def _grow(self, g: jax.Array, h: jax.Array, row_mask, feature_mask,
              node_key, hist_scale=None) -> Tuple[TreeArrays, jax.Array]:
        """One tree via the configured tree learner (serial or a
        shard_map-distributed mode; reference CreateTreeLearner
        tree_learner.cpp:15).  ``hist_scale``: [2] (g, h) scales in
        quantized-levels mode."""
        if self.parallel_mode in (None, "data_gspmd"):
            # under data_gspmd this is the serial program over row-sharded
            # inputs: GSPMD inserts the reductions the explicit path psums
            args = (self.bins, g, h, row_mask, self.num_bins_arr,
                    self.nan_bin_arr, self.is_cat_arr, feature_mask, self.hp)
            if self._use_batched_grower():
                from ..learner.batch_grower import grow_tree_batched
                out = grow_tree_batched(
                    *args, batch=int(self.config.tpu_split_batch),
                    bundle=self.bundle, monotone=self.monotone_arr,
                    hist_scale=hist_scale,
                    interaction_sets=self.interaction_sets,
                    rng_key=node_key, forced=self.forced_splits,
                    cegb=self.cegb, bins_words=self.bins_words)
                if self.cegb is not None:
                    arrays, lor, self.cegb = out
                    return arrays, lor
                return out
            kwargs = dict(monotone=self.monotone_arr, rng_key=node_key,
                          interaction_sets=self.interaction_sets,
                          forced=self.forced_splits, bundle=self.bundle,
                          hist_scale=hist_scale,
                          bins_words=self.bins_words)
            if self.cegb is not None:
                arrays, lor, self.cegb = grow_tree(*args, cegb=self.cegb,
                                                   **kwargs)
                return arrays, lor
            return grow_tree(*args, **kwargs)
        if self.parallel_mode == "feature":
            from ..parallel.feature_parallel import grow_tree_feature_parallel
            if feature_mask is not None and self._pad_cols:
                feature_mask = jnp.pad(feature_mask, (0, self._pad_cols))
            # quantized levels rejected at construction (__init__ fatal);
            # hist_scale is always None on this path
            with phase("collective_grow_dispatch", mode="feature"):
                arrays, lor = grow_tree_feature_parallel(
                    self.mesh, self.bins, g, h, row_mask, self.num_bins_arr,
                    self.nan_bin_arr, self.is_cat_arr, feature_mask, self.hp)
            return arrays, lor
        from ..parallel.data_parallel import (grow_tree_batched_sharded,
                                              grow_tree_sharded)
        p = self._pad_rows
        if p:
            g = jnp.pad(g, (0, p))
            h = jnp.pad(h, (0, p))
            row_mask = jnp.pad(jnp.ones(g.shape[0] - p, bool)
                               if row_mask is None else row_mask, (0, p))
        if self.parallel_mode in ("data", "voting") \
                and self._use_batched_grower():
            with phase("collective_grow_dispatch",
                       mode=self.parallel_mode, batched=True):
                arrays, lor = grow_tree_batched_sharded(
                    self.mesh, self.bins, g, h, row_mask, self.num_bins_arr,
                    self.nan_bin_arr, self.is_cat_arr, feature_mask, self.hp,
                    batch=int(self.config.tpu_split_batch),
                    bundle=self.bundle,
                    monotone=self.monotone_arr, hist_scale=hist_scale,
                    interaction_sets=self.interaction_sets,
                    parallel_mode=self.parallel_mode,
                    top_k=int(self.config.top_k), metrics=self.metrics)
            return arrays, (lor[:-p] if p else lor)
        with phase("collective_grow_dispatch",
                   mode=self.parallel_mode, batched=False):
            arrays, lor = grow_tree_sharded(
                self.mesh, self.bins, g, h, row_mask, self.num_bins_arr,
                self.nan_bin_arr, self.is_cat_arr, feature_mask, self.hp,
                bundle=self.bundle, parallel_mode=self.parallel_mode,
                top_k=int(self.config.top_k), monotone=self.monotone_arr,
                rng_key=node_key, interaction_sets=self.interaction_sets,
                forced=self.forced_splits, hist_scale=hist_scale,
                metrics=self.metrics)
        return arrays, (lor[:-p] if p else lor)

    def _use_batched_grower(self) -> bool:
        """Batched split rounds (learner/batch_grower.py) when requested and
        the tree uses only its supported feature set.  An active bounded
        pool routes through the batched grower even at tpu_split_batch=1
        (batch=1 rounds produce trees IDENTICAL to the strict learner, so
        histogram_pool_size composes with strict leaf-wise order).

        The decision is pure config state, memoized per
        ``_derive_learner_state`` so a fallback is warned about and
        counted ONCE per configuration (``batched_path_fallbacks`` in the
        telemetry registry — VERDICT Weak #5: silent slow-path training
        must be visible)."""
        if self._batched_decision is not None:
            return self._batched_decision
        pool_active = 0 < self.hp.hist_pool_slots < self.hp.num_leaves
        if int(self.config.tpu_split_batch) <= 1 and not pool_active:
            self._batched_decision = False
            return False
        # categorical splits, all three monotone methods, interaction
        # constraints, path smoothing, CEGB, linear trees and (since
        # round 6) forced splits x hist pool are batched-capable
        # (learner/batch_grower.py)
        # batched voting carries the PV-Tree protocol including
        # categorical splits (round 5: the winner's column psums for the
        # bitset, the strict learner's cadence) but not forced splits
        # (batch_grower asserts; advanced monotone is already downgraded
        # to intermediate under voting at construction)
        voting_unsupported = self.parallel_mode == "voting" and \
            self.forced_splits is not None
        # extra_trees / by-node sampling need per-node rng keys, which the
        # sharded batched wrapper does not plumb yet — serial only.
        # data_gspmd runs the SERIAL code path (keys plumb normally), so
        # it is exempt like serial.
        rng_parallel = self.parallel_mode not in (None, "data_gspmd") and (
            self.hp.extra_trees or self.hp.feature_fraction_bynode < 1.0
            or self.forced_splits is not None)
        # CEGB is batched-capable (batch_grower round-4 lift); it only
        # ever reaches this dispatch in serial mode — __init__ fatals on
        # cegb_* with any non-serial tree_learner (gbdt.py:401)
        reasons = [name for name, hit in (
            ("forced-splits-under-voting", voting_unsupported),
            ("extra_trees/bynode-sampling/forced-splits-under-"
             "distributed", rng_parallel),
            ("unsupported-parallel-mode",
             self.parallel_mode not in (None, "data", "voting",
                                        "data_gspmd")),
        ) if hit]
        if reasons:
            log.warning("tpu_split_batch > 1 ignored (%s): falling back "
                        "to the strict leaf-wise learner"
                        % ", ".join(reasons))
            self._count("batched_path_fallbacks")
            from ..obs.events import emit_event
            emit_event("strict_learner_fallback", reasons=reasons)
            if pool_active:
                # the pool lives in the batched grower only; the strict
                # learner keeps the full [L, F, B, 4] state resident, so
                # the user's memory cap is NOT honored on this path —
                # warn and tally like the feature-parallel case
                log.warning("histogram_pool_size inert under the strict "
                            "leaf-wise fallback (%s): full per-leaf "
                            "histogram state stays resident"
                            % ", ".join(reasons))
                self._count("hist_pool_fallbacks")
            self._batched_decision = False
            return False
        self._batched_decision = True
        return True

    def _renew_leaves(self, arrays: TreeArrays, leaf_of_row: jax.Array,
                      cls_idx: int) -> TreeArrays:
        """Leaf-output renewal for l1/quantile/mape (reference
        RenewTreeOutput); returns arrays with UNSHRUNK final leaf values."""
        if self.objective is not None and self.objective.need_renew_tree_output:
            lor = np.asarray(leaf_of_row)
            score_host = np.asarray(self.scores[:, cls_idx], np.float64)
            renewed = self.objective.renew_tree_output(
                score_host, None, lor, int(arrays.num_leaves))
            if renewed is not None:
                lv = np.asarray(arrays.leaf_value).copy()
                lv[:len(renewed)] = renewed
                arrays = arrays._replace(leaf_value=jnp.asarray(lv, jnp.float32))
        return arrays

    def _feature_mask_for_tree(self, iter_: Optional[int] = None
                               ) -> Optional[jax.Array]:
        frac = float(self.config.feature_fraction)
        if frac >= 1.0:
            return None
        f = self.num_features
        kf = max(1, int(np.ceil(frac * f)))
        rng = np.random.default_rng(
            self.config.feature_fraction_seed
            + (self.iter_ if iter_ is None else iter_))
        chosen = rng.choice(f, size=kf, replace=False)
        mask = np.zeros(f, bool)
        mask[chosen] = True
        return jnp.asarray(mask)

    # ------------------------------------------------------------- evaluate
    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        return self._eval_metric_list("training", self.train_metrics,
                                      self.scores)

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        for vi, ms in enumerate(self.valid_metrics):
            out.extend(self._eval_metric_list(
                self.valid_names[vi], ms, self.valid_scores[vi]))
        return out

    def _eval_metric_list(self, set_name, metrics, scores_dev):
        """Evaluate on device where supported (metrics.py eval_device —
        scalars cross the boundary, not score arrays); host f64 otherwise
        and always under deterministic=true."""
        use_dev = (bool(self.config.tpu_device_eval)
                   and not bool(self.config.deterministic)
                   and scores_dev.shape[1] == 1)
        out = []
        score_host = None
        for m in metrics:
            res = m.eval_device(scores_dev[:, 0], self.objective) \
                if use_dev else None
            if res is None:
                if score_host is None:
                    score_host = self._host_scores(scores_dev)
                res = m.eval(score_host, self.objective)
            for name, val in res:
                out.append((set_name, name, val, m.bigger_is_better))
        return out

    def _host_scores(self, scores: jax.Array) -> np.ndarray:
        s = np.asarray(scores, np.float64)
        return s[:, 0] if s.shape[1] == 1 else s

    # ------------------------------------------------------------- predict
    #: rows x trees above which predict_raw batches on the device; below
    #: it the host f64 walk wins (no binning pass, no compile) and keeps
    #: full-double accumulation for the tiny inputs tests compare
    #: bit-tightly.  At 1M rows x 100 trees the host walk measured 136 s
    #: vs ~1 s device (round 4).
    DEVICE_PREDICT_MIN_WORK = 20_000_000

    #: _device_predict_raw row-block geometry, as class attributes so
    #: tests can shrink them to exercise blocking/bucketing without
    #: million-row inputs.  BLOCK bounds the [ni, n] decision-bit
    #: transients (~0.5 GB bf16 per 1M rows at 255 leaves); QUANTUM is
    #: the tail padding grain.
    PREDICT_BLOCK_ROWS = 1_048_576
    PREDICT_TAIL_QUANTUM = 131_072

    def predict_raw(self, X: np.ndarray, start_iteration: int = 0,
                    num_iteration: int = -1, early=None) -> np.ndarray:
        X = np.asarray(X, dtype=np.float64)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        k = self.num_tree_per_iteration
        total_iters = len(self.models) // k
        end = total_iters if num_iteration <= 0 else \
            min(total_iters, start_iteration + num_iteration)
        n_trees = max(0, (end - start_iteration) * k)
        if (early is None and X.shape[0] * n_trees
                >= self.DEVICE_PREDICT_MIN_WORK):
            dev = self._device_predict_raw(X, start_iteration, end)
            if dev is not None:
                return dev
        out = np.zeros((X.shape[0], k))
        active = np.ones(X.shape[0], bool) if early is not None else None
        for it in range(start_iteration, end):
            for c in range(k):
                if early is not None:
                    out[active, c] += self.models[it * k + c].predict(X[active])
                else:
                    out[:, c] += self.models[it * k + c].predict(X)
            if early is not None and (it + 1) % early[1] == 0:
                from ..basic import _margin_reached
                active &= ~_margin_reached(out, early[2])
                if not active.any():
                    break
        return out[:, 0] if k == 1 else out

    def _device_predict_raw(self, X: np.ndarray, start_it: int,
                            end_it: int) -> Optional[np.ndarray]:
        """Batched on-device prediction: bin X once with the training
        mappers (a raw split ``value <= threshold`` is exactly
        ``bin <= threshold_bin`` under them) and run the matmul batch
        predictor — ``predict_numeric_forest`` for plain numeric
        models, ``predict_bitset_forest`` for categorical / EFB-bundled
        / linear models (round 5; these previously kept 15-30x-slower
        walks).  One compiled program instead of a per-tree host walk.
        """
        k = self.num_tree_per_iteration
        models = self.models[start_it * k:end_it * k]
        if not models:
            return None
        # row blocks bound the [ni, n] decision-bit transients of the
        # matmul predictors; ragged tails pad UP so a fresh shape per
        # remainder never pays seconds of XLA compile per distinct
        # predict size.  predict_bucketing=on (default) pads the tail to
        # a GEOMETRIC ladder of quantum multiples {q, 2q, 4q, ..., blk},
        # bounding the compiled program count at log2(blk/q)+1 across
        # ANY mix of request row counts; =off keeps the pre-serving
        # next-multiple-of-q padding (up to blk/q shapes).  Padded rows
        # are sliced off and the matmul predictors are per-row exact, so
        # outputs are bit-identical either way.
        blk = int(self.PREDICT_BLOCK_ROWS)
        tail_q = min(int(self.PREDICT_TAIL_QUANTUM), blk)
        bucketing = self.config.predict_bucketing == "on"
        general = (any(t.is_linear for t in models)
                   or bool(self.hp.has_categorical)
                   or self.bundle is not None)
        if general:
            # categorical / EFB-bundled / linear models: the BITSET
            # forest predictor (per-node decision bitsets over logical
            # bins; sentinel bins make unseen-category and NaN rows
            # match the host raw-space walk, so outputs never depend on
            # batch size)
            from ..models.predict import predict_bitset_forest
            fb, lin, cat_feats = self._forest_bitset_arrays(models, k)
            bins_np = self.train_set.bin_external_pred(X)
            raw_np = np.asarray(X, np.float32) if lin is not None else None
        else:
            from ..models.predict import predict_numeric_forest
            fa = self._forest_arrays(models, k)
            bins_np = self.train_set.bin_external(X)
        outs = []
        n_all = bins_np.shape[0]
        total_pad = 0
        for r0 in range(0, n_all, blk):
            chunk = bins_np[r0:r0 + blk]
            rows = chunk.shape[0]
            if bucketing:
                target = tail_q
                while target < rows:
                    target *= 2
                pad = min(target, blk) - rows
            else:
                pad = (-rows) % tail_q
            total_pad += pad
            if pad:
                chunk = np.concatenate(
                    [chunk, np.zeros((pad, chunk.shape[1]), chunk.dtype)])
            bins_t = jnp.asarray(np.ascontiguousarray(chunk.T))
            if general:
                raw_d = nan_d = None
                if lin is not None:
                    rchunk = raw_np[r0:r0 + blk]
                    if pad:
                        rchunk = np.concatenate(
                            [rchunk, np.zeros((pad, rchunk.shape[1]),
                                              rchunk.dtype)])
                    raw_d = jnp.asarray(np.nan_to_num(rchunk))
                    nan_d = jnp.asarray(
                        np.ascontiguousarray(np.isnan(rchunk).T),
                        jnp.bfloat16)
                # route the (module-jitted) predictor lookup through the
                # process compile cache so predict programs share the
                # round_compile_hits/misses telemetry with the round
                # bodies — a new shape is a counted miss, a repeat a hit
                fn = cc_get_or_build(
                    ("predict_bitset_forest",
                     cc_sig((fb, bins_t, k, cat_feats, lin, raw_d, nan_d))),
                    lambda: predict_bitset_forest, metrics=self.metrics)
                res = fn(fb, bins_t, k, cat_feats=cat_feats,
                         lin=lin, raw=raw_d, raw_nan=nan_d)
            else:
                fn = cc_get_or_build(
                    ("predict_numeric_forest", cc_sig((fa, bins_t, k))),
                    lambda: predict_numeric_forest, metrics=self.metrics)
                res = fn(fa, bins_t, k)
            outs.append(np.asarray(res, np.float64)[:rows])
        if bucketing:
            self._count("predict_bucketed_calls")
            if total_pad:
                self._count("predict_bucket_pad_rows", total_pad)
        out = np.concatenate(outs, axis=0)
        return out[:, 0] if k == 1 else out

    def _forest_bitset_arrays(self, models, k: int):
        """Host Tree list -> stacked BitsetForest (+ LinearLeaves when
        any tree is linear) for the GENERAL matmul predictor.  Numeric
        nodes (bundled or not) stay threshold compares in LOGICAL bin
        space; only true categorical nodes get bitsets, over the narrow
        categorical bin range plus the unseen/NaN sentinel bins of
        ``bin_external_pred``.  Returns (fb, lin, cat_feats)."""
        from ..models.predict import BitsetForest, LinearLeaves
        ds = self.train_set
        L = max(max(t.num_leaves for t in models), 2)
        ni = L - 1
        T = len(models)
        orig_to_packed = {o: p for p, o in enumerate(ds.used_feature_idx)}
        nan_bin_np = np.asarray(self.nan_bin_arr)
        is_cat_np = np.asarray(ds.categorical_array())
        cat_feats = tuple(int(p) for p in np.nonzero(is_cat_np)[0])
        # categorical one-hot width: widest cat feature + 2 sentinels
        Bc = max((ds.mappers[ds.used_feature_idx[p]].num_bin
                  for p in cat_feats), default=1) + 2
        # cat nodes per tree, padded to a shared width (>= 1)
        C = 1
        cat_nodes = []
        for t in models:
            nn = max(t.num_leaves - 1, 0)
            nodes = [nd for nd in range(nn)
                     if int(t.decision_type[nd]) & 1]
            cat_nodes.append(nodes)
            C = max(C, len(nodes))
        feat = np.zeros((T, ni), np.int32)
        thr = np.zeros((T, ni), np.int32)
        dl = np.zeros((T, ni), bool)
        nanb = np.full((T, ni), -2, np.int32)
        catn = np.full((T, C), ni, np.int32)   # ni = dead pad slot
        catf = np.zeros((T, C), np.int32)
        catb = np.zeros((T, C, Bc), np.float32)
        mpos = np.zeros((T, L, ni), np.float32)
        mneg = np.zeros((T, L, ni), np.float32)
        depth = np.full((T, L), -1, np.int32)
        value = np.zeros((T, L), np.float32)
        any_linear = any(t.is_linear for t in models)
        if any_linear:
            Fr = ds.num_total_features
            lconst = np.zeros((T, L), np.float32)
            lcoeff = np.zeros((T, L, Fr), np.float32)
            lmask = np.zeros((T, L, Fr), np.float32)
        for ti, t in enumerate(models):
            nn = max(t.num_leaves - 1, 0)
            value[ti, :t.num_leaves] = t.leaf_value[:t.num_leaves]
            _leaf_path_masks(t, mpos[ti], mneg[ti], depth[ti])
            if any_linear and t.is_linear:
                for l in range(t.num_leaves):
                    lconst[ti, l] = t.leaf_const[l]
                    for fi, f in enumerate(t.leaf_features[l]):
                        lcoeff[ti, l, f] = t.leaf_coeff[l][fi]
                        lmask[ti, l, f] = 1.0
            if nn:
                pf = np.array([orig_to_packed.get(int(f), 0)
                               for f in t.split_feature[:nn]], np.int32)
                feat[ti, :nn] = pf
                thr[ti, :nn] = t.threshold_bin[:nn]
                dl[ti, :nn] = (np.asarray(t.decision_type[:nn]) & 2) > 0
                nanb[ti, :nn] = nan_bin_np[pf]
            for ci, nd in enumerate(cat_nodes[ti]):
                p = int(feat[ti, nd])
                catn[ti, ci] = nd
                catf[ti, ci] = p
                csi = int(t.cat_split_index[nd])
                sets = set(t.cat_threshold[csi])
                mapper = ds.mappers[ds.used_feature_idx[p]]
                for b, c in enumerate(mapper.bin_2_categorical):
                    if c in sets:
                        catb[ti, ci, b] = 1.0
                # sentinels ride at this FEATURE's (num_bin, num_bin+1):
                # unseen -> right (stays 0); NaN -> cat_nan_left
                # (tree.cpp CategoricalDecision)
                if csi < len(t.cat_nan_left) and t.cat_nan_left[csi]:
                    catb[ti, ci, mapper.num_bin + 1] = 1.0
        fb = BitsetForest(
            feat=jnp.asarray(feat), thr=jnp.asarray(thr),
            dl=jnp.asarray(dl), nanb=jnp.asarray(nanb),
            catn=jnp.asarray(catn), catf=jnp.asarray(catf),
            catb=jnp.asarray(catb, jnp.bfloat16),
            mpos=jnp.asarray(mpos, jnp.bfloat16),
            mneg=jnp.asarray(mneg, jnp.bfloat16),
            depth=jnp.asarray(depth), value=jnp.asarray(value),
            cls=jnp.asarray(np.arange(T, dtype=np.int32) % k))
        lin = None
        if any_linear:
            lin = LinearLeaves(const=jnp.asarray(lconst),
                               coeff=jnp.asarray(lcoeff),
                               featmask=jnp.asarray(lmask, jnp.bfloat16))
        return fb, lin, cat_feats

    def _forest_arrays(self, models, k: int):
        """Host Tree list -> stacked ForestArrays for the matmul batch
        predictor: per tree, the per-node split operands plus each
        leaf's path-direction masks (which internal-node decisions, and
        in which direction, place a row in that leaf)."""
        from ..models.predict import ForestArrays
        L = max(max(t.num_leaves for t in models), 2)
        ni = L - 1
        T = len(models)
        orig_to_packed = {o: p for p, o in
                          enumerate(self.train_set.used_feature_idx)}
        nan_bin_np = np.asarray(self.nan_bin_arr)
        feat = np.zeros((T, ni), np.int32)
        thr = np.zeros((T, ni), np.int32)
        dl = np.zeros((T, ni), bool)
        nanb = np.full((T, ni), -2, np.int32)
        mpos = np.zeros((T, L, ni), np.float32)
        mneg = np.zeros((T, L, ni), np.float32)
        depth = np.full((T, L), -1, np.int32)
        value = np.zeros((T, L), np.float32)
        for ti, t in enumerate(models):
            nn = max(t.num_leaves - 1, 0)
            pf = np.array([orig_to_packed.get(int(f), 0)
                           for f in t.split_feature[:nn]], np.int32)
            feat[ti, :nn] = pf
            thr[ti, :nn] = t.threshold_bin[:nn]
            dl[ti, :nn] = (t.decision_type[:nn] & 2) > 0
            nanb[ti, :nn] = nan_bin_np[pf] if nn else 0
            value[ti, :t.num_leaves] = t.leaf_value[:t.num_leaves]
            _leaf_path_masks(t, mpos[ti], mneg[ti], depth[ti])
        return ForestArrays(
            feat=jnp.asarray(feat), thr=jnp.asarray(thr),
            dl=jnp.asarray(dl), nanb=jnp.asarray(nanb),
            mpos=jnp.asarray(mpos, jnp.bfloat16),
            mneg=jnp.asarray(mneg, jnp.bfloat16),
            depth=jnp.asarray(depth), value=jnp.asarray(value),
            cls=jnp.asarray(np.arange(T, dtype=np.int32) % k))

    def predict(self, X: np.ndarray, raw_score: bool = False,
                start_iteration: int = 0, num_iteration: int = -1,
                pred_leaf: bool = False, early=None) -> np.ndarray:
        if pred_leaf:
            X = np.asarray(X, dtype=np.float64)
            if X.ndim == 1:
                X = X.reshape(1, -1)
            k = self.num_tree_per_iteration
            total_iters = len(self.models) // k
            end = total_iters if num_iteration <= 0 else \
                min(total_iters, start_iteration + num_iteration)
            leaves = [self.models[it * k + c].predict_leaf_index(X)
                      for it in range(start_iteration, end) for c in range(k)]
            return np.stack(leaves, axis=1) if leaves else \
                np.zeros((X.shape[0], 0), np.int32)
        raw = self.predict_raw(X, start_iteration, num_iteration, early=early)
        if raw_score or self.objective is None or \
                not self.objective.need_convert_output:
            return raw
        return np.asarray(self.objective.convert_output(jnp.asarray(raw)))

    # -------------------------------------------------------------- export
    def num_trees(self) -> int:
        return len(self.models)

    def current_iteration(self) -> int:
        return self.iter_

    def rollback_one_iter(self) -> None:
        """reference GBDT::RollbackOneIter (gbdt.cpp:454) — pop the last
        iteration's trees and subtract their scores (excluding any folded
        boost-from-average bias, which self.scores tracks separately)."""
        if self.iter_ <= self.num_init_iteration:
            return
        k = self.num_tree_per_iteration
        for c in reversed(range(k)):
            tree = self.models.pop()
            arrays = _tree_to_arrays_stub(tree, self.train_set,
                                          exclude_bias=True)
            contrib = predict_bins_tree(
                arrays, self.bins, self.nan_bin_arr, self.bundle,
                self.hp.has_categorical)[:self.train_set.num_data]
            self.scores = self.scores.at[:, c].add(-contrib)
            # valid scores got this tree in train_one_iter; pop it there too
            for vi in range(len(self.valid_sets)):
                vc = predict_bins_tree(
                    arrays, self._valid_bins[vi], self.nan_bin_arr,
                    self.bundle, self.hp.has_categorical)
                self.valid_scores[vi] = \
                    self.valid_scores[vi].at[:, c].add(-vc)
        self.iter_ -= 1


def _leaf_path_masks(t: Tree, mpos: np.ndarray, mneg: np.ndarray,
                     depth: np.ndarray) -> None:
    """Fill one tree's leaf path-direction masks in place (shared by the
    matmul batch predictors): DFS from the root recording each leaf's
    (node, direction) path; children encode leaves as -(leaf_idx + 1).
    mpos/mneg: [L, ni]; depth: [L] (-1 stays for dead slots)."""
    if t.num_leaves <= 1:
        depth[0] = 0
        return
    stack = [(0, [])]
    while stack:
        node, path = stack.pop()
        for child, left in ((t.left_child[node], True),
                            (t.right_child[node], False)):
            p2 = path + [(node, left)]
            if child < 0:
                leaf = -int(child) - 1
                depth[leaf] = len(p2)
                for nd, lft in p2:
                    (mpos if lft else mneg)[leaf, nd] = 1.0
            else:
                stack.append((int(child), p2))


def _tree_to_arrays_stub(tree: Tree, dataset: Dataset,
                         exclude_bias: bool = False,
                         num_leaves_out: Optional[int] = None) -> TreeArrays:
    """Host Tree -> device TreeArrays (packed feature idx, bin thresholds).
    ``exclude_bias`` subtracts the folded boost-from-average bias so the
    result is the tree's own contribution to the score tensors.
    ``num_leaves_out`` pads every array to a common leaf capacity so
    trees of different sizes stack into one [T, ...] pytree."""
    L = max(num_leaves_out or tree.num_leaves, 2)
    ni = L - 1
    orig_to_packed = {o: p for p, o in enumerate(dataset.used_feature_idx)}
    sf = np.array([orig_to_packed.get(int(f), 0)
                   for f in tree.split_feature], np.int32)

    def pad(a, fill, dtype):
        out = np.full(ni, fill, dtype)
        out[:len(a)] = a[:ni]
        return out

    n_bins = dataset.device_n_bins()
    bitset = np.zeros((ni, n_bins), bool)
    for i in range(min(len(tree.split_feature), ni)):
        if not (tree.decision_type[i] & 1):
            continue
        csi = int(tree.cat_split_index[i])
        if csi < 0 or csi >= len(tree.cat_threshold):
            continue
        mapper = dataset.mappers[int(tree.split_feature[i])]
        table = mapper._cat_2_bin or {}
        for c in tree.cat_threshold[csi]:
            b = table.get(int(c))
            if b is not None and b < n_bins:
                bitset[i, b] = True

    return TreeArrays(
        split_feature=jnp.asarray(pad(sf, 0, np.int32)),
        split_bin=jnp.asarray(pad(tree.threshold_bin, 0, np.int32)),
        default_left=jnp.asarray(pad((tree.decision_type & 2) > 0, False, bool)),
        split_cat=jnp.asarray(pad((tree.decision_type & 1) > 0, False, bool)),
        left_child=jnp.asarray(pad(tree.left_child, -1, np.int32)),
        right_child=jnp.asarray(pad(tree.right_child, -1, np.int32)),
        split_gain=jnp.zeros(ni, jnp.float32),
        cat_bitset=jnp.asarray(bitset),
        internal_value=jnp.zeros(ni, jnp.float32),
        internal_count=jnp.zeros(ni, jnp.float32),
        leaf_value=jnp.asarray(np.concatenate(
            [tree.leaf_value - (tree.bias if exclude_bias else 0.0),
             np.zeros(L - tree.num_leaves)])[:L].astype(np.float32)),
        leaf_count=jnp.zeros(L, jnp.float32),
        leaf_weight=jnp.zeros(L, jnp.float32),
        leaf_depth=jnp.zeros(L, jnp.int32),
        leaf_path=jnp.zeros((L, dataset.num_features), bool),
        num_leaves=jnp.int32(tree.num_leaves),
    )
