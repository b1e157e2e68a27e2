"""Multi-host distributed training launcher.

The reference ships two multi-machine entries: the socket/MPI CLI
(reference: src/network/linkers_socket.cpp mesh from ``machines``/
``machine_list_filename``/``num_machines``, config.h:1086-1110) and the Dask
wrapper (python-package/lightgbm/dask.py — one worker per rank, each calling
plain ``train()`` with network params).  The TPU-native equivalent rides
``jax.distributed``: every process calls :func:`initialize` (coordinator =
first machine), after which ``jax.devices()`` spans all hosts and the SAME
``shard_map`` collectives used single-host scale over ICI/DCN — no custom
transport layer exists to maintain (SURVEY.md §2.6's "delete the entire
layer").

:func:`train_multihost` is the per-process entry (the analogue of Dask's
``_train_part``): each process contributes its local row shard, bin mappers
are agreed on by all-gathering a row sample (the reference loader's
bin-mapper sync, dataset_loader.cpp distributed path), and every process
ends with an identical Booster.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Sequence

import numpy as np

from ..utils import log
from ..ops.table import take_small_table


def initialize(machines: Optional[str] = None,
               machine_list_filename: Optional[str] = None,
               num_machines: Optional[int] = None,
               rank: Optional[int] = None,
               local_listen_port: int = 12400) -> None:
    """Bring up the jax.distributed runtime from reference-style network
    params.  ``machines`` = "host1:port1,host2:port2,..." (first entry is
    the coordinator); alternatively a machine_list file with one host[:port]
    per line.  ``rank`` defaults to $LGBTPU_RANK / $JAX_PROCESS_ID."""
    import jax
    if machine_list_filename and not machines:
        with open(machine_list_filename) as f:
            entries = [ln.strip() for ln in f if ln.strip()]
        machines = ",".join(e if ":" in e else f"{e}:{local_listen_port}"
                            for e in entries)
    if not machines:
        log.fatal("initialize() needs machines= or machine_list_filename=")
    hosts = machines.split(",")
    if num_machines is None:
        num_machines = len(hosts)
    if rank is None:
        rank = int(os.environ.get("LGBTPU_RANK",
                                  os.environ.get("JAX_PROCESS_ID", "0")))
    coordinator = hosts[0] if ":" in hosts[0] \
        else f"{hosts[0]}:{local_listen_port}"
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_machines,
                               process_id=rank)
    log.info(f"distributed runtime up: rank {rank}/{num_machines}, "
             f"{jax.device_count()} global device(s)")


def load_rank_shard(path: str, params: Optional[Dict[str, Any]] = None,
                    rank: Optional[int] = None,
                    num_machines: Optional[int] = None):
    """Load THIS rank's row shard of a text data file.

    Parity with the reference's distributed loading
    (``DatasetLoader::LoadFromFile(filename, rank, num_machines)``,
    dataset_loader.h:23): when ``pre_partition=true`` the file is assumed to
    already contain only this machine's rows and is loaded whole; otherwise
    every rank reads the shared file and keeps its deterministic row stripe
    (round-robin by row index — the reference uses a seeded random
    assignment, dataset_loader.cpp; any agreed disjoint cover works because
    the shards are only ever consumed by order-insensitive histogram sums).

    Returns ``(features, label, meta)`` — feed to :func:`train_multihost`.
    ``rank``/``num_machines`` default to the live jax.distributed process.
    """
    import jax

    from ..config import Config, normalize_params
    from ..io.parser import load_text_file

    cfg = Config(normalize_params(params or {}))
    if rank is None:
        rank = jax.process_index()
    if num_machines is None:
        num_machines = jax.process_count()
    feats, label, meta = load_text_file(path, cfg)
    if bool(cfg.pre_partition) or num_machines <= 1:
        return feats, label, meta
    n = feats.shape[0]
    if meta.get("group") is not None and len(meta["group"]):
        # ranking data: stripe whole QUERIES, not rows — a query's rows must
        # stay on one rank (reference distributed loading keeps query
        # boundaries intact; per-query lambda gradients need them together)
        sizes = np.asarray(meta["group"], np.int64)
        qid_of_row = np.repeat(np.arange(sizes.shape[0]), sizes)
        keep_q = np.arange(sizes.shape[0]) % num_machines == rank
        keep = keep_q[qid_of_row]
        meta = dict(meta)
        meta["group"] = sizes[keep_q]
    else:
        keep = np.arange(n) % num_machines == rank
    feats = feats[keep]
    label = label[keep] if label is not None else None
    meta = {k: (np.asarray(v)[keep] if np.ndim(v) and
                hasattr(v, "__len__") and len(v) == n else v)
            for k, v in meta.items()}
    return feats, label, meta


def train_multihost(params: Dict[str, Any], data,
                    label: Optional[np.ndarray] = None,
                    weight: Optional[np.ndarray] = None,
                    group: Optional[np.ndarray] = None,
                    num_boost_round: int = 100,
                    on_round=None,
                    init_model_text: Optional[str] = None,
                    snapshot_path: Optional[str] = None,
                    snapshot_interval: int = 0):
    """Data-parallel training from per-process row shards.

    Every process passes ITS OWN rows; returns an identical Booster on all
    processes.  Bin mappers are constructed from an all-gathered row sample
    so shards bin identically (reference dataset_loader.cpp rank-sharded
    loading + bin-mapper allgather).  Uses the same grow_tree under
    shard_map as single-host ``tree_learner=data``.

    Elastic hooks (parallel/cluster.py + robustness/elastic.py):
    ``on_round(it)`` fires after each completed round — the cluster
    worker publishes its liveness heartbeat there.  ``init_model_text``
    continues a prior model: its trees are kept, the remaining rounds of
    the TOTAL ``num_boost_round`` are trained, and the score cache is
    rebuilt by predicting the prior model on this rank's rows.
    ``snapshot_path`` + ``snapshot_interval`` make rank 0 publish an
    atomic model-text snapshot every that-many rounds — the recovery
    point an elastic relaunch resumes from (the multihost loop has no
    engine CheckpointManager; the snapshot is this tier's checkpoint).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..basic import Booster, Dataset as UserDataset
    from ..config import Config, normalize_params
    from ..io.dataset import Dataset as InnerDataset
    from ..models.tree import Tree
    from ..objectives import create_objective
    from ..boosting.gbdt import GBDT, _hp_from_config
    from ..learner.grower import grow_tree
    from .mesh import DATA_AXIS

    params = normalize_params(params)
    cfg = Config(params)
    if isinstance(data, (str, os.PathLike)):
        data, flabel, fmeta = load_rank_shard(str(data), params)
        if label is None:
            label = flabel
        if weight is None:
            weight = fmeta.get("weight")
        if group is None and fmeta.get("group") is not None \
                and len(fmeta["group"]):
            group = fmeta["group"]
    if label is None:
        log.fatal("train_multihost: label is required (pass label= or a "
                  "data file whose label column is set)")
    data = np.asarray(data, np.float64)
    label = np.asarray(label)
    n_local = data.shape[0]
    n_proc = jax.process_count()

    # ---- agree on bin mappers: gather a per-process sample of raw rows.
    # The sample size must be identical on every rank (allgather needs equal
    # shapes), so agree on the global MIN shard size first.
    n_all = np.asarray(multihost_utils.process_allgather(
        jnp.asarray([n_local], jnp.int32)))
    n_min = int(n_all.min())
    per = max(1, min(n_min, int(cfg.bin_construct_sample_cnt) // n_proc))
    rng = np.random.default_rng(int(cfg.data_random_seed))
    idx = rng.choice(n_local, size=per, replace=False) if per < n_local \
        else np.arange(n_local)
    sample_global = np.asarray(
        multihost_utils.process_allgather(jnp.asarray(data[idx],
                                                      jnp.float32)))
    sample_global = sample_global.reshape(-1, data.shape[1])

    inner = InnerDataset.from_data(sample_global, label=None, config=cfg)
    # rebin THIS process's rows with the agreed mappers
    local = InnerDataset.from_data(data, label=label, config=cfg,
                                   weight=weight, reference=inner)

    # ---- global device mesh; each process donates its row shard
    mesh = Mesh(np.array(jax.devices()), (DATA_AXIS,))
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    n_dev = jax.device_count()
    # every process pads to the GLOBAL max shard size (rounded up to its
    # device count) so all ranks agree on the assembled global shape even
    # when row striping left them unequal row counts
    dev_per_proc = max(1, n_dev // n_proc)
    n_max = int(n_all.max())
    per_proc = n_max + ((-n_max) % dev_per_proc)
    pad = per_proc - n_local
    bins_l = np.pad(local.bins, ((0, pad), (0, 0)))
    mask_l = np.pad(np.ones(n_local, bool), (0, pad))
    g_shape = (per_proc * n_proc,)

    bins_g = jax.make_array_from_process_local_data(
        sharding, bins_l, (g_shape[0], bins_l.shape[1]))
    mask_g = jax.make_array_from_process_local_data(sharding, mask_l, g_shape)

    hp = _hp_from_config(cfg, local.device_n_bins())
    num_bins = jnp.asarray(local.num_bins_array())
    nan_bin = jnp.asarray(local.nan_bin_array())
    is_cat = jnp.asarray(local.categorical_array())

    objective = create_objective(cfg)
    obj_name = objective.NAME if objective is not None else "regression"
    fast_objs = ("binary", "regression")
    if obj_name not in fast_objs:
        # general path: gradients computed HOST-side per process on this
        # rank's shard (any objective, incl. per-query lambdarank — the
        # Dask wrapper's _train_part likewise runs the full local
        # objective; queries stay whole per rank via load_rank_shard)
        from ..io.dataset import Metadata
        md = Metadata(n_local)
        md.set_label(np.asarray(label, np.float64))
        if weight is not None:
            md.set_weight(np.asarray(weight, np.float64))
        if group is not None:
            md.set_group(np.asarray(group, np.int64))
        objective.init(md, n_local)
        if objective.num_model_per_iteration != 1:
            log.fatal(f"train_multihost supports single-model-per-iteration "
                      f"objectives, got {obj_name}")
    label_l = np.pad(np.asarray(label, np.float32), (0, pad))
    label_g = jax.make_array_from_process_local_data(sharding, label_l,
                                                     g_shape)
    lr = float(cfg.learning_rate)

    from jax import shard_map
    from ..learner.grower import TreeArrays

    tree_specs = jax.tree.map(lambda _: P(),
                              TreeArrays(*[0] * len(TreeArrays._fields)))

    @jax.jit
    def step(scores, bins_a, y, m):
        def local_step(sc, b, yy, mm):
            if obj_name == "binary":
                sign = jnp.where(yy > 0, 1.0, -1.0)
                resp = -sign / (1.0 + jnp.exp(sign * sc))
                g = resp * mm
                h = jnp.abs(resp) * (1.0 - jnp.abs(resp)) * mm + 1e-9
            else:
                g = (sc - yy) * mm
                h = mm
            tree, leaf_of_row = grow_tree(b, g, h, mm > 0, num_bins, nan_bin,
                                          is_cat, None, hp,
                                          axis_name=DATA_AXIS)
            return tree, sc + lr * take_small_table(tree.leaf_value,
                                                    leaf_of_row)

        return shard_map(
            local_step, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS)),
            out_specs=(tree_specs, P(DATA_AXIS)),
            check_vma=False)(scores, bins_a, y, m)

    @jax.jit
    def step_with_grads(scores, bins_a, g_a, h_a, m):
        def local_step(sc, b, g, h, mm):
            tree, leaf_of_row = grow_tree(b, g * mm, h * mm + 1e-9, mm > 0,
                                          num_bins, nan_bin, is_cat, None,
                                          hp, axis_name=DATA_AXIS)
            return tree, sc + lr * take_small_table(tree.leaf_value,
                                                    leaf_of_row)

        return shard_map(
            local_step, mesh=mesh,
            in_specs=(P(DATA_AXIS),) * 5,
            out_specs=(tree_specs, P(DATA_AXIS)),
            check_vma=False)(scores, bins_a, g_a, h_a, m)

    def _local_scores(scores):
        parts = sorted(scores.addressable_shards, key=lambda s: s.index)
        return np.concatenate([np.asarray(s.data) for s in parts])[:n_local]

    def _assemble(tree_list):
        booster = Booster.__new__(Booster)
        booster.params = params
        booster.best_iteration = -1
        booster.best_score = {}
        booster.train_set = None
        booster.pandas_categorical = None
        booster._gbdt = None
        feature_infos = []
        for j in range(local.num_total_features):
            m = local.mappers[j]
            feature_infos.append(
                "none" if m.is_trivial()
                else f"[{m.min_val:g}:{m.max_val:g}]")
        booster._loaded = {
            "trees": list(tree_list), "num_class": 1,
            "num_tree_per_iteration": 1,
            "max_feature_idx": data.shape[1] - 1,
            "objective": obj_name if obj_name != "binary"
            else "binary sigmoid:1",
            "feature_names": local.feature_names,
            "feature_infos": feature_infos,
        }
        return booster

    def _snapshot(tree_list):
        # atomic temp + rename, same idiom as the checkpoint manifest: a
        # relaunching parent never reads a half-written snapshot
        text = _assemble(tree_list).model_to_string()
        tmp_path = snapshot_path + ".tmp"
        with open(tmp_path, "w") as fh:
            fh.write(text)
        os.replace(tmp_path, snapshot_path)

    trees = []
    start_round = 0
    if init_model_text:
        # elastic continuation: keep the prior trees, rebuild this rank's
        # score cache from the prior model's raw prediction on its rows
        prior = Booster(model_str=init_model_text)
        trees = list(prior._loaded["trees"])
        start_round = len(trees)
        if start_round >= num_boost_round:
            log.warning(f"train_multihost: init model already has "
                        f"{start_round} trees (target {num_boost_round}); "
                        "nothing to train")
        raw = np.asarray(prior.predict(data, raw_score=True),
                         np.float32).reshape(-1)
        sc_l = np.pad(raw, (0, pad))
        scores = jax.make_array_from_process_local_data(sharding, sc_l,
                                                        g_shape)
    else:
        scores = jax.device_put(jnp.zeros(g_shape, jnp.float32), sharding)
    for it in range(start_round, num_boost_round):
        if obj_name in fast_objs:
            arrays, scores = step(scores, bins_g, label_g, mask_g)
        else:
            sc_local = _local_scores(scores)
            gj, hj = objective.get_gradients(jnp.asarray(sc_local))
            g_l = np.pad(np.asarray(gj, np.float32).reshape(-1), (0, pad))
            h_l = np.pad(np.asarray(hj, np.float32).reshape(-1), (0, pad))
            g_g = jax.make_array_from_process_local_data(sharding, g_l,
                                                         g_shape)
            h_g = jax.make_array_from_process_local_data(sharding, h_l,
                                                         g_shape)
            arrays, scores = step_with_grads(scores, bins_g, g_g, h_g,
                                             mask_g)
        t = Tree.from_arrays(jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)), arrays), local)
        t.apply_shrinkage(lr)
        trees.append(t)
        if snapshot_path and snapshot_interval > 0 \
                and jax.process_index() == 0 \
                and (it + 1) % snapshot_interval == 0:
            _snapshot(trees)
        if on_round is not None:
            on_round(it)

    return _assemble(trees)
