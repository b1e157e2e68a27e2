"""The wide one-hot job of the benchmark's cell ``allstate-train``
(configuration ``allstate-onehot``), at small seeded sizes on the CPU:
the split search in bundle space against the expansion to virtual space,
the range-predicate partition against the inverse table, matmul valid
scoring against the frontier walk, a bundled job against the same data
unbundled, the program against the plain CSR reference through the
cell's own comparison, that reference against the dense one, the planted
fault, and what the program counts and names for the cell's ``efb_*``
metrics.  (One tree at the cell's shapes compiled for a described chip:
``tests/test_chip_compile.py``, with the other such compiles.)"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

import cells
import lightgbm_tpu as lgb
from cells import BENCH
from lightgbm_tpu.boosting.gbdt import _bundle_search
from lightgbm_tpu.io.bundling import apply_bundles, bundle_ranges, plan_bundles
from lightgbm_tpu.learner import batch_grower, grower
from lightgbm_tpu.learner.grower import DeviceBundle
from lightgbm_tpu.models.predict import (predict_bins_tree,
                                         predict_bins_tree_matmul)
from lightgbm_tpu.obs.metrics import COUNTERS, global_metrics
from lightgbm_tpu.ops import round_fuse
from lightgbm_tpu.ops.split import (SplitHyper, find_best_split,
                                    find_best_split_ranges)
from lightgbm_tpu.utils.timer import global_timer

CELL = "allstate-train"
# no block of two levels: its two columns are complements, an exact tie
# that the default bin's mass, a difference, breaks by rounding
LEVELS = [40, 120, 200, 3, 4, 5, 9]
ROWS, VALID_ROWS, ROUNDS = 30000, 4000, 4
FEW_ROWS = 12000


# ------------------------------------------------ a plan with every layout
def _mixed_bins(seed=0, n=5000):
    """Three dense numeric columns, a one-hot block of 20 levels, and six
    exclusive multi-bin members whose default bin comes first, last and
    in the middle of their bins."""
    rng = np.random.default_rng(seed)
    cols = [rng.integers(0, 40, n) for _ in range(3)]
    level = rng.integers(0, 20, n)
    cols += [(level == k).astype(int) for k in range(20)]
    owner = rng.integers(0, 6, n)
    for k, (nb, d) in enumerate([(5, 0), (6, 5), (7, 3), (4, 0), (5, 2), (3, 2)]):
        c = np.full(n, d)
        c[owner == k] = rng.integers(0, nb, n)[owner == k]
        cols.append(c)
    bins = np.stack(cols, 1).astype(np.uint8)
    return bins, bins.max(0).astype(np.int32) + 1


class _Planned:
    """What ``_bundle_search`` reads of a Dataset."""

    def __init__(self, plan, num_bins):
        self.plan, self.nb = plan, num_bins

    def device_bundle_ranges(self):
        return bundle_ranges(self.plan, self.nb, 256)

    def num_bins_array(self):
        return self.nb


@pytest.fixture(scope="module")
def mixed():
    bins, num_bins = _mixed_bins()
    plan = plan_bundles(bins, num_bins, max_total_bins=256)
    assert sorted(len(b) for b in plan.bundles) == [1, 1, 1, 6, 20]
    assert list(plan.default_bin[23:]) == [0, 5, 3, 0, 2, 2]
    bundle = DeviceBundle(*(jnp.asarray(a) for a in (
        plan.feat_col, plan.src_idx, plan.valid, plan.default_bin,
        plan.inv_table)), search=_bundle_search(_Planned(plan, num_bins)))
    return bins, num_bins, plan, apply_bundles(bins, plan), bundle


@pytest.mark.parametrize("trial", range(6))
def test_bundle_space_search_finds_the_expanded_search_s_split(mixed, trial):
    """Same feature, threshold and sums, gains to float32 rounding, over
    leaves of every size, with and without a feature mask."""
    bins, num_bins, plan, phys, bundle = mixed
    rng = np.random.default_rng(100 + trial)
    n, fv = bins.shape
    g = (rng.normal(size=n) + (bins[:, rng.integers(0, fv)] > 0)
         * rng.normal()).astype(np.float32)
    h = rng.uniform(0.1, 1, n).astype(np.float32)
    sel = rng.random(n) < (0.03, 0.2, 0.5, 0.8, 1.0, 1.0)[trial]
    hist = np.zeros((plan.num_bundles, 256, 4), np.float32)
    for c in range(plan.num_bundles):
        for ch, w in enumerate((g[sel], h[sel], None)):
            hist[c, :, ch] = np.bincount(phys[sel, c], weights=w, minlength=256)
    tot = [jnp.float32(x) for x in (g[sel].sum(), h[sel].sum(), sel.sum())]
    fm = jnp.asarray(rng.random(fv) < 0.8) if trial % 2 else None
    hp = SplitHyper(num_leaves=31, min_data_in_leaf=5,
                    min_sum_hessian_in_leaf=1.0, lambda_l2=0.5)
    nanb, iscat = jnp.full((fv,), -1, jnp.int32), jnp.zeros((fv,), bool)
    before = global_metrics.counter("bundle_expand_calls")
    want = find_best_split(grower._expand_hist(jnp.asarray(hist), bundle, *tot),
                           *tot, jnp.asarray(num_bins), nanb, iscat, fm, hp)
    assert global_metrics.counter("bundle_expand_calls") == before + 1
    got = find_best_split_ranges(jnp.asarray(hist), *tot, bundle.search, fm, hp)
    assert (int(got.feature), int(got.threshold)) == \
        (int(want.feature), int(want.threshold))
    assert not bool(got.default_left) and not bool(got.is_categorical)
    for name in ("gain", "left_sum_g", "left_sum_h", "left_count",
                 "right_sum_g", "right_sum_h", "right_count"):
        np.testing.assert_allclose(getattr(got, name), getattr(want, name),
                                   rtol=2e-4, atol=2e-3, err_msg=name)


def test_range_predicate_partition_routes_as_the_inverse_table(mixed):
    """The fused kernel on the bundle columns, by ``split_ranges``, against
    ``inv_table`` on every member kind and on both sides of a default."""
    bins, num_bins, plan, phys, bundle = mixed
    rng = np.random.default_rng(5)
    n = bins.shape[0]
    feats = np.array([0, 5, 22, 23, 24, 25, 25, 27, 28], np.int32)
    thr = np.array([17, 0, 0, 0, 4, 2, 3, 1, 0], np.int32)
    K = len(feats)
    lor = rng.integers(0, K, n).astype(np.int32)
    parents = np.arange(K, dtype=np.int32)
    new_leaves = np.arange(K, 2 * K, dtype=np.int32)
    ranges = grower.split_ranges(jnp.asarray(feats), jnp.asarray(thr),
                                 jnp.zeros(K, bool), None, bundle, 256)
    new_lor, _ = round_fuse.partition_select_pallas(
        jnp.asarray(phys.T), jnp.asarray(lor), jnp.ones(n, jnp.int32),
        *ranges[:4], ranges[4].astype(jnp.int32), ranges[5],
        jnp.asarray(parents),
        jnp.asarray(new_leaves), jnp.ones(K, jnp.int32),
        jnp.asarray(parents), rows_per_block=512, interpret=True)
    virtual = plan.inv_table[feats[lor], phys[np.arange(n), plan.feat_col[feats[lor]]]]
    np.testing.assert_array_equal(virtual, bins[np.arange(n), feats[lor]])
    want = np.where(virtual <= thr[lor], lor, new_leaves[lor])
    np.testing.assert_array_equal(np.asarray(new_lor), want)
    assert (want != lor).any() and (want == lor).any()


# ----------------------------------------------------------- the cell, small
def _construct(params, data):
    (x, y), (xv, yv) = data
    ds = lgb.Dataset(x, label=y, params=params).construct()
    return ds, ds.create_valid(xv, label=yv).construct()


def _job(cfg, data, sets=None, **more):
    """One job of the cell at the test's size: the booster and what it
    recorded of the valid set a round."""
    cfg = dict(cfg, params={**cfg["params"], **more})
    return cells.train(cfg, sets or _construct(cfg["params"], data), ROUNDS)


def _judged(cfg, inputs, bst, series):
    return cells.judged(cfg, inputs, cells.answers(bst, series))


@pytest.fixture(scope="module")
def cell():
    return cells.find(
        CELL, rows=ROWS, valid_rows=VALID_ROWS, features=15 + sum(LEVELS),
        data={"levels": LEVELS, "pos_rate": 0.2, "logit_sd": 1.5},
        params={"num_leaves": 15, "min_sum_hessian_in_leaf": 5.0},
        compare={"block_rows": 8192, "split_nodes": 8, "split_min_share": 0.05,
                 "split_trees": ROUNDS,
                 "auc_floor": {"round": ROUNDS, "auc": 0.66}})[1]


@pytest.fixture(scope="module")
def data(cell):
    return cells.data(cell)


@pytest.fixture(scope="module")
def inputs(data):
    return cells.inputs(data)


@pytest.fixture(scope="module")
def job(cell, data):
    names = ("efb_bundles", "efb_features", "efb_conflict_rows",
             "bundle_space_search_rounds", "bundle_expand_calls")
    before = {c: global_metrics.counter(c) for c in names}
    global_timer.reset()
    global_timer.enable()       # a booster's start resets this table
    try:
        sets = _construct(cell["params"], data)
        spans = global_timer.as_dict()
    finally:
        global_timer.disable()
    bst, aucs = _job(cell, data, sets)
    moved = {c: global_metrics.counter(c) - v for c, v in before.items()}
    return bst, aucs, moved, spans


def test_the_manifest_names_the_cell_and_its_metrics():
    manifest, cell, cfg, traffic = cells.bench.find_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("allstate-onehot", "train-jobs-csr", 1)
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == ["allstate-train"]]
    # the issue's eight, and the cell's names for the accepted readers of
    # the other layers its round program runs (REVIEW.md)
    same = ["hist_compact_ms", "hist_kernel_ms", "hist_fill_share",
            "tree_root_ms", "score_update_ms", "gradients_ms", "quantize_ms",
            "between_dispatch_ms", "job_start_ms", "compile_s", "lower_s",
            "compile_or_load_s"]
    assert sorted(m["name"] for m in mine) == sorted([
        "efb_bundle_plan_s", "efb_construct_s", "efb_device_idle_share",
        "efb_find_splits_ms", "efb_hist_ms", "efb_partition_ms",
        "efb_unscoped_device_ms", "efb_valid_score_ms"]
        + ["efb_" + n for n in same])
    entry = {m["name"]: {k: v for k, v in m.items()
                         if k not in ("name", "workloads")}
             for m in manifest["per_layer"]}
    assert all(entry["efb_" + n] == entry[n] for n in same)
    assert all(os.path.exists(os.path.join(BENCH, "layers", m["name"] + ".py"))
               for m in mine)
    assert {m["name"]: m["moves"] for m in mine if m["moves"] == "setup_s"} \
        .keys() == {"efb_construct_s", "efb_bundle_plan_s", "efb_compile_s",
                    "efb_lower_s", "efb_compile_or_load_s"}
    # the published job, nothing cut
    assert (cfg["rows"], cfg["valid_rows"], cfg["features"], cfg["reduced"]) \
        == (13184290, 1000000, 4228, [])
    assert cfg["rows"] == cfg["published_rows"]
    assert int(cfg["data"]["numeric"]) + sum(cfg["data"]["levels"]) == 4228
    assert (cfg["params"]["num_leaves"], cfg["params"]["min_data_in_leaf"],
            cfg["params"]["min_sum_hessian_in_leaf"]) == (255, 0, 100)
    assert "enable_bundle" not in cfg["params"]
    assert traffic["num_boost_round"] == 1016 and traffic["dispatch_rounds"] == 8
    assert sum(1 for w in manifest["workloads"] if w["chips"] == 4) == 1


def test_the_generator_returns_one_hot_csr_rows(cell, data):
    (x, y), _ = data
    blocks = len(LEVELS)
    assert x.format == "csr" and x.shape == (ROWS, 15 + sum(LEVELS))
    assert x.dtype == np.float64 and x.indices.dtype == np.int32
    assert (np.diff(x.indptr) == 15 + blocks).all()
    idx = x.indices.reshape(ROWS, -1)
    assert (idx[:, :15] == np.arange(15)).all() and (np.diff(idx, axis=1) > 0).all()
    first = 15 + np.concatenate([[0], np.cumsum(LEVELS)])
    assert ((idx[:, 15:] >= first[:-1]) & (idx[:, 15:] < first[1:])).all()
    assert (x.data.reshape(ROWS, -1)[:, 15:] == 1.0).all()
    assert abs(float(y.mean()) - cell["data"]["pos_rate"]) < 0.02


def test_the_program_counts_and_names_what_the_cell_reads(job):
    bst, _, moved, spans = job
    gb = bst._gbdt
    assert {"efb_bundles", "efb_features", "efb_conflict_rows",
            "bundle_space_search_rounds", "bundle_expand_calls"} <= set(COUNTERS)
    plan = gb.train_set.bundle_plan
    assert gb.bundle.search is not None and gb._bundle_space
    assert moved == {"efb_bundles": plan.num_bundles,
                     "efb_features": 15 + sum(LEVELS), "efb_conflict_rows": 0,
                     "bundle_space_search_rounds": ROUNDS,
                     "bundle_expand_calls": 0}
    c = gb.metrics.counter
    assert (c("efb_bundles"), c("efb_features")) == \
        (plan.num_bundles, 15 + sum(LEVELS))
    assert c("bundle_space_search_rounds") == ROUNDS
    # numeric singletons, and no member of a shared column conflicts
    assert sorted(len(b) for b in plan.bundles)[:15] == [1] * 15
    assert plan.num_bundles < 30
    # construct (train and valid) and the three spans inside the train's
    assert spans["construct"]["count"] == 2
    assert spans["bundle_matrix"]["count"] == 2
    assert spans["sparse_bin_mappers"]["count"] == spans["bundle_plan"]["count"] == 1
    inner = sum(spans[k]["total_s"] for k in
                ("sparse_bin_mappers", "bundle_plan", "bundle_matrix"))
    assert inner <= spans["construct"]["total_s"]


def test_a_bundled_job_grows_the_unbundled_job_s_trees(cell, data):
    """Both jobs on the first 12,000 rows: the unbundled job's histograms are
    one-hot contractions over rows x 396 columns x 256 bins on the CPU (the
    strict grower: 200 s at the file's 30,000 rows), and what is compared
    is the trees, split for split, not what they have learned.  (At 8,000
    rows two numeric splits tie over an empty bin and the two searches
    state the same partition by two thresholds.)"""
    (x, y), valid = data
    few = ((x[:FEW_ROWS], y[:FEW_ROWS]), valid)
    plain, aucs_p = _job(cell, few, enable_bundle=False)
    assert plain._gbdt.bundle is None
    bst, aucs = _job(cell, few)
    assert len(bst._gbdt.train_set.bundle_plan.bundles) < 30
    assert len(bst._gbdt.models) == len(plain._gbdt.models) == ROUNDS
    for a, b in zip(bst._gbdt.models, plain._gbdt.models):
        ni = a.num_leaves - 1
        assert a.num_leaves == b.num_leaves == 15
        assert np.array_equal(a.split_feature[:ni], b.split_feature[:ni])
        assert np.array_equal(a.threshold[:ni], b.threshold[:ni])
        assert np.array_equal(a.leaf_count[:15], b.leaf_count[:15])
        np.testing.assert_allclose(a.leaf_value[:15], b.leaf_value[:15],
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(aucs["auc"], aucs_p["auc"], atol=1e-6)


def test_matmul_valid_scoring_gives_the_frontier_walk_s_scores(job):
    """One tree grown on the job's bundle columns, scored on the valid
    set's both ways: the range predicate on the physical column against
    the walk through ``inv_table``, bit for bit; and the fused partition
    kernel (interpret mode) left the training rows where the walk puts
    them."""
    gb = job[0]._gbdt
    assert gb._matmul_valid_ok() and gb._valid_bins_t[0] is not None
    sign = jnp.where(jnp.asarray(gb.train_set.label) > 0, 1.0, -1.0)
    grad = (-sign * 0.5).astype(jnp.float32)
    hess = jnp.full_like(grad, 0.25)
    round_fuse._FUSE_TEST_INTERPRET = True      # read when traced
    try:
        arrays, leaf_of_row = batch_grower.grow_tree_batched.__wrapped__(
            gb.bins, grad, hess, None, gb.num_bins_arr, gb.nan_bin_arr,
            gb.is_cat_arr, None, gb.hp, batch=4, bundle=gb.bundle)
    finally:
        round_fuse._FUSE_TEST_INTERPRET = False
    assert int(arrays.num_leaves) == 15
    members = np.asarray([len(gb.train_set.bundle_plan.bundles[c])
                          for c in np.asarray(gb.bundle.feat_col)])
    used = np.asarray(arrays.split_feature)[:14]
    assert (members[used] > 1).any() and (members[used] == 1).any()
    walk = predict_bins_tree(arrays, gb._valid_bins[0], gb.nan_bin_arr,
                             gb.bundle, False)
    fast = predict_bins_tree_matmul(arrays, gb._valid_bins_t[0],
                                    gb.nan_bin_arr, gb.bundle,
                                    n_bins=gb.hp.n_bins)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(walk))
    from lightgbm_tpu.models.predict import predict_bins_leaf
    np.testing.assert_array_equal(
        np.asarray(leaf_of_row),
        np.asarray(predict_bins_leaf(arrays, gb.bins, gb.nan_bin_arr,
                                     gb.bundle, False)))


def test_the_program_agrees_with_the_plain_csr_reference(cell, inputs, job):
    correct, compared = _judged(cell, inputs, *job[:2])
    assert correct, compared
    assert compared["leaf_count_mismatch"]["value"] == 0


def test_the_csr_reference_reads_what_the_dense_reference_reads(cell, inputs,
                                                                job):
    answers = cells.answers(*job[:2])
    sparse = cells.numbers(cell, inputs, answers)
    dense = {part: (np.ascontiguousarray(x.toarray().T.astype(np.float32)), y)
             for part, (x, y) in inputs.items()}
    want = cells.numbers(cell, dense, answers, "gbdt_plain", "gbdt_binary")
    assert sparse.keys() == want.keys()
    for k in want:
        np.testing.assert_allclose(sparse[k], want[k], rtol=1e-9, atol=1e-12,
                                   err_msg=k)
    assert sparse["split_searched"] > 0


def test_a_shifted_segment_in_the_search_is_not_correct(cell, data, inputs):
    import faults_efb
    with cells.planted(faults_efb.shift_member_segments):
        correct, compared = _judged(cell, inputs, *_job(cell, data))
    assert not correct
    over = [k for k in ("leaf_count_mismatch", "split_regret_mean")
            if compared[k]["value"] > compared[k]["limit"]]
    assert over, compared


def test_a_search_that_skips_half_the_features_reads_a_finite_regret(
        cell, data, inputs):
    """The fault the regret's limit rests on: every stated split is a true
    and allowed one (the counts agree), only gain is given away, and the
    comparison reads that as a finite number over its limit."""
    import faults_efb
    with cells.planted(faults_efb.skip_odd_features):
        bst, aucs = _job(cell, data)
        used = np.concatenate([t.split_feature[:t.num_leaves - 1]
                               for t in bst._gbdt.models])
        correct, compared = _judged(cell, inputs, bst, aucs)
    assert (used % 2 == 0).all()
    assert not correct and compared["leaf_count_mismatch"]["value"] == 0
    regret = compared["split_regret_mean"]
    assert regret["limit"] < regret["value"] < 1.0, compared


# ----------------------------------------------------- the cell's rehearsal
def test_the_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --workload allstate-train --rehearse-cpu``: the
    cell's whole control flow (CSR generator, construct with its spans,
    the driver's path check, nothing compiled inside the window, the CSR
    reference and comparison) at 120,000 rows, where auto mode still
    picks K=42 and int8; it can never print a result line.  ``correct``
    is not asked for: 12,000 positives under int8 gradient noise give
    the split search's regret no meaning at this size."""
    lines = cells.rehearse(CELL, 3000000019)
    window = next(ln["window"] for ln in lines if "window" in ln)
    setup = next(ln for ln in lines if "setup_phases_s" in ln)
    path = setup["path"]
    assert {k: path[k] for k in ("tpu_split_batch", "hist_dtype",
                                 "packed_mirror", "device_n_bins")} == {
        "tpu_split_batch": 42, "hist_dtype": "int8", "packed_mirror": False,
        "device_n_bins": 256}
    assert path["bundle_space_search_rounds"] == window["rounds"]
    assert path["bundle_expand_calls"] == 0
    assert path["efb_conflict_rows"]["train"] == 0
    assert set(setup["setup_spans_s"]) == {"construct", "sparse_bin_mappers",
                                           "bundle_plan", "bundle_matrix"}
    cells.assert_compared_within_limits(lines, (
        "leaf_count_mismatch", "leaf_value_gap_median", "train_score_gap",
        "valid_auc_gap"))


def test_a_program_without_the_counters_is_refused_at_once(monkeypatch):
    cells.assert_refused_without(monkeypatch, "train_jobs_csr",
                                 "bundle_space_search_rounds")
