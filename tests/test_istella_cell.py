"""The ranking cell ``istella-rank-train`` (configuration ``istella-letor``,
PERF.md section 4), on the CPU at small seeded sizes: the program's
lambdarank gradients and device NDCG against the benchmark's plain
reference (``benchmark/reference/rank_plain.py``, which imports nothing
of the program), a job against the reference through the cell's
comparison, the planted faults and the bfloat16 control, the manifest,
what the program counts and names for the readers, the cell's rehearsal
through ``benchmark/run.py``, the driver's refusal of a program without
the counters, and one tree at 220 columns through the two-plane-group
compaction in interpret mode.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import cells
import lightgbm_tpu as lgb
import lightgbm_tpu.ops.histogram as H
import lightgbm_tpu.ops.round_fuse as RF
from lightgbm_tpu import metrics as M
from lightgbm_tpu import objectives as O
from lightgbm_tpu.config import Config
from lightgbm_tpu.learner.batch_grower import grow_tree_batched
from lightgbm_tpu.obs.metrics import COUNTERS
from lightgbm_tpu.ops.split import SplitHyper

from cells import bench, load_module

CELL = "istella-rank-train"
KS = [1, 3, 5, 10]


@pytest.fixture(scope="module")
def ref():
    return load_module("reference", "rank_plain")


class _Meta:
    weight = position = None


def _skewed(seed, queries=40, longest=300):
    """Skewed query lengths, labels 0-4 mostly 0, scores with many ties."""
    rng = np.random.default_rng(seed)
    sizes = np.clip(rng.lognormal(3.0, 1.0, queries), 2, longest).astype(np.int64)
    n = int(sizes.sum())
    y = np.minimum(4, rng.geometric(0.6, n) - 1).astype(np.float32)
    score = rng.integers(-3, 4, n).astype(np.float32) * np.float32(0.37)
    return sizes, y, score


def _objective(sizes, y, **over):
    cfg = Config({"objective": "lambdarank", "verbose": -1, **over})
    m = _Meta()
    m.label, m.query_boundaries = y, np.concatenate([[0], np.cumsum(sizes)])
    obj = O.create_objective(cfg)
    obj.init(m, len(y))
    return obj


# ------------------------------------------------- gradients and the NDCG
@pytest.mark.parametrize("trunc,norm", [(30, True), (30, False), (5, True)])
def test_gradients_match_the_plain_reference(ref, trunc, norm):
    """Pair by pair as published: skewed query lengths (five rungs of the
    ladder), ties in most queries, the truncation level and the
    normalisation on and off."""
    sizes, y, score = _skewed(3)
    obj = _objective(sizes, y, lambdarank_truncation_level=trunc,
                     lambdarank_norm=norm)
    assert obj._rank_bucket_count >= 4
    g, h = obj.jitted_gradients(jnp.asarray(score))
    want_g, want_h = ref.gradients(ref.Queries(sizes, y, trunc), score, 1.0,
                                   trunc, norm)
    scale = np.abs(want_g).max()
    np.testing.assert_allclose(np.asarray(g), want_g, rtol=2e-5, atol=2e-6 * scale)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=2e-5, atol=2e-6 * scale)
    assert np.abs(want_g).max() > 0 and (want_h >= 0).all()


def test_gradients_are_one_gather_and_no_scatter():
    """Every doc has one slot: the buckets' results come back through
    ``slot_of_doc`` as a gather, and the job's constants ride the sort."""
    sizes, y, score = _skewed(4)
    obj = _objective(sizes, y)
    text = jax.jit(obj.get_gradients).lower(jnp.asarray(score)).as_text()
    assert "scatter" not in text
    slots = np.asarray(obj._rank_state[1])
    assert len(np.unique(slots)) == len(y)
    assert slots.max() < obj._rank_counts["rank_slot_rows"]


def test_device_ndcg_matches_the_reference(ref):
    sizes, y, score = _skewed(5, queries=60)
    y[:sizes[0]] = 0                      # a query without a relevant doc: 1.0
    cfg = Config({"objective": "lambdarank", "metric": "ndcg", "eval_at": KS,
                  "verbose": -1})
    m = M.NDCGMetric(cfg)
    meta = _Meta()
    meta.label, meta.query_boundaries = y, np.concatenate([[0], np.cumsum(sizes)])
    m.init(meta, len(y))
    got = np.asarray(m.eval_device_traced(jnp.asarray(score)))
    want = ref.Queries(sizes, y, 30).ndcg(score, KS)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    host = [v for _, v in m.eval(score.astype(np.float64))]
    np.testing.assert_allclose(host, want, rtol=0, atol=1e-12)


# --------------------------------------------- a job against the reference
@pytest.fixture(scope="module")
def tiny(ref):
    """The cell's configuration at a size the strict learner trains in
    seconds: the comparison needs trees, NDCG series and scores, whatever
    loop gave them."""
    cfg = cells.find(
        CELL, rows=6000, queries=60, valid_rows=2000, valid_queries=20,
        features=24,
        data={"queries": [60, 20], "informative": 8, "min_docs": 8,
              "max_docs": 400},
        params={"num_leaves": 15, "min_sum_hessian_in_leaf": 5.0},
        compare={"block_rows": 8192, "split_nodes": 8, "split_candidates": 16,
                 "split_min_share": 0.1, "split_trees": None})[1]
    parts = cells.data(cfg)
    sets = load_module("drivers", "train_jobs_rank").construct(
        lgb, cfg["params"], *parts)
    comparison = load_module("comparisons", cfg["comparison"])
    inputs = {"train": parts[0], "valid": parts[1]}

    def job(plant_it=None, rounds=4):
        # the faults of tools/faults_rank.py patch the objective, the
        # metric and a hyper-parameter: the strict grower stays compiled
        with cells.planted(plant_it, grower=False):
            bst, series = cells.train(cfg, sets, rounds)
        return cells.answers(bst, series)
    judge = lambda answers: cells.judged(cfg, inputs, answers, seed=0)
    return cfg, job, judge, comparison, inputs


def test_a_sound_job_reads_correct(tiny):
    _, job, judge, _, _ = tiny
    correct, compared = judge(job())
    tight = {k: c for k, c in compared.items() if k != "split_regret_mean"}
    assert all(c["value"] <= c["limit"] for c in tight.values()), compared
    assert compared["leaf_count_mismatch"]["value"] == 0
    assert compared["train_score_gap"]["value"] == 0.0
    assert compared["leaf_value_gap_p99"]["value"] < 1e-4


@pytest.mark.parametrize("fault,reading", [
    ("no_normalisation", "leaf_value_gap_median"),
    ("truncation_quadrupled", "leaf_value_gap_median"),
    ("drop_max_dcg", "leaf_value_gap_median"),
    ("ties_reversed", "leaf_value_gap_p99"),
    ("boundary_off_by_one", "leaf_value_gap_median"),
    ("drop_delta_ndcg", "leaf_value_gap_median"),
    ("ndcg_one_ideal", "valid_ndcg_gap"),
    ("ignore_min_hessian", "stated_hessian_shortfall"),
])
def test_each_planted_fault_reads_not_correct(tiny, fault, reading):
    import faults_rank
    _, job, judge, _, _ = tiny
    correct, compared = judge(job(getattr(faults_rank, fault)))
    assert not correct
    assert compared[reading]["value"] > compared[reading]["limit"], compared


def test_the_bfloat16_control_reads_not_correct(ref, tiny):
    cfg, job, judge, comparison, inputs = tiny
    control = comparison.control_answers(ref, cfg, {"trees": job()["trees"]},
                                         inputs, jnp.bfloat16)
    correct, compared = judge(control)
    assert not correct
    failed = {k for k, c in compared.items() if c["value"] > c["limit"]}
    assert failed & {"leaf_value_gap_median", "leaf_value_gap_p99",
                     "train_score_gap"}, compared
    assert compared["leaf_count_mismatch"]["value"] == 0


# --------------------------------------------------- manifest and readers
NEW = ["rank_grad_gather_ms", "rank_grad_sort_ms", "rank_grad_pairs_ms",
       "rank_grad_accumulate_ms", "rank_ndcg_ms", "rank_slot_fill_share",
       "rank_bucket_plan_s"]
BORROWED = ["rank_gradients_ms", "rank_hist_ms", "rank_hist_compact_ms",
            "rank_hist_kernel_ms", "rank_partition_ms", "rank_find_splits_ms",
            "rank_score_update_ms", "rank_valid_score_ms",
            "rank_unscoped_device_ms", "rank_device_idle_share",
            "rank_between_dispatch_ms", "rank_construct_s", "rank_compile_s",
            # after review: the accepted metrics of layers this cell runs too
            "rank_quantize_ms", "rank_tree_root_ms", "rank_hist_fill_share",
            "rank_unnamed_device_ms", "rank_job_start_ms", "rank_lower_s",
            "rank_compile_or_load_s"]


def test_the_manifest_names_the_cell_and_its_metrics():
    manifest, cell, cfg, traffic = bench.find_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("istella-letor", "train-jobs-rank", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == "istella-letor")
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert (cfg["rows"], cfg["queries"], cfg["valid_rows"], cfg["valid_queries"],
            cfg["features"]) == (7_325_625, 23_219, 3_129_004, 9_799, 220)
    assert cfg["params"]["objective"] == "lambdarank"
    assert cfg["params"]["eval_at"] == KS and traffic["dispatch_rounds"] == 8
    mine = [m for m in manifest["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == NEW + BORROWED
    moves = {m["name"]: m["moves"] for m in mine}
    assert {k for k, v in moves.items() if v == "setup_s"} == \
        {"rank_bucket_plan_s", "rank_construct_s", "rank_compile_s",
         "rank_lower_s", "rank_compile_or_load_s"}
    for m in mine:
        assert callable(load_module("layers", m["name"]).read)
    sizes = load_module("datagen", "letor_queries").query_sizes(
        cfg["data"], 0, cfg["rows"])
    assert sizes.sum() == cfg["rows"] and len(sizes) == cfg["queries"]
    assert len(O._rank_bucket_ladder(sizes, "auto")) >= 4


def test_the_program_counts_and_names_what_the_readers_read():
    driver = load_module("drivers", "train_jobs_rank")
    from harness import rank_trace
    assert set(driver.NEEDS) <= set(COUNTERS)
    sizes, y, score = _skewed(6)
    obj = _objective(sizes, y)
    assert obj._rank_counts["rank_queries"] == len(sizes)
    assert obj._rank_counts["rank_docs"] == len(y)
    assert obj._rank_counts["rank_slot_rows"] == len(y) + obj._rank_pad_rows
    text = jax.jit(obj.get_gradients).lower(jnp.asarray(score)).as_text(
        debug_info=True)
    for scope in rank_trace.RANK_SCOPES[:4]:
        assert scope in text, scope
    cfg = Config({"metric": "ndcg", "eval_at": KS, "verbose": -1})
    m = M.NDCGMetric(cfg)
    meta = _Meta()
    meta.label, meta.query_boundaries = y, np.concatenate([[0], np.cumsum(sizes)])
    m.init(meta, len(y))
    text = jax.jit(m.eval_device_traced).lower(jnp.asarray(score)).as_text(
        debug_info=True)
    assert "ndcg_sort" in text
    # the readers on a hand-written table: time goes to the innermost scope
    ms = 1_000_000
    run = "jit(run)/while/body/"
    table = {"spans": [["bench.window", 0, 100 * ms]], "ops": [
        [0, 0, 10 * ms, "%gather.1", run + "gradients/rank_gather/gather"],
        [0, 10 * ms, 20 * ms, "%sort.1", run + "gradients/rank_sort/sort"],
        [0, 30 * ms, 30 * ms, "%fusion.1", run + "gradients/rank_pairs/mul"],
        [0, 60 * ms, 5 * ms, "%gather.2", run + "gradients/rank_accumulate/gather"],
        [0, 65 * ms, 1 * ms, "%fusion.2", run + "gradients/mul"],
        [0, 70 * ms, 8 * ms, "%sort.2", run + "valid_metric/jit(run)/ndcg_sort/sort"],
        [0, 78 * ms, 2 * ms, "%fusion.3", run + "valid_metric/jit(run)/div"],
        [0, 80 * ms, 9 * ms, "%fusion.4", run + "score_update/add"]]}
    got = rank_trace.reduce_table(table)["scope_s"]
    assert {k: round(v * 1e3) for k, v in got.items()} == {
        "rank_gather": 10, "rank_sort": 20, "rank_pairs": 30,
        "rank_accumulate": 5, "gradients": 1, "ndcg_sort": 8, "valid_metric": 2}


def test_a_program_without_the_counters_is_refused_at_once(monkeypatch):
    driver = load_module("drivers", "train_jobs_rank")
    cells.assert_refused_without(monkeypatch, "train_jobs_rank", *driver.NEEDS)


# ------------------------------------------------------------- the rehearsal
def test_the_cell_rehearses_on_the_cpu():
    """``run.py --rehearse-cpu``: the whole run's control flow at 100,352
    docs: the fused scan, the driver's path check, nothing compiled inside
    the window, the reference through the comparison.  In a process of its
    own, as the other cells' rehearsals are: ``run.py`` sets the
    persistent compile cache to keep every program, however small, and a
    test process that took that setting over would fill ``tests/.jax_cache``
    with entries that the AOT store's tests then trip over."""
    lines = cells.rehearse(CELL, 1, limit_s=900)   # 216 s in the driver's PR 47 run
    window = next(ln["window"] for ln in lines if "window" in ln)
    setup = next(ln for ln in lines if "setup_s" in ln)
    assert setup["path"]["rank_queries"] == 318
    assert setup["path"]["rank_docs"] == 100_352
    assert setup["path"]["rank_bucket_count"] >= 4
    assert all(len(v) == window["rounds"] for v in setup["valid_ndcg"].values())
    cells.assert_compared_within_limits(lines, (
        "leaf_count_mismatch", "leaf_value_gap_median", "leaf_value_gap_p99",
        "train_score_gap", "valid_ndcg_gap"))


# ------------------------------------- 220 columns: two byte-plane groups
def test_one_tree_at_220_columns_through_the_two_group_compaction():
    """F = 220 gives 55 packed words and 64 payload rows: two plane groups
    in ``compact_payload_pallas``, which no other cell reaches.  The tree
    grown through the kernels (interpret mode) is the tree the sorted
    gather grows (integer gradients: every sum exact)."""
    rng = np.random.default_rng(2)
    n, f = 3000, 220
    bins = jnp.asarray(rng.integers(0, 255, size=(n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.integers(-2, 3, size=n).astype(np.float32))
    hess = jnp.asarray(rng.integers(1, 5, size=n).astype(np.float32))
    args = (bins, grad, hess, None, jnp.full((f,), 256, jnp.int32),
            jnp.full((f,), -1, jnp.int32), jnp.zeros((f,), bool), None,
            SplitHyper(num_leaves=15, min_data_in_leaf=5, n_bins=256,
                       hist_dtype="float32"))
    t0, lor0 = grow_tree_batched.__wrapped__(*args, batch=4)
    H._PAYLOAD_TEST_INTERPRET = True
    RF._FUSE_TEST_INTERPRET = True
    try:
        t1, lor1 = grow_tree_batched.__wrapped__(*args, batch=4)
    finally:
        H._PAYLOAD_TEST_INTERPRET = False
        RF._FUSE_TEST_INTERPRET = False
    for name in ("split_feature", "split_bin", "leaf_value", "leaf_count"):
        np.testing.assert_array_equal(np.asarray(getattr(t0, name)),
                                      np.asarray(getattr(t1, name)))
    np.testing.assert_array_equal(np.asarray(lor0), np.asarray(lor1))
