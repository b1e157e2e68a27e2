"""The native-categorical job of the benchmark's cell ``allstate-cat-train``
(configuration ``allstate-categorical``), at small seeded sizes on the
CPU: the generator against ``onehot_schema``'s rows, the program against
the plain reference through the cell's own comparison, the planted
faults of ``tools/faults_cat.py`` each failing a limit, what the program
counts and names for the cell's ``cat_*`` metrics, the cell's rehearsal
through ``benchmark/run.py`` and the driver's refusal of a program
without the counters."""

import json
import os
import re

import jax
import numpy as np
import pytest

import cells
import lightgbm_tpu as lgb
from cells import BENCH
from lightgbm_tpu.obs.metrics import COUNTERS, global_metrics
from lightgbm_tpu.ops import round_fuse
from lightgbm_tpu.utils.timer import global_timer

CELL = "allstate-cat-train"
# two columns with more levels than a column has bins for, the four
# smallest of the published schema (2, 3, 3, 4: the one-hot variant)
LEVELS = [40, 300, 600, 2, 3, 3, 4, 9, 24]
ROWS, VALID_ROWS, ROUNDS = 30000, 4000, 4
COUNTS = ("cat_features", "cat_subset_features", "cat_levels_kept",
          "cat_other_rows", "cat_splits", "cat_subset_splits",
          "cat_left_levels", "fused_partition_declined")


@pytest.fixture(scope="module")
def cell():
    return cells.find(
        CELL, rows=ROWS, valid_rows=VALID_ROWS, features=15 + len(LEVELS),
        data={"levels": LEVELS, "pos_rate": 0.2, "logit_sd": 1.5},
        params={"num_leaves": 15, "min_sum_hessian_in_leaf": 5.0,
                "min_data_per_group": 50,
                # the cell's path: under 100,000 rows auto mode picks the
                # strict grower and float32 histograms, whose leaves are
                # not renewed from full gradients (a subset split's
                # children then carry cat_l2)
                "tpu_split_batch": 8, "tpu_hist_dtype": "int8",
                "use_quantized_grad": True, "quant_train_renew_leaf": True},
        categorical={"columns": list(range(15, 15 + len(LEVELS))),
                     "cat_features": len(LEVELS), "cat_subset_features": 5},
        compare={"block_rows": 8192, "split_nodes": 8, "split_min_share": 0.05,
                 "split_trees": ROUNDS,
                 "auc_floor": {"round": ROUNDS, "auc": 0.66}})[1]


@pytest.fixture(scope="module")
def data(cell):
    return cells.data(cell)


@pytest.fixture(scope="module")
def inputs(data):
    return cells.inputs(data)


def _construct(cell, data):
    (_, xt64, y), (_, xv64, yv) = data
    ds = lgb.Dataset(xt64.T, label=y, params=cell["params"],
                     categorical_feature=cell["categorical"]["columns"]) \
        .construct()
    return ds, ds.create_valid(xv64.T, label=yv).construct()


def _job(cell, data, sets=None):
    """One job of the cell at the test's size, its partition in the fused
    kernel (interpret mode), as on the chip."""
    return cells.train(cell, sets or _construct(cell, data), ROUNDS,
                       interpret_partition=True)


def _judged(cell, inputs, bst, series):
    driver = cells.load_module("drivers", "train_jobs_cat")
    return cells.judged(cell, inputs,
                        cells.answers(bst, series, driver.plain_trees))


@pytest.fixture(scope="module")
def job(cell, data):
    cells.program.free_everything()
    before = {c: global_metrics.counter(c) for c in COUNTS}
    global_timer.reset()
    global_timer.enable()       # a booster's start resets this table
    try:
        sets = _construct(cell, data)
        spans = global_timer.as_dict()
    finally:
        global_timer.disable()
    bst, aucs = _job(cell, data, sets)
    moved = {c: global_metrics.counter(c) - v for c, v in before.items()}
    return bst, aucs, moved, spans


# ------------------------------------------------------------------ manifest
def test_the_manifest_names_the_cell_and_its_metrics():
    manifest, cell, cfg, traffic = cells.bench.find_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("allstate-categorical", "train-jobs-cat", 1)
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == ["allstate-cat-train"]]
    same = ["find_splits_ms", "hist_ms", "hist_kernel_ms", "hist_compact_ms",
            "hist_fill_share", "partition_ms", "valid_score_ms",
            "score_update_ms", "gradients_ms", "quantize_ms", "tree_root_ms",
            "unscoped_device_ms", "unnamed_device_ms", "device_idle_share",
            "between_dispatch_ms", "job_start_ms", "construct_s", "compile_s",
            "lower_s", "compile_or_load_s",
            # the host before the device has work (PR 40's readers)
            "program_trace_s", "program_lowering_s",
            "program_backend_compile_s", "program_cache_load_s",
            "round_program_lower_s", "programs_lowered", "job_start_init_ms",
            "job_start_call_ms", "job_start_wait_ms",
            "construct_bin_mappers_s", "construct_bin_matrix_s"]
    new = ["cat_subset_search_ms", "cat_bitset_ms", "cat_split_share",
           "cat_left_levels_mean", "cat_other_row_share", "cat_bin_mappers_s"]
    assert sorted(m["name"] for m in mine) == sorted(
        new + ["cat_" + n for n in same])
    assert all(os.path.exists(os.path.join(BENCH, "layers", m["name"] + ".py"))
               for m in mine)
    # nothing an accepted metric had is touched: no list but the cell's own
    assert not any("allstate-cat-train" in m.get("workloads", ())
                   for m in manifest["per_layer"] if m not in mine)
    onehot = json.load(open(os.path.join(BENCH, "configs",
                                         "allstate-onehot.json")))
    # the published job, nothing cut, on allstate-onehot's rows
    assert (cfg["rows"], cfg["valid_rows"], cfg["features"], cfg["reduced"]) \
        == (13184290, 1000000, 32, [])
    assert cfg["data"] == dict(onehot["data"], generator="claims_codes")
    assert {k: cfg["params"][k] for k in onehot["params"]} == onehot["params"]
    assert {k: cfg["params"][k] for k in cfg["published"]["categorical_defaults"]} \
        == cfg["published"]["categorical_defaults"] == {
            "max_cat_threshold": 32, "cat_l2": 10.0, "cat_smooth": 10.0,
            "max_cat_to_onehot": 4, "min_data_per_group": 100}
    assert cfg["categorical"]["columns"] == list(range(15, 32))
    assert len(next(c for c in manifest["configs"]
                    if c["name"] == "allstate-categorical")["source"]) <= 200
    assert traffic["num_boost_round"] == 1016 and traffic["dispatch_rounds"] == 8
    assert sum(1 for w in manifest["workloads"] if w["chips"] == 4) == 1


# ----------------------------------------------------------------- generator
def test_the_generator_codes_the_levels_onehot_schema_draws(cell, data):
    """The same rows as ``onehot_schema``'s CSR: one-hot coding the codes
    back (through the fixed permutation) gives its indicator columns, its
    numeric columns and its labels, for both parts."""
    from harness import load_module
    gen = load_module("datagen", "claims_codes")
    schema = load_module("datagen", "onehot_schema")
    spec = cell["data"]
    first = 15 + np.concatenate([[0], np.cumsum(LEVELS)[:-1]])
    for part, (xt32, xt64, y) in enumerate(data):
        rows = xt64.shape[1]
        csr, y_csr = schema.make(spec, 0, part, rows, schema.columns(spec))
        np.testing.assert_array_equal(y, y_csr)
        dense = csr.toarray()
        np.testing.assert_array_equal(xt64[:15].T, dense[:, :15])
        np.testing.assert_array_equal(xt32, xt64.astype(np.float32))
        for k, levels in enumerate(LEVELS):
            code = xt64[15 + k].astype(np.int64)
            perm = gen.code_of_level(spec, k)
            assert sorted(perm) == list(range(levels))
            level = np.argsort(perm)[code]       # the permutation undone
            block = dense[:, first[k]:first[k] + levels]
            np.testing.assert_array_equal(block.argmax(1), level)
            assert (block.sum(1) == 1).all()
    # codes are neither in frequency order nor all in place
    assert (gen.code_of_level(spec, 2) != np.arange(600)).any()


# ------------------------------------------------------- counters and scopes
def test_the_program_counts_and_names_what_the_cell_reads(cell, job):
    bst, _, moved, spans = job
    gb = bst._gbdt
    assert set(COUNTS) | {"cat_bin_mappers_s"} <= set(COUNTERS)
    mappers = [gb.train_set.mappers[j] for j in cell["categorical"]["columns"]]
    kept = [len(m.bin_2_categorical) for m in mappers]
    assert kept == [40, 254, 254, 2, 3, 3, 4, 9, 24]
    assert [m.other_bin for m in mappers] == [-1, 254, 254] + [-1] * 6
    other = sum(int((np.asarray(gb.train_set.bins)[:, j] == m.other_bin).sum())
                for j, m in zip(cell["categorical"]["columns"], mappers)
                if m.other_bin >= 0)
    assert other > 0
    trees = gb.models
    cat_nodes = sum(int((np.asarray(t.decision_type[:t.num_leaves - 1]) & 1)
                        .sum()) for t in trees)
    sets = [(t.split_feature[i], t.cat_threshold[int(t.cat_split_index[i])])
            for t in trees for i in range(t.num_leaves - 1)
            if t.decision_type[i] & 1]
    by_set = [s for f, s in sets if kept[f - 15] > 4]
    assert cat_nodes > 0 and by_set
    assert moved == {
        "cat_features": len(LEVELS), "cat_subset_features": 5,
        "cat_levels_kept": sum(kept), "cat_other_rows": other,
        "cat_splits": cat_nodes, "cat_subset_splits": len(by_set),
        "cat_left_levels": sum(len(s) for s in by_set),
        "fused_partition_declined": 0}
    assert {c: gb.metrics.counter(c) for c in COUNTS} == moved
    assert gb.hp.cat_subset_cols == (15, 16, 17, 22, 23)
    assert max(len(s) for s in by_set) <= 32
    # the span: once a categorical column in the mappers, once in each
    # set's bins
    assert spans["cat_bin_mappers"]["count"] == 3 * len(LEVELS)
    assert spans["cat_bin_mappers"]["total_s"] <= spans["construct"]["total_s"]
    # the scopes, by name, in the round program's text
    from lightgbm_tpu.learner import batch_grower
    round_fuse._FUSE_TEST_INTERPRET = True      # read when traced
    try:
        text = jax.jit(batch_grower.grow_tree_batched.__wrapped__,
                       static_argnames=("hp", "batch")).lower(
            gb.bins, jax.numpy.zeros(ROWS), jax.numpy.ones(ROWS), None,
            gb.num_bins_arr, gb.nan_bin_arr, gb.is_cat_arr, None, gb.hp,
            batch=8).compile().as_text()
    finally:
        round_fuse._FUSE_TEST_INTERPRET = False
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    from harness import cat_trace
    inner = {cat_trace.scope_of(p) for p in paths}
    assert {"cat_subset", "cat_bitset", "find_splits"} <= inner
    for scope in ("find_splits/vmap(cat_subset)/", "tree_root/cat_subset/",
                  "find_splits/vmap(cat_bitset)/",
                  "/partition/find_splits/cat_bitset/"):
        assert any(scope in p for p in paths), scope
    # the scan sorts; it gathers nothing
    under = [p for p in paths if cat_trace.scope_of(p) == "cat_subset"]
    assert any(p.endswith("sort") for p in under)
    assert not any("gather" in p for p in under)


# ---------------------------------------------------------- against the plain
def test_the_program_agrees_with_the_plain_reference(cell, inputs, job):
    bst, aucs = job[:2]
    correct, compared = _judged(cell, inputs, bst, aucs)
    assert compared["leaf_count_mismatch"]["value"] == 0
    assert correct, compared


def test_the_training_scores_are_the_returned_model_s(data, job):
    """What the job holds for its training rows is what the model it
    returns says of their raw values, a 600-level column among them."""
    bst = job[0]
    (_, xt64, _), _ = data
    held = np.asarray(bst._gbdt.scores)[:, 0]
    said = bst.predict(xt64.T, raw_score=True)
    np.testing.assert_allclose(held, said, rtol=0, atol=2e-6)


FAULTS = {"fold_unbinned_levels": "leaf_count_mismatch",
          "shift_left_sets": "leaf_count_mismatch",
          "widen_left_sets": "split_regret_mean"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(cell, data, inputs, fault):
    """The faults of ``tools/faults_cat.py`` that break what the job
    returns fail the limit that holds it, under the published limits:
    the parent's fold of unbinned levels and a left set shifted by a bin
    put rows where the stated model does not; a set of more levels than
    ``max_cat_threshold`` is one no scan could state."""
    import faults_cat
    with cells.planted(getattr(faults_cat, fault)):
        correct, compared = _judged(cell, inputs, *_job(cell, data))
    assert not correct
    assert compared[FAULTS[fault]]["value"] > compared[FAULTS[fault]]["limit"], \
        compared
    if fault == "widen_left_sets":
        assert not np.isfinite(compared["split_regret_mean"]["value"])
        assert compared["leaf_count_mismatch"]["value"] == 0
    else:
        assert compared["train_score_gap"]["value"] > \
            compared["train_score_gap"]["limit"]


@pytest.mark.parametrize("fault,pos_rate", [("drop_cat_l2", 0.2),
                                            ("skip_descending", 0.85)])
def test_a_fault_of_the_search_gives_gain_away(cell, fault, pos_rate):
    """The two faults that state true and allowed splits and only give
    gain away, read by ``split_regret_mean`` against the sound job's on
    the same rows, with float32 histograms (at 30,000 rows the int8
    gradients' noise reads 0.05 by itself): ``cat_l2`` left out ranks
    small sets too high; without the descending direction the scan
    cannot state "the levels with the FEWEST positives against the rest",
    which is where the gain lies once most rows are positive (at a fifth
    positive the ascending direction finds it all, and the fault reads
    as the sound job does)."""
    import faults_cat
    cell = dict(cell, data=dict(cell["data"], pos_rate=pos_rate),
                params={k: v for k, v in cell["params"].items()
                        if k not in ("tpu_hist_dtype", "use_quantized_grad",
                                     "quant_train_renew_leaf")})
    data = cells.data(cell)
    inputs = cells.inputs(data)
    cells.program.free_everything()
    sound = _judged(cell, inputs, *_job(cell, data))[1]
    with cells.planted(getattr(faults_cat, fault)):
        planted = _judged(cell, inputs, *_job(cell, data))[1]
    assert sound["split_regret_mean"]["value"] < 0.01
    assert planted["leaf_count_mismatch"]["value"] == 0
    limit = 3.0 * sound["split_regret_mean"]["value"]
    assert limit < planted["split_regret_mean"]["value"] < 1.0, (sound, planted)


# ----------------------------------------------------- the cell's rehearsal
def test_the_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --workload allstate-cat-train --rehearse-cpu``:
    the cell's whole control flow (generator, construct with its spans,
    the driver's path check, nothing compiled inside the window, the
    reference and comparison) at 120,000 rows, where auto mode still
    picks K=42 and int8; it can never print a result line.  ``correct`` is
    not asked for: the split search's regret means nothing at this size."""
    lines = cells.rehearse(CELL, 3000000019)
    setup = next(ln for ln in lines if "setup_phases_s" in ln)
    path = setup["path"]
    assert {k: path[k] for k in ("tpu_split_batch", "hist_dtype",
                                 "packed_mirror", "device_n_bins")} == {
        "tpu_split_batch": 42, "hist_dtype": "int8", "packed_mirror": False,
        "device_n_bins": 256}
    assert (path["cat_features"], path["cat_subset_features"],
            path["efb_bundles"]) == (17, 13, 0)
    assert path["cat_splits"] > 0 and path["cat_other_rows"] > 0
    assert set(setup["setup_spans_s"]) == {"construct", "dense_bin_mappers",
                                           "dense_bin_matrix",
                                           "cat_bin_mappers"}
    cells.assert_compared_within_limits(lines, (
        "leaf_count_mismatch", "leaf_value_gap_median", "train_score_gap",
        "valid_auc_gap"))


def test_a_program_without_the_counters_is_refused_at_once(monkeypatch):
    cells.assert_refused_without(monkeypatch, "train_jobs_cat",
                                 "fused_partition_declined")
