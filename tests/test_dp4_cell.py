"""The four-chip data-parallel job of the benchmark's cell
``criteo-dp4-train`` (configuration ``criteo-host4``), at a small size on
four of the CPU's virtual devices: against the plain reference through
the cell's own comparison and limits, against ``tree_learner=serial``
tree for tree, with one shard's histogram left out of the sum, and what
the per-iteration loop counts and names for the cell's ``dp_*``
metrics."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import cells
import lightgbm_tpu as lgb
from cells import BENCH, REPO
from lightgbm_tpu.obs.metrics import COUNTERS, global_metrics
from lightgbm_tpu.parallel.mesh import device_window
from test_tracing_scopes import _brute_force_rows

CELL = "criteo-dp4-train"
ROWS, VALID_ROWS, ROUNDS, CHIPS = 20000, 2000, 3, 4
# the batched int8 learner auto mode picks at the cell's size, asked for
# by name at this one (auto mode engages from 100,000 rows)
SMALL = {"num_leaves": 31, "tpu_split_batch": 8, "tpu_hist_dtype": "int8",
         "use_quantized_grad": True, "quant_train_renew_leaf": True,
         "min_data_in_leaf": 20}


def _cell():
    """The cell's configuration at rehearsal size, cut further: fewer
    rows and leaves, the floor under the AUC to match.  Limits as they
    stand."""
    return cells.find(CELL, rows=ROWS, valid_rows=VALID_ROWS, params=SMALL,
                      compare={"block_rows": 8192, "split_nodes": 8,
                               "auc_floor": {"round": ROUNDS, "auc": 0.66}})


def _job(cfg, data, tree_learner, callbacks=()):
    """One job over four of the CPU's devices: the booster and what it
    recorded of the valid set a round."""
    (_, xt64, y), (_, xv64, yv) = data
    cfg = dict(cfg, params={**cfg["params"], "tree_learner": tree_learner})
    sets = cells.program.construct(lgb, cfg["params"], (xt64, y), (xv64, yv))
    with device_window(CHIPS):
        return cells.train(cfg, sets, ROUNDS, callbacks=callbacks)


def _judged(cfg, data, bst, series):
    return cells.judged(cfg, cells.inputs(data), cells.answers(bst, series))


@pytest.fixture(scope="module")
def cell():
    return _cell()[1]


@pytest.fixture(scope="module")
def data(cell):
    return cells.data(cell)


@pytest.fixture(scope="module")
def data_job(cell, data):
    before = {c: global_metrics.counter(c) for c in
              ("sharded_rounds", "collective_bytes")}
    bst, aucs = _job(cell, data, "data")
    moved = {c: global_metrics.counter(c) - v for c, v in before.items()}
    return bst, aucs, moved


def test_the_manifest_names_the_cell_and_its_six_metrics():
    manifest = cells.bench.find_cell(CELL)[0]
    cell, cfg = _cell()
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("criteo-host4", "train-jobs-dp", 4)
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == ["criteo-dp4-train"]]
    # PR 30's six, and since PR 40 the job's start
    assert sorted(m["name"] for m in mine) == [
        "dp_between_programs_ms", "dp_chip_skew_ms", "dp_collective_ms",
        "dp_device_idle_share", "dp_hist_ms", "dp_job_start_ms",
        "dp_valid_eval_ms"]
    assert all(m["moves"] == "train_round_ms" and os.path.exists(
        os.path.join(BENCH, "layers", m["name"] + ".py")) for m in mine)
    with open(os.path.join(BENCH, "configs", "criteo-share.json")) as fh:
        share = json.load(fh)
    with open(os.path.join(BENCH, "configs", "criteo-host4.json")) as fh:
        host = json.load(fh)
    # four chips' shares of the same job, under the same limits
    assert host["rows"] == 4 * share["rows"] == 4 * host["rows_per_chip"]
    assert host["valid_rows"] == 4 * share["valid_rows"]
    assert host["limits"] == share["limits"]
    assert host["params"] == {**share["params"], "tree_learner": "data"}
    assert host["rows"] * host["hosts_sharing_the_rows"] \
        == host["published_rows"]


def test_the_job_ran_over_four_devices_in_the_per_iteration_loop(data_job):
    gb = data_job[0]._gbdt
    assert gb.parallel_mode == "data" and gb.mesh.devices.size == CHIPS
    assert not gb.supports_fused()
    for a in (gb.bins, gb.scores, gb.objective._sign):
        shards = {s.device: s.data.shape[0] for s in a.addressable_shards}
        assert len(shards) == CHIPS and set(shards.values()) == {ROWS // CHIPS}
    # the valid set whole on every device, placed once
    for a in (gb._valid_bins[0], gb._valid_bins_t[0], gb.valid_scores[0]):
        assert len(a.devices()) == CHIPS and a.is_fully_replicated
    # the mirror is transposed on the devices from the placed bins, into
    # the placement the loop's programs compiled for
    assert gb._valid_bins_t[0].sharding == gb._valid_bins[0].sharding
    assert gb._valid_bins_t[0].committed
    c = gb.metrics.counter
    assert c("sharded_rounds") == c("strict_rounds") == ROUNDS
    assert c("fused_rounds") == 0


def test_data_over_four_devices_agrees_with_the_plain_reference(
        cell, data, data_job):
    bst, aucs, _ = data_job
    correct, compared = _judged(cell, data, bst, aucs)
    assert correct, compared
    assert compared["leaf_count_mismatch"]["value"] == 0


def test_data_over_four_devices_grows_the_serial_trees(cell, data, data_job):
    serial, aucs_s = _job(cell, data, "serial")
    assert serial._gbdt.parallel_mode is None
    bst, aucs, _ = data_job
    assert len(bst._gbdt.models) == len(serial._gbdt.models) == ROUNDS
    for a, b in zip(bst._gbdt.models, serial._gbdt.models):
        ni = a.num_leaves - 1
        assert a.num_leaves == b.num_leaves
        assert np.array_equal(a.split_feature[:ni], b.split_feature[:ni])
        assert np.array_equal(a.threshold[:ni], b.threshold[:ni])
        assert np.array_equal(a.leaf_count[:a.num_leaves],
                              b.leaf_count[:b.num_leaves])
    np.testing.assert_allclose(aucs["auc"], aucs_s["auc"], atol=1e-6)


def test_a_shard_left_out_of_the_sum_is_not_correct(cell, data):
    import faults_dp
    with cells.planted(faults_dp.hist_drop_shard, shard=1):
        correct, compared = _judged(cell, data, *_job(cell, data, "data"))
    assert not correct
    over = [k for k in ("leaf_count_mismatch", "split_regret_mean")
            if compared[k]["value"] > compared[k]["limit"]]
    assert over, compared


# ------------------------------------------- what the loop counts and names
def test_the_loop_counts_rounds_payload_and_rows(data, data_job):
    bst, _, moved = data_job
    gb = bst._gbdt
    assert {"sharded_rounds", "collective_bytes"} <= set(COUNTERS)
    assert moved["sharded_rounds"] == ROUNDS
    assert moved["collective_bytes"] == gb.metrics.counter("collective_bytes")
    # every tree is full here: the payload of a full tree, every round
    assert all(t.num_leaves == 31 for t in gb.models)
    assert gb.metrics.counter("collective_bytes") \
        == ROUNDS * gb._collective_bytes_per_tree()
    assert gb.metrics.counter("hist_rows_selected") \
        == _brute_force_rows(bst, data[0][1].T)


def test_collective_payload_is_the_operand_the_program_reduces(data_job):
    """``_collective_bytes_per_tree`` against the compiled sharded tree
    program: every all-reduced f32 histogram operand of one pass."""
    import chip_smoke
    gb = data_job[0]._gbdt
    with device_window(CHIPS):
        text = chip_smoke._sharded_program_text(gb)
    assert "hist_allreduce" in text and "stats_allreduce" in text
    f, b = gb.bins.shape[1], gb.hp.n_bins
    per_pass = {}           # scope -> bytes of histogram operands reduced
    for m in re.finditer(r"= \(?([^=]*?)\)? all-reduce(?:-start)?\(.*?"
                         r'op_name="([^"]*)"', text):
        shapes = re.findall(r"f32\[([\d,]+)\]", m.group(1))
        hist = sum(4 * int(np.prod([int(x) for x in s.split(",")]))
                   for s in shapes if s.endswith(f"{b},4"))
        if hist:
            where = "root" if "tree_root" in m.group(2) else "round"
            per_pass[where] = per_pass.get(where, 0) + hist
    k = int(gb.config.tpu_split_batch)
    assert per_pass == {"root": f * b * 4 * 4, "round": k * f * b * 4 * 4}
    # 30 splits in passes of 8 leaves (no warm-up ladder at this size)
    assert gb._pass_widths(30) == [8, 8, 8, 8]
    assert gb._collective_bytes_per_tree() \
        == per_pass["root"] + 4 * per_pass["round"]
    assert gb._collective_bytes_per_tree(1) \
        == per_pass["root"] + per_pass["round"]


def test_a_round_of_the_loop_is_covered_by_spans(cell, data, tmp_path):
    """``score_update`` and ``valid_eval`` beside the spans the loop
    had: between ``iteration``'s start and end nothing long is left
    unnamed."""
    path = tmp_path / "trace.json"
    params = {**cell["params"], "trace_output": str(path)}
    _job(dict(cell, params=params), data, "data")
    events = json.loads(path.read_text())
    events = events["traceEvents"] if isinstance(events, dict) else events
    by_name = {}
    for e in events:
        if e.get("ph") == "X":
            by_name.setdefault(e["name"], []).append(e)
    for name in ("boosting_gradients", "quantize", "tree_growth",
                 "score_update", "valid_eval", "tree_finalize",
                 "metric_eval", "iteration"):
        assert len(by_name.get(name, [])) == ROUNDS, name
    inner = ("boosting_gradients", "quantize", "tree_growth", "score_update",
             "valid_eval", "tree_finalize", "metric_eval")
    for it in by_name["iteration"]:
        a, b = it["ts"], it["ts"] + it["dur"]
        covered = sum(e["dur"] for n in inner for e in by_name[n]
                      if a <= e["ts"] and e["ts"] + e["dur"] <= b + 1)
        assert covered >= 0.9 * it["dur"], (covered, it["dur"])


def test_leaf_counts_are_recounted_where_f32_sums_round(cell, data):
    """From 2**24 rows on the counts a tree carries can round; the
    recount from the rows' leaves, forced here at a small size, states
    the same (exact) counts and changes nothing else."""
    from lightgbm_tpu.learner import batch_grower
    base, _ = _job(cell, data, "data")
    with cells.planted(lambda setattr_: setattr_(batch_grower,
                                                 "_F32_EXACT_ROWS", 1)):
        bst, _ = _job(cell, data, "data")
        with device_window(CHIPS):
            import chip_smoke
            text = chip_smoke._sharded_program_text(bst._gbdt)
    assert "leaf_recount" in text
    X = data[0][1].T
    for a, b in zip(bst._gbdt.models, base._gbdt.models):
        assert np.array_equal(a.leaf_count, b.leaf_count)
        assert np.array_equal(a.threshold, b.threshold)
        assert np.array_equal(
            a.leaf_count[:a.num_leaves],
            np.bincount(a.predict_leaf_index(X), minlength=a.num_leaves))


def test_a_second_job_compiles_no_gradient_program(cell, data):
    """The binary objective's gradients are one program a shape, the
    rows' signs an argument: a new booster on the same rows finds it."""
    from lightgbm_tpu.objectives import _binary_gradients_jit
    _job(cell, data, "data")
    size = _binary_gradients_jit._cache_size()
    assert size > 0
    _job(cell, data, "data")
    assert _binary_gradients_jit._cache_size() == size


# ------------------------------------------------ the loop, program by program
_COUNT_PROGRAMS = """
import json, os, sys
sys.path.insert(0, os.path.join({repo!r}, "tests"))
import conftest  # noqa: F401  (the CPU's eight devices, the compile cache)
import jax
from jax._src import pjit
from jax._src.interpreters import pxla
import test_dp4_cell as T

cfg = T._cell()[1]
data = T.cells.data(cfg)
T._job(cfg, data, "data")              # everything compiled
# every execution through Python: no C++ fast path, its entries dropped
calls = [0]
execute = pxla.ExecuteReplicated.__call__
def counted(self, *args):
    calls[0] += 1
    return execute(self, *args)
pxla.ExecuteReplicated.__call__ = counted
pjit._get_fastpath_data = lambda *a, **k: None
jax.clear_caches()
marks = []
def after_round(env):
    marks.append(calls[0])
after_round.order = 95
(xt32, xt64, y), (xv32, xv64, yv) = data
params = dict(cfg["params"], tree_learner="data")
ds = T.lgb.Dataset(xt64.T, label=y, params=params).construct()
dv = ds.create_valid(xv64.T, label=yv).construct()
before = sorted(vars(dv)), sorted(vars(ds))
with T.device_window(T.CHIPS):
    T.lgb.train(params, ds, num_boost_round=T.ROUNDS, valid_sets=[dv],
                callbacks=[after_round])
print(json.dumps({{"marks": marks,
                  "same_attributes":
                  before == (sorted(vars(dv)), sorted(vars(ds)))}}))
"""


def test_the_loop_runs_the_parents_programs_and_leaves_the_dataset_alone():
    """PR 38's rule for the four-chip cell, pinned: a ``tree_learner=data``
    binary job with a valid set executes as many programs as at the
    parent of the PR that brought the ranking cell (a645479, counted
    there with this same script: 83 up to the end of the first round,
    booster and ``add_valid`` included, then 74 a round on the CPU's mesh
    with this container's JAX; the chip's loop runs 63), and the booster
    puts no attribute on either ``Dataset`` (no memoised transpose: the
    refused PR 37's ``_bins_t_host``).  Since PR 39 the first mark is 84:
    ``add_valid`` executes ONE program more a valid set, the transpose of
    the placed bins on the devices (``_valid_mirror_program``), where the
    parent transposed on the host and placed the result (a placement is
    no execution and was never counted here); the rounds keep their 74."""
    out = subprocess.run(
        [sys.executable, "-c", _COUNT_PROGRAMS.format(repo=REPO)],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.splitlines()[-1])
    assert got["same_attributes"]
    assert got["marks"] == [84, 158, 232], got


# ----------------------------------------------------- the cell's rehearsal
def test_the_cell_rehearses_on_four_cpu_devices():
    """``benchmark/run.py --workload criteo-dp4-train --rehearse-cpu``:
    the cell's whole control flow (driver, path check, nothing compiled
    inside the window, comparison) at 100,000 rows, where auto mode
    still picks K=42 and int8; it can never print a result line."""
    lines = cells.rehearse(
        CELL, 3000000019,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    setup = next(ln for ln in lines if "setup_phases_s" in ln)
    assert setup["path"] == {"tpu_split_batch": 42, "hist_dtype": "int8",
                             "packed_mirror": False, "device_n_bins": 256,
                             "parallel_mode": "data", "mesh_devices": 4}
    assert lines[-1]["correct"] is True, lines[-1]["compared"]


def test_a_program_without_the_counters_is_refused_at_once(monkeypatch):
    cells.assert_refused_without(monkeypatch, "train_jobs_dp", "sharded_rounds")
