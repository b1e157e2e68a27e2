"""The wide dense job of the benchmark's cell ``epsilon-train``
(configuration ``epsilon-dense``: upstream's Epsilon job, 400,000 x 2,000
dense columns), at a few thousand rows on the CPU: ONE job at all 2,000
columns against the plain reference through the cell's own comparison,
with its bfloat16 control and what it counts for the cell's readers; the
planted faults of ``tools/faults_wide.py`` each failing a limit, at 300
columns (ten column blocks); what the manifest names, the byte-count
functions of ``harness/wide_bytes.py`` against a hand reckoning, and the
cell's rehearsal through ``benchmark/run.py``."""

import json
import os

import jax.numpy as jnp
import pytest

import cells
import lightgbm_tpu as lgb
from cells import BENCH
from lightgbm_tpu.obs.metrics import COUNTERS
from lightgbm_tpu.ops import hist_pallas

CELL = "epsilon-train"
ROWS, ROUNDS = 4096, 3
#: columns of the jobs that need column blocks, not the cell's 63 of them:
#: ten blocks of 32 under the real budget, the last with 12 columns
NARROW = 300
METRICS = ("wide_hist_ms", "wide_hist_roofline_share", "wide_find_splits_ms",
           "wide_partition_ms", "wide_device_idle_share",
           "wide_construct_bin_mappers_s")


def _sized(features, rows=ROWS):
    """The cell at ``rows`` rows and ``features`` columns: its
    configuration, the comparison's inputs and the constructed sets."""
    # a stronger signal than the cell's: 4,096 rows have to show a planted
    # fault that the cell shows on 400,000
    cfg = cells.find(CELL, rows=rows, valid_rows=rows // 4, features=features,
                     data={"separation": 3.0},
                     compare={"split_trees": ROUNDS,
                              "auc_floor": {"round": ROUNDS, "auc": 0.6}})[1]
    data = cells.data(cfg)
    (_, xt64, y), (_, xv64, yv) = data
    sets = cells.program.construct(lgb, cfg["params"], (xt64, y), (xv64, yv))
    return cfg, cells.inputs(data), sets


def _job(cfg, sets):
    """One job at the test's size, its partition in the fused kernel
    (interpret mode), as on the chip: the booster and its answers."""
    bst, series = cells.train(cfg, sets, ROUNDS, interpret_partition=True)
    return bst, cells.answers(bst, series)


@pytest.fixture(scope="module")
def wide():
    """The file's ONE job at the cell's own 2,000 columns, on 2,048 rows
    (its counters, the reference and the bfloat16 control read it).  On the
    CPU every histogram of it holds rows x 2,000 x 256 floats of one-hot
    at once (``ops/histogram.py build_histogram`` unrolls the columns'
    chunks and XLA:CPU shares no buffer between them): 4.2 GB a call here,
    8.4 GB at 4,096 rows, and a process's first touch of that much memory
    costs minutes of system time.  What needs column BLOCKS and not this
    width runs on ``narrow``."""
    cfg, inputs, sets = _sized(2000, rows=2048)
    cells.program.free_everything()
    return (cfg, inputs, *_job(cfg, sets))


@pytest.fixture(scope="module")
def narrow():
    return _sized(NARROW)


# ------------------------------------------------------------------ manifest
def test_the_manifest_names_the_cell_and_its_metrics():
    bench = cells.bench
    manifest, cell, cfg, traffic = bench.find_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("epsilon-dense", "train-jobs-wide", 1)
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == ["epsilon-train"]]
    assert sorted(m["name"] for m in mine) == sorted(METRICS)
    assert all(os.path.exists(os.path.join(BENCH, "layers", m["name"] + ".py"))
               for m in mine)
    assert {m["name"]: m["moves"] for m in mine} == {
        **{n: "train_round_ms" for n in METRICS},
        "wide_construct_bin_mappers_s": "setup_s"}
    # nothing an accepted metric had is touched: no list but the cell's own
    assert not any("epsilon-train" in m.get("workloads", ())
                   for m in manifest["per_layer"] if m not in mine)
    assert len(manifest["per_layer"]) <= 128
    # the published job, nothing cut
    assert (cfg["rows"], cfg["valid_rows"], cfg["features"], cfg["reduced"]) \
        == (400000, 100000, 2000, [])
    assert cfg["params"] == {
        "objective": "binary", "metric": "auc", "num_leaves": 255,
        "max_bin": 255, "learning_rate": 0.1, "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 100, "tree_learner": "serial",
        "verbose": -1}
    assert (cfg["reference"], cfg["comparison"]) == ("gbdt_plain",
                                                     "gbdt_binary")
    entry = next(c for c in manifest["configs"] if c["name"] == "epsilon-dense")
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert len(entry["why"]) <= 200
    assert len(cell["why"]) <= 200
    # train-jobs' traffic to the letter, through the driver that refuses a
    # program without the column-block counters before any data is made
    with open(os.path.join(BENCH, "traffic", "train-jobs.json")) as fh:
        accepted = json.load(fh)
    assert {k: v for k, v in bench.load_json(
        "traffic", "train-jobs-wide.json").items()
        if k not in ("driver", "what")} == {
            k: v for k, v in accepted.items() if k not in ("driver", "what")}
    assert traffic["driver"] == "train_jobs_wide"
    assert traffic["num_boost_round"] == 1016 and traffic["dispatch_rounds"] == 8
    assert sum(1 for w in manifest["workloads"] if w["chips"] == 4) == 1
    # a block of the reference holds 2,000 float columns in half a gigabyte
    assert cfg["compare"]["block_rows"] * 2000 * 4 <= 1 << 29


# ------------------------------------------------- what the program counts
def test_the_program_counts_what_the_readers_read(wide):
    cell, _, bst, _ = wide
    gb = bst._gbdt
    assert {"hist_col_blocks", "hist_state_bytes", "hist_vmem_budget_bytes",
            "construct_bin_mappers_s"} <= set(COUNTERS)
    K = int(gb.config.tpu_split_batch)
    assert gb.metrics.counter("hist_col_blocks") == \
        hist_pallas.pass_col_blocks(2000, K, 256, "int8") > 1
    assert gb.metrics.counter("hist_state_bytes") == \
        int(cell["params"]["num_leaves"]) * 2000 * 256 * 4 * 4
    assert gb.metrics.counter("hist_vmem_budget_bytes") == \
        hist_pallas.VMEM_BUDGET_BYTES
    assert gb.metrics.counter("fused_rounds") == ROUNDS
    # at the cell's own shapes: 63 column blocks, a 2.09 GB state
    assert hist_pallas.pass_col_blocks(2000, 42, 256, "int8") == 63
    assert 255 * 2000 * 256 * 4 * 4 == 2_088_960_000


def test_the_dispatch_span_carries_the_histograms_built(monkeypatch, narrow):
    """``dispatch_done`` says how many leaves' histograms its rows went
    into (a root's and one child's a split): what the roofline share's
    bytes are counted from."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    seen = []
    real = GBDT._phase

    def spy(self, name, **counts):
        if name == "dispatch_done":
            seen.append(counts)
        return real(self, name, **counts)
    monkeypatch.setattr(GBDT, "_phase", spy)
    bst, _ = _job(narrow[0], narrow[2])
    leaves = sum(t.num_leaves for t in bst._gbdt.models)
    assert sum(c["hist_leaves_built"] for c in seen) == leaves
    assert sum(c["hist_rows_selected"] for c in seen) >= ROUNDS * ROWS


def test_the_byte_counts_are_the_hand_reckoned_ones():
    """One K = 42 pass over half of 400,000 rows of 2,000 columns into 42
    leaves: 200,000 x (2,000 + 12) bytes in, 42 x 2,000 x 256 x 3 x 4 out."""
    from harness import wide_bytes
    assert wide_bytes.pass_bytes(200_000, 2000, 256, 42) == \
        402_400_000 + 258_048_000
    assert wide_bytes.onehot_ops(200_000, 2000, 256, 42) == \
        2 * 200_000 * 2000 * 256 * 126
    assert wide_bytes.roofline_s(819_000_000, "TPU v5 lite") == \
        pytest.approx(1e-3)
    with pytest.raises(KeyError):
        wide_bytes.roofline_s(1, "cpu")


def test_the_readers_reduce_a_window_as_reckoned_by_hand(monkeypatch, capsys):
    """``harness/wide_trace.py`` on the numbers of the cell's traced chip
    run (PR 45, seed 4500000016): all histogram work a round, and the
    roofline share from the window's counts; nothing to read, nothing
    returned."""
    import jax
    from harness import scoped, wide_trace
    red = {"scope_s": {"hist_kernel": 21.458155, "hist_compact": 0.963916,
                       "hist_update": 8.351034},
           "round_hist_s": {"hist_kernel": 20.055889, "hist_compact": 0.888214,
                            "hist_update": 8.351034}}
    run = {"rounds": 32, "trace": {"scope_s": {"round_hist": 29.295137}}}
    monkeypatch.setattr(scoped, "of_this_run", lambda: red)
    assert wide_trace.hist_ms_per_round(run) == pytest.approx(961.66, abs=0.01)
    monkeypatch.setattr(wide_trace, "_window_counts", lambda: {
        "hist_rows_selected": 52952796, "hist_leaves_built": 8160,
        "trees": 32})
    monkeypatch.setattr(scoped, "workload_of", lambda argv=None: "epsilon-train")

    class Chip:
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    assert wide_trace.roofline_share(run) == pytest.approx(0.8532, abs=1e-3)
    said = json.loads(capsys.readouterr().err.split("wide_hist: ")[1])
    assert said["necessary_bytes"] == 156676065552
    assert said["onehot_ops"] == 5219995951104000
    monkeypatch.setattr(wide_trace, "_window_counts", lambda: None)
    assert wide_trace.roofline_share(run) is None
    monkeypatch.setattr(scoped, "of_this_run", lambda: None)
    assert wide_trace.hist_ms_per_round(run) is None


# ------------------------------------------------------- against the reference
def test_the_program_agrees_with_the_plain_reference(wide):
    cell, inputs, _, answers = wide
    correct, compared = cells.judged(cell, inputs, answers)
    assert correct, compared
    assert compared["leaf_count_mismatch"]["value"] == 0


def test_the_bfloat16_control_is_not_correct(wide):
    cell, inputs, _, answers = wide
    ref = cells.load_module("reference", cell["reference"])
    ctrl = cells.load_module("comparisons", cell["comparison"]).control_answers(
        ref, cell, answers, inputs, jnp.bfloat16)
    correct, compared = cells.judged(cell, inputs, ctrl)
    assert not correct
    assert compared["leaf_value_gap_median"]["value"] > \
        compared["leaf_value_gap_median"]["limit"]


FAULTS = {"drop_col_block": "split_regret_mean",
          "shift_col_block": "leaf_count_mismatch",
          "skip_state_update": "leaf_count_mismatch"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(narrow, fault):
    """The faults of ``tools/faults_wide.py`` each fail the limit that
    holds what they break: a dropped column block states splits the raw
    rows do not bear out (the block of the data's heaviest column); a
    block written one column off and a state slot never written put rows
    where the stated counts do not.  A block in the middle needs three
    blocks, not the cell's 63."""
    import faults_wide
    cell, inputs, sets = narrow
    gen = cells.load_module("datagen", cell["data"]["generator"])
    target = gen.strongest_feature(cell["data"], NARROW)
    cb, ncb = faults_wide._blocks(NARROW)
    assert ncb > 1 and 0 < target // cb < ncb - 1   # a block in the middle
    with cells.planted(faults_wide.WIDE[fault], feature=target):
        correct, compared = cells.judged(cell, inputs, _job(cell, sets)[1])
    assert not correct
    held = compared[FAULTS[fault]]
    assert not held["value"] <= held["limit"], compared


# ----------------------------------------------------- the cell's rehearsal
def test_the_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --workload epsilon-train --rehearse-cpu``: the
    cell's whole control flow (generator, construct, the driver's path
    check, nothing compiled inside the window, the reference and the
    comparison) at 4,096 rows x 2,000 columns with the batched grower and
    int8 histograms asked for by name; it can never print a result
    line."""
    lines = cells.rehearse(CELL, 4500000019)
    path = next(ln for ln in lines if "setup_phases_s" in ln)["path"]
    assert path == {"tpu_split_batch": 8, "hist_dtype": "int8",
                    "packed_mirror": False, "device_n_bins": 256,
                    "hist_col_blocks": 63,
                    "hist_state_bytes": 15 * 2000 * 256 * 16,
                    "hist_vmem_budget_bytes": hist_pallas.VMEM_BUDGET_BYTES}
    assert lines[-1]["correct"], lines[-1]["compared"]


def test_a_program_without_the_counters_is_refused_at_once(monkeypatch):
    """The parent of this cell's PR, whose round program at this width
    does not finish compiling: refused before any data is made."""
    cells.assert_refused_without(monkeypatch, "train_jobs_wide",
                                 "hist_col_blocks")
