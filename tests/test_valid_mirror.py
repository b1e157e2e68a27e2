"""A valid set's transposed bins (``GBDT._valid_bins_t``: what the matmul
valid scorer and the fused round program read) are made on the device
from the placed ``[n, F]`` bins by one program a placement
(``boosting/gbdt.py`` ``_valid_mirror_program``), not on the host: the
array the parent's ``_place_whole(np.ascontiguousarray(bins.T))`` gave,
bit for bit and placement for placement, in a serial job, a
``tree_learner=data`` job on the CPU's mesh, a bundled job whose plan
states ranges and (``None``) a ``linear_tree`` job."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import gbdt as gbdt_mod
from lightgbm_tpu.obs import compile_events
from lightgbm_tpu.obs.metrics import COUNTERS, global_metrics
from test_efb import _onehot_data

ROUNDS = 4
FAST = {"num_leaves": 15, "learning_rate": 0.15, "min_data_in_leaf": 5,
        "verbose": -1}
CASES = {
    "serial": {**FAST, "objective": "binary", "metric": ["auc"]},
    "data": {**FAST, "objective": "binary", "metric": ["auc"],
             "tree_learner": "data"},
    "bundled": {**FAST, "objective": "binary", "metric": ["auc"],
                "enable_bundle": True},
    "linear_tree": {**FAST, "objective": "regression", "metric": ["l2"],
                    "linear_tree": True},
}
MIRRORED = {"serial": True, "data": True, "bundled": True,
            "linear_tree": False}


def _sets(case):
    """The case's training and valid ``Dataset``, constructed."""
    params = CASES[case]
    if case == "bundled":
        (x, y), (xv, yv) = _onehot_data(seed=5), _onehot_data(n=1000, seed=6)
    else:
        rng = np.random.default_rng(3)
        x = rng.normal(size=(3000, 12))
        y = x @ rng.normal(size=12) + rng.normal(size=3000)
        if params["objective"] == "binary":
            y = (y > 0).astype(np.float64)
        x, xv, y, yv = x[:2000], x[2000:], y[:2000], y[2000:]
    ds = lgb.Dataset(x, label=y, params=params).construct()
    return ds, ds.create_valid(xv, label=yv).construct()


def _train(case, sets):
    evals = {}
    bst = lgb.train(CASES[case], sets[0], num_boost_round=ROUNDS,
                    valid_sets=[sets[1]],
                    callbacks=[lgb.record_evaluation(evals)])
    (series,) = evals["valid_0"].values()
    return bst, series


@pytest.fixture(scope="module", params=list(CASES))
def job(request):
    case = request.param
    sets = _sets(case)
    return (case, sets) + _train(case, sets)


def _host_mirror_program(sharding):
    """The parent's formulation behind the program's call: the bins
    transposed by numpy and copied to the device a second time."""
    def mirror(bins):
        host = np.ascontiguousarray(np.asarray(bins).T)
        return jnp.asarray(host) if sharding is None \
            else jax.device_put(host, sharding)
    return mirror


def test_the_mirror_is_the_parents_array_made_on_the_device(job):
    case, _, bst, _ = job
    gb = bst._gbdt
    placed, mirror = gb._valid_bins[0], gb._valid_bins_t[0]
    bins = gb.valid_sets[0].bins
    assert gb._matmul_valid_ok() == MIRRORED[case]
    assert (case == "bundled") == (gb.bundle is not None
                                   and gb.bundle.search is not None)
    assert (case == "data") == (gb.mesh is not None)
    assert np.array_equal(np.asarray(placed), bins)
    c = gb.metrics.snapshot()["counters"]
    if not MIRRORED[case]:
        assert mirror is None
        assert "valid_mirror_device_bytes" not in c
        return
    # what the parent's line gave: placed whole as the [n, F] bins are
    want = gb._place_whole(np.ascontiguousarray(bins.T))
    assert np.array_equal(np.asarray(mirror), np.asarray(want))
    assert (mirror.shape, mirror.dtype) == (bins.shape[::-1], np.uint8)
    assert (mirror.committed, mirror.sharding) \
        == (want.committed, want.sharding) \
        == (placed.committed, placed.sharding)
    assert mirror.committed == (case == "data")
    if case == "data":
        assert mirror.is_fully_replicated
        assert len(mirror.devices()) == gb.mesh.devices.size > 1
    assert {"valid_mirror_device_bytes", "valid_mirror_host_bytes"} \
        <= set(COUNTERS)
    assert c["valid_mirror_device_bytes"] == bins.nbytes
    assert c["valid_mirror_host_bytes"] == 0


def test_the_span_lies_inside_the_boosters_construction(job, tmp_path):
    case, sets, _, _ = job
    path = tmp_path / "trace.json"
    lgb.train({**CASES[case], "trace_output": str(path)}, sets[0],
              num_boost_round=1, valid_sets=[sets[1]])
    events = json.loads(path.read_text())
    events = events["traceEvents"] if isinstance(events, dict) else events
    spans = {}
    for e in events:
        if e.get("ph") == "X":
            spans.setdefault(e["name"], []).append(e)
    (mirror,), (init,) = spans["valid_mirror"], spans["booster_init"]
    assert init["ts"] <= mirror["ts"]
    assert mirror["ts"] + mirror["dur"] <= init["ts"] + init["dur"] + 1


def test_a_second_booster_on_the_same_dataset_compiles_nothing(job):
    """The transpose is one program a (shape, placement) kept by the
    process: the benchmark's window job finds its warm-up job's."""
    case, sets, _, _ = job
    assert compile_events.install() or compile_events.installed()
    programs = gbdt_mod._valid_mirror_program.cache_info().currsize
    names = ("xla_compile_events", "xla_program_lowerings")
    before = [global_metrics.counter(n) for n in names]
    bst = lgb.Booster(CASES[case], sets[0])
    bst.add_valid(sets[1], "again")
    assert [global_metrics.counter(n) for n in names] == before
    assert gbdt_mod._valid_mirror_program.cache_info().currsize == programs
    assert (bst._gbdt._valid_bins_t[0] is not None) == MIRRORED[case]


def test_scores_and_metrics_equal_the_host_transposes(job, monkeypatch):
    case, sets, bst, series = job
    monkeypatch.setattr(gbdt_mod, "_valid_mirror_program",
                        _host_mirror_program)
    host_bst, host_series = _train(case, sets)
    assert len(series) == ROUNDS and series == host_series
    got, want = (np.asarray(b._gbdt.valid_scores[0]) for b in (bst, host_bst))
    assert got.dtype == want.dtype and np.array_equal(got, want)
    assert bst.model_to_string() == host_bst.model_to_string()
