"""A benchmark cell at test size: what the ``tests/test_*_cell.py`` files
share.  Plain functions and no fixtures (each file keeps its own, so that
xdist's per-file workers lose nothing): the cell's configuration at
rehearsal size with a test's overrides, its data from the benchmark's own
generator, one job, the job's answers, the cell's comparison against its
plain reference, the rehearsal through ``benchmark/run.py`` in a process of
its own, and the driver's refusal of a program without its counters.  A new
cell's test file starts from these and holds only what is the cell's own.
"""

import contextlib
import json
import os
import subprocess
import sys

import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import round_fuse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for _p in (os.path.join(BENCH, "tools"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run as bench                                    # noqa: E402
from harness import compare, load_module, program      # noqa: E402

#: the seed the comparisons draw their probes from
SEED = 2147483659


def find(name, **overrides):
    """``(cell, cfg)``: the cell's entry in the manifest and its
    configuration at rehearsal size.  An override that is a dict is laid
    over the configuration's dict of that name, any other replaces the
    value."""
    _, cell, cfg, _ = bench.find_cell(name, rehearse_cpu=True)
    cfg = dict(cfg)
    for key, value in overrides.items():
        if isinstance(value, dict) and isinstance(cfg.get(key), dict):
            value = {**cfg[key], **value}
        cfg[key] = value
    return cell, cfg


def data(cfg):
    """The train and the valid part of the configuration's generator."""
    gen = load_module("datagen", cfg["data"]["generator"])
    f = int(cfg["features"])
    return (gen.make(cfg["data"], 0, 0, int(cfg["rows"]), f),
            gen.make(cfg["data"], 0, 1, int(cfg["valid_rows"]), f))


def inputs(data):
    """What a binary cell's comparison reads of the two parts: the float32
    (or CSR) matrix, the first of a part, and the labels, its last."""
    return {"train": (data[0][0], data[0][-1]),
            "valid": (data[1][0], data[1][-1])}


def train(cfg, sets, rounds, interpret_partition=False, callbacks=()):
    """One job of ``rounds`` rounds on the constructed ``(train, valid)``
    sets: the booster and what it recorded of the valid set each round
    (``{"auc": [...]}``, ``{"ndcg@1": [...], ...}``).  With
    ``interpret_partition`` the partition runs in the fused kernel
    (interpret mode), as on the chip."""
    evals = {}
    round_fuse._FUSE_TEST_INTERPRET = bool(interpret_partition)  # read when traced
    try:
        bst = lgb.train(cfg["params"], sets[0], num_boost_round=rounds,
                        valid_sets=[sets[1]],
                        callbacks=[lgb.record_evaluation(evals), *callbacks])
    finally:
        round_fuse._FUSE_TEST_INTERPRET = False
    return bst, evals["valid_0"]


def answers(bst, series, plain_trees=program.plain_trees):
    """What a driver's ``collect`` hands the comparison: the trees, the
    valid series (``valid_auc``, or ``valid_ndcg`` by cut-off) and the
    training scores the job holds."""
    out = {"trees": plain_trees(bst._gbdt.models),
           "train_scores": program.train_scores(bst)}
    if "auc" in series:
        out["valid_auc"] = series["auc"]
    else:
        out["valid_ndcg"] = {int(name.split("@")[1]): values
                             for name, values in series.items()}
    return out


def numbers(cfg, inputs, answers, reference=None, comparison=None, seed=SEED):
    """The cell's comparison of ``answers`` against its plain reference."""
    ref = load_module("reference", reference or cfg["reference"])
    cmp_ = load_module("comparisons", comparison or cfg["comparison"])
    return cmp_.gaps(ref, cfg, answers, inputs, seed)


def judged(cfg, inputs, answers, limits=None, **how):
    """``(correct, compared)`` under the cell's limits."""
    return compare.judge(numbers(cfg, inputs, answers, **how),
                         limits or cfg["limits"])


@contextlib.contextmanager
def planted(plant=None, grower=True, **where):
    """A fault of ``benchmark/tools/faults*.py`` in the program for the
    block's duration (``plant`` takes a ``setattr`` and patches with it;
    ``None`` plants nothing); nothing compiled before or under the fault
    outlives it.  ``grower=False`` for a fault that no module-level
    ``jax.jit`` traces (the objective, a metric, a hyper-parameter: what a
    booster's own programs trace): only the boosters' runners are dropped
    and the trees' programs stay compiled for the next case."""
    from lightgbm_tpu.ops.compile_cache import GLOBAL_COMPILE_CACHE
    clear = program.free_everything if grower else GLOBAL_COMPILE_CACHE.clear
    patch = pytest.MonkeyPatch()
    clear()
    if plant is not None:
        plant(patch.setattr, **where)
    try:
        yield
    finally:
        patch.undo()
        clear()


def rehearse(name, seed, env=None, limit_s=600):
    """``benchmark/run.py --workload <name> --rehearse-cpu`` in a process
    of its own (``run.py`` sets the persistent compile cache to keep every
    program, however small; a test process that took that over would fill
    ``tests/.jax_cache`` with entries the AOT store's tests trip over).
    Returns the JSON lines it printed.  Every rehearsal exits 0, compiles
    nothing inside its window and can never print a result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", name,
         "--seed", str(seed), "--seconds", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=limit_s, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    window = next(ln["window"] for ln in lines if "window" in ln)
    assert not any(window["compiled_in_window"].values())
    assert "rehearsal" in lines[-1] and "metrics" not in lines[-1]
    return lines


def assert_compared_within_limits(lines, names=None):
    """Every reading of the rehearsal's last line (or those of ``names``)
    at or under its limit."""
    compared = lines[-1]["compared"]
    over = {k: c for k, c in compared.items()
            if (names is None or k in names) and not c["value"] <= c["limit"]}
    assert not over and (names is None or set(names) <= set(compared)), compared


def assert_refused_without(monkeypatch, driver, *counters):
    """The parent of a cell's PR, a program that does not count what the
    cell's path is checked by: the driver refuses it (exit code 2, naming
    the counters) before any data is made."""
    from lightgbm_tpu.obs import metrics
    driver = load_module("drivers", driver)
    monkeypatch.setattr(metrics, "COUNTERS", {
        k: v for k, v in metrics.COUNTERS.items() if k not in counters})
    for module in (driver, getattr(driver, "_base", None)):
        if hasattr(module, "make_data"):
            monkeypatch.setattr(module, "make_data",
                                lambda ctx: pytest.fail("data was made"))

    class Ctx:
        cfg = traffic = phases = {}
    with pytest.raises(program.Refused) as refused:
        driver.prepare(Ctx())
    assert refused.value.code == 2
    assert all(c in refused.value.why for c in counters)
