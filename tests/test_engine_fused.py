"""The fused-rounds fast path (engine.py -> ``GBDT.train_fused``) against
the per-iteration loop: the same model to the bit, for a binary job, for
per-round feature masks, for seeds past int32 and for multiclass, and the
jobs that must keep the loop.  (Split from test_engine.py, whose file was
the suite's second longest: ``--dist loadfile`` gives a file to one
worker.)"""

import numpy as np

import lightgbm_tpu as lgb

FAST = {"num_leaves": 15, "learning_rate": 0.15, "min_data_in_leaf": 5,
        "max_bin": 63, "verbosity": 0}


def test_fused_rounds_identical_to_loop():
    """The fused-rounds fast path (engine.py -> GBDT.train_fused) must
    produce the BIT-IDENTICAL model to the per-iteration loop — same
    trees, same text, same predictions (scores are carried on device in
    both paths and quantized levels make every sum exact)."""
    rng = np.random.default_rng(0)
    n, f = 120_000, 6
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X @ rng.normal(size=f) > 0).astype(np.float32)
    p = {"objective": "binary", "verbose": -1, "num_leaves": 31}
    b_fused = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                        num_boost_round=7)
    assert b_fused._gbdt.supports_fused()

    def noop(env):
        pass
    b_loop = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                       num_boost_round=7, callbacks=[noop])
    assert b_fused.model_to_string() == b_loop.model_to_string()
    np.testing.assert_array_equal(b_fused.predict(X[:500]),
                                  b_loop.predict(X[:500]))


def test_fused_ineligible_paths_fall_back(synthetic_binary):
    """Configs with per-iteration host state (bagging, custom fobj,
    valid sets) must keep the classic loop and still train fine."""
    X, y = synthetic_binary
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config

    def make(params):
        p = {"objective": "binary", "verbose": -1, **params}
        ds = lgb.Dataset(X, label=y, params=p)
        ds.construct()
        return GBDT(Config(p), ds.inner)

    assert not make({"bagging_fraction": 0.5,
                     "bagging_freq": 1}).supports_fused()
    assert not make({"linear_tree": True}).supports_fused()
    assert not make({"objective": "quantile"}).supports_fused()
    # multiclass is fused-capable since the k-trees-per-round lift
    # (small fixtures need an explicit split batch: the fused path
    # rides the batched grower, and auto-K stays 1 below 100k rows);
    # impure objectives (per-call RNG) are the remaining objective gate
    assert make({"num_class": 3, "objective": "multiclass",
                 "tpu_split_batch": 4}).supports_fused()


def test_fused_feature_fraction_matches_loop():
    """Per-ROUND feature-fraction masks inside a fused chunk: the mask
    seed advances with the iteration exactly like the loop (round-4
    review catch: drawing all T masks at one iter_ froze the subset for
    a whole chunk)."""
    rng = np.random.default_rng(2)
    n, f = 120_000, 8
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X @ rng.normal(size=f) > 0).astype(np.float32)
    p = {"objective": "binary", "verbose": -1, "num_leaves": 15,
         "feature_fraction": 0.5}
    b_fused = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                        num_boost_round=6)
    assert b_fused._gbdt.supports_fused()

    def noop(env):
        pass
    b_loop = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                       num_boost_round=6, callbacks=[noop])
    assert b_fused.model_to_string() == b_loop.model_to_string()
    # and the subsets genuinely vary across trees
    d = b_fused.dump_model()
    feats = [tuple(sorted({s["split_feature"] for s in _iter_splits(
        t["tree_structure"])})) for t in d["tree_info"]]
    assert len(set(feats)) > 1, feats


def _iter_splits(node):
    if "split_feature" in node:
        yield node
        for k in ("left_child", "right_child"):
            if isinstance(node.get(k), dict):
                yield from _iter_splits(node[k])


def test_fused_large_seed_no_overflow():
    """seed big enough that seed*7919 exceeds int32: the fused path must
    neither crash nor diverge from the loop (round-4 review catch —
    per-round PRNG keys are computed host-side as python ints)."""
    rng = np.random.default_rng(3)
    n, f = 110_000, 5
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X @ rng.normal(size=f) > 0).astype(np.float32)
    p = {"objective": "binary", "verbose": -1, "num_leaves": 15,
         "seed": 400_000, "extra_seed": 5_000}
    b_fused = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                        num_boost_round=4)
    assert b_fused._gbdt.supports_fused()

    def noop(env):
        pass
    b_loop = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                       num_boost_round=4, callbacks=[noop])
    assert b_fused.model_to_string() == b_loop.model_to_string()


def test_fused_multiclass_identical_to_loop():
    """Fused rounds now carry k trees per scan step (one-vs-all, class
    order and per-class PRNG folds matching the classic loop), so
    multiclass training through train_fused must be bit-identical to
    the per-iteration loop."""
    rng = np.random.default_rng(13)
    n = 3000
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = ((X[:, 0] > 0).astype(int) + (X[:, 1] > 0.5).astype(int))
    p = {**FAST, "objective": "multiclass", "num_class": 3,
         "tpu_split_batch": 4}
    b_fused = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                        num_boost_round=6)
    assert b_fused._gbdt.supports_fused()
    noop = lambda env: None   # any callback forces the classic loop
    b_loop = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                       num_boost_round=6, callbacks=[noop])
    assert b_fused.model_to_string() == b_loop.model_to_string()
    pr = b_fused.predict(X)
    acc = float(np.mean(np.argmax(pr, axis=1) == y))
    assert acc > 0.85
