"""End-to-end training tests (reference analogue:
tests/python_package_test/test_engine.py — metric-threshold assertions and
model-reload equivalence, SURVEY.md §4)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.metrics import _weighted_auc

FAST = {"num_leaves": 15, "learning_rate": 0.15, "min_data_in_leaf": 5,
        "max_bin": 63, "verbosity": 0}


def _auc(y, p):
    return _weighted_auc(np.asarray(y, float), np.asarray(p, float), None)


def test_binary(synthetic_binary):
    X, y = synthetic_binary
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=30)
    p = bst.predict(X)
    assert ((p >= 0) & (p <= 1)).all()
    assert _auc(y, p) > 0.9


def test_binary_reference_example(binary_example):
    Xtr, ytr, Xte, yte = binary_example
    ds = lgb.Dataset(Xtr, label=ytr, params=FAST)
    dv = ds.create_valid(Xte, label=yte)
    res = {}
    bst = lgb.train({**FAST, "objective": "binary", "metric": ["auc"]},
                    ds, num_boost_round=30, valid_sets=[dv],
                    valid_names=["te"],
                    callbacks=[lgb.record_evaluation(res)])
    assert res["te"]["auc"][-1] > 0.80
    # improves over iterations
    assert res["te"]["auc"][-1] > res["te"]["auc"][0]


def test_regression(synthetic_regression):
    X, y = synthetic_regression
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "regression"}, ds,
                    num_boost_round=40)
    p = bst.predict(X)
    mse = float(np.mean((p - y) ** 2))
    base = float(np.var(y))
    assert mse < 0.3 * base


def test_regression_l1(synthetic_regression):
    X, y = synthetic_regression
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "regression_l1"}, ds,
                    num_boost_round=30)
    mae = float(np.mean(np.abs(bst.predict(X) - y)))
    base = float(np.mean(np.abs(y - np.median(y))))
    assert mae < 0.6 * base


@pytest.mark.parametrize("objective", ["huber", "fair", "quantile", "mape"])
def test_regression_variants(synthetic_regression, objective):
    X, y = synthetic_regression
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": objective}, ds, num_boost_round=15)
    p = bst.predict(X)
    assert np.isfinite(p).all()


def test_poisson():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1500, 4))
    lam = np.exp(0.5 * X[:, 0] - 0.3 * X[:, 1])
    y = rng.poisson(lam).astype(np.float64)
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "poisson"}, ds, num_boost_round=30)
    p = bst.predict(X)
    assert (p > 0).all()
    assert np.corrcoef(p, lam)[0, 1] > 0.7


def test_multiclass():
    rng = np.random.default_rng(1)
    n = 1800
    X = rng.normal(size=(n, 5))
    y = np.argmax(X[:, :3] + 0.3 * rng.normal(size=(n, 3)), axis=1).astype(float)
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "multiclass", "num_class": 3},
                    ds, num_boost_round=20)
    p = bst.predict(X)
    assert p.shape == (n, 3)
    np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-5)
    acc = float((np.argmax(p, axis=1) == y).mean())
    assert acc > 0.8


def test_multiclassova():
    rng = np.random.default_rng(2)
    n = 1200
    X = rng.normal(size=(n, 5))
    y = np.argmax(X[:, :3], axis=1).astype(float)
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "multiclassova", "num_class": 3},
                    ds, num_boost_round=15)
    acc = float((np.argmax(bst.predict(X), axis=1) == y).mean())
    assert acc > 0.8


def test_lambdarank(synthetic_ranking):
    X, y, group = synthetic_ranking
    ds = lgb.Dataset(X, label=y, group=group, params=FAST)
    res = {}
    bst = lgb.train({**FAST, "objective": "lambdarank",
                     "metric": ["ndcg"], "eval_at": [5]},
                    ds, num_boost_round=25, valid_sets=[ds],
                    callbacks=[lgb.record_evaluation(res)])
    hist = res["training"]["ndcg@5"]
    assert hist[-1] > 0.75
    assert hist[-1] > hist[0]


def test_lambdarank_truncation_pairs_match_dense():
    """The O(nq*T*Q) sorted-space pair enumeration (rank_objective.hpp
    truncation loop) produces the SAME gradients as a brute-force dense
    [Q, Q] enumeration on small queries."""
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective

    rng = np.random.default_rng(5)
    nq, per_q = 8, 12
    n = nq * per_q
    y = rng.integers(0, 4, size=n).astype(np.float64)
    score = rng.normal(size=n).astype(np.float32)
    cfg = Config({"objective": "lambdarank",
                  "lambdarank_truncation_level": 5, "verbose": -1})

    class Meta:
        pass

    m = Meta()
    m.label = y
    m.weight = None
    m.query_boundaries = np.arange(0, n + 1, per_q)
    m.position = None
    obj = create_objective(cfg)
    obj.init(m, n)
    g, h = obj.get_gradients(jnp.asarray(score))
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)

    # brute force: all pairs, truncation by min sorted position, exactly
    # the reference's FindBestThreshold-free lambda math
    s = float(cfg.sigmoid)
    trunc = int(cfg.lambdarank_truncation_level)
    gains = np.power(2.0, y) - 1.0
    g_ref = np.zeros(n)
    h_ref = np.zeros(n)
    for q in range(nq):
        sl = slice(q * per_q, (q + 1) * per_q)
        ys, ss_, gg = y[sl], score[sl].astype(np.float64), gains[sl]
        order = np.argsort(-ss_, kind="stable")
        rank = np.argsort(order)
        top = np.sort(gg)[::-1][:trunc]
        maxdcg = np.sum(top / np.log2(np.arange(2, len(top) + 2)))
        inv = 1.0 / maxdcg if maxdcg > 0 else 0.0
        lam_sum = 0.0
        gq = np.zeros(per_q)
        hq = np.zeros(per_q)
        for i in range(per_q):
            for j in range(per_q):
                if ys[i] <= ys[j] or min(rank[i], rank[j]) >= trunc:
                    continue
                di = 1.0 / np.log2(rank[i] + 2.0)
                dj = 1.0 / np.log2(rank[j] + 2.0)
                delta = abs((gg[i] - gg[j]) * (di - dj)) * inv
                rho = 1.0 / (1.0 + np.exp(s * np.clip(
                    ss_[i] - ss_[j], -50.0 / s, 50.0 / s)))
                lam = -s * rho * delta
                hes = s * s * rho * (1.0 - rho) * delta
                gq[i] += lam
                gq[j] -= lam
                hq[i] += hes
                hq[j] += hes
                lam_sum += abs(lam)
        if cfg.lambdarank_norm and lam_sum > 0:
            nf = np.log2(1.0 + lam_sum) / lam_sum
            gq *= nf
            hq *= nf
        g_ref[sl], h_ref[sl] = gq, hq
    np.testing.assert_allclose(g, g_ref, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(h, h_ref, rtol=2e-4, atol=2e-6)


def test_lambdarank_long_queries_memory_bounded():
    """5k-doc queries train without materializing [nq, Q, Q] (VERDICT r1
    #7: the dense tensor would be nq*Q^2*4B = 2 GB per channel here)."""
    rng = np.random.default_rng(11)
    nq, per_q = 10, 5000
    n = nq * per_q
    X = rng.normal(size=(n, 4)).astype(np.float32)
    w = rng.normal(size=4)
    y = np.clip((X @ w + rng.normal(scale=0.8, size=n)) * 0.8 + 1.5,
                0, 4).round()
    ds = lgb.Dataset(X, label=y, group=np.full(nq, per_q),
                     params={**FAST})
    bst = lgb.train({**FAST, "objective": "lambdarank",
                     "metric": ["ndcg"], "eval_at": [10]},
                    ds, num_boost_round=2, valid_sets=[ds])
    (_, _, val, _), = bst.eval_train()
    assert val > 0.3


def test_linear_tree(synthetic_regression):
    """linear_tree=true fits ridge models in the leaves
    (linear_tree_learner.cpp CalculateLinear): on a piecewise-linear target
    it beats constant leaves, and predictions round-trip through save/load."""
    X, y = synthetic_regression
    p = {**FAST, "objective": "regression", "linear_tree": True,
         "num_leaves": 7}
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, num_boost_round=12)
    pred_lin = bst.predict(X)
    mse_lin = float(np.mean((pred_lin - y) ** 2))

    p0 = {**FAST, "objective": "regression", "num_leaves": 7}
    ds0 = lgb.Dataset(X, label=y, params=p0)
    bst0 = lgb.train(p0, ds0, num_boost_round=12)
    mse_const = float(np.mean((bst0.predict(X) - y) ** 2))
    assert mse_lin < mse_const  # linear leaves strictly help here

    # model text round-trip preserves the linear leaves
    s = bst.model_to_string()
    assert "is_linear=1" in s and "num_features=" in s
    bst2 = lgb.Booster(model_str=s)
    np.testing.assert_allclose(pred_lin, bst2.predict(X), rtol=1e-5,
                               atol=1e-6)
    # NaN rows fall back to the constant leaf output, not garbage
    Xn = X.copy()
    Xn[:5, :] = np.nan
    pn = bst2.predict(Xn)
    assert np.isfinite(pn).all()


def test_lambdarank_position_bias(synthetic_ranking):
    """Position-debiased LTR (rank_objective.hpp positions_/pos_biases_):
    training with a position column still learns, and the per-position bias
    factors move away from zero."""
    X, y, group = synthetic_ranking
    rng = np.random.default_rng(11)
    # synthetic presentation positions 0..9, lower position = more exposure
    position = np.concatenate([rng.permutation(20) % 10 for _ in group])
    ds = lgb.Dataset(X, label=y, group=group, position=position, params=FAST)
    res = {}
    bst = lgb.train({**FAST, "objective": "lambdarank", "metric": ["ndcg"],
                     "eval_at": [5],
                     "lambdarank_position_bias_regularization": 0.1},
                    ds, num_boost_round=15, valid_sets=[ds],
                    callbacks=[lgb.record_evaluation(res)])
    hist = res["training"]["ndcg@5"]
    assert hist[-1] > hist[0]
    obj = bst._gbdt.objective
    assert obj._positions is not None
    assert np.abs(obj._pos_biases).max() > 0


def test_rank_xendcg(synthetic_ranking):
    X, y, group = synthetic_ranking
    ds = lgb.Dataset(X, label=y, group=group, params=FAST)
    res = {}
    bst = lgb.train({**FAST, "objective": "rank_xendcg",
                     "metric": ["ndcg"], "eval_at": [5]},
                    ds, num_boost_round=25, valid_sets=[ds],
                    callbacks=[lgb.record_evaluation(res)])
    hist = res["training"]["ndcg@5"]
    assert hist[-1] > hist[0]


def test_cross_entropy(synthetic_binary):
    X, y = synthetic_binary
    # probabilistic labels
    yp = np.clip(y * 0.9 + 0.05, 0, 1)
    ds = lgb.Dataset(X, label=yp, params=FAST)
    bst = lgb.train({**FAST, "objective": "cross_entropy"}, ds,
                    num_boost_round=20)
    p = bst.predict(X)
    assert ((p >= 0) & (p <= 1)).all()
    assert _auc(y, p) > 0.85


def test_early_stopping(synthetic_binary):
    X, y = synthetic_binary
    Xtr, ytr = X[:1500], y[:1500]
    Xva, yva = X[1500:], y[1500:]
    ds = lgb.Dataset(Xtr, label=ytr, params=FAST)
    dv = ds.create_valid(Xva, label=yva)
    bst = lgb.train({**FAST, "objective": "binary", "metric": ["binary_logloss"]},
                    ds, num_boost_round=200, valid_sets=[dv],
                    callbacks=[lgb.early_stopping(5, verbose=False)])
    assert bst.best_iteration < 200


def test_custom_objective_and_metric(synthetic_binary):
    X, y = synthetic_binary

    def fobj(preds, dataset):
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - y, p * (1 - p)

    def feval(preds, dataset):
        p = 1.0 / (1.0 + np.exp(-preds))
        return "my_auc", _auc(y, p), True

    ds = lgb.Dataset(X, label=y, params=FAST)
    res = {}
    bst = lgb.train({**FAST, "objective": "none"}, ds, num_boost_round=20,
                    valid_sets=[ds], fobj=fobj, feval=feval,
                    callbacks=[lgb.record_evaluation(res)])
    assert res["training"]["my_auc"][-1] > 0.9


def test_save_load_roundtrip(synthetic_binary, tmp_path):
    X, y = synthetic_binary
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=10)
    p1 = bst.predict(X)
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    bst2 = lgb.Booster(model_file=path)
    p2 = bst2.predict(X)
    np.testing.assert_allclose(p1, p2, atol=1e-5)
    # model text round-trips through parse + re-serialize
    s1 = bst2.model_to_string()
    bst3 = lgb.Booster(model_str=s1)
    np.testing.assert_allclose(p1, bst3.predict(X), atol=1e-5)


def test_dump_model_json(synthetic_binary):
    X, y = synthetic_binary
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=3)
    d = bst.dump_model()  # dict, like the reference Booster.dump_model
    assert d["num_class"] == 1
    assert len(d["tree_info"]) == 3
    assert "tree_structure" in d["tree_info"][0]


def test_bagging_and_feature_fraction(synthetic_binary):
    X, y = synthetic_binary
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary", "bagging_fraction": 0.6,
                     "bagging_freq": 2, "feature_fraction": 0.7},
                    ds, num_boost_round=20)
    assert _auc(y, bst.predict(X)) > 0.85


def test_goss(synthetic_binary):
    X, y = synthetic_binary
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary", "boosting": "goss"},
                    ds, num_boost_round=25)
    assert _auc(y, bst.predict(X)) > 0.85


def test_dart(synthetic_binary):
    X, y = synthetic_binary
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary", "boosting": "dart",
                     "drop_rate": 0.2}, ds, num_boost_round=15)
    assert _auc(y, bst.predict(X)) > 0.85


def test_rf(synthetic_binary):
    X, y = synthetic_binary
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary", "boosting": "rf",
                     "bagging_fraction": 0.7, "bagging_freq": 1,
                     "num_iterations": 20},
                    ds, num_boost_round=20)
    assert _auc(y, bst.predict(X)) > 0.85


def test_weights(synthetic_binary):
    X, y = synthetic_binary
    w = np.where(y > 0, 2.0, 1.0)
    ds = lgb.Dataset(X, label=y, weight=w, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=10)
    # upweighting positives shifts mean prediction up vs unweighted
    ds0 = lgb.Dataset(X, label=y, params=FAST)
    bst0 = lgb.train({**FAST, "objective": "binary"}, ds0, num_boost_round=10)
    assert bst.predict(X).mean() > bst0.predict(X).mean()


def test_categorical_feature():
    rng = np.random.default_rng(5)
    n = 1500
    cat = rng.integers(0, 6, size=n).astype(float)
    other = rng.normal(size=n)
    effect = np.array([2.0, -1.0, 0.5, -2.0, 1.0, 0.0])
    y = (effect[cat.astype(int)] + 0.3 * other +
         rng.normal(scale=0.3, size=n) > 0).astype(float)
    X = np.stack([cat, other], axis=1)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=25)
    assert _auc(y, bst.predict(X)) > 0.9


def test_categorical_sorted_subset():
    """High-cardinality categorical must use many-vs-many splits (reference
    feature_histogram.cpp:241 sorted-subset scan), not just one-hot."""
    rng = np.random.default_rng(11)
    n, k = 4000, 40
    cat = rng.integers(0, k, size=n)
    effect = rng.normal(size=k)
    other = rng.normal(size=(n, 3))
    y = (effect[cat] + 0.2 * other[:, 0] +
         rng.normal(scale=0.3, size=n) > 0).astype(float)
    X = np.column_stack([cat.astype(float), other])
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=30)
    assert _auc(y, bst.predict(X)) > 0.93
    # sorted-subset splits put >1 category on the left
    assert any(len(c) > 1 for t in bst._gbdt.models for c in t.cat_threshold)
    # text round-trip preserves the bitsets exactly
    bst2 = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_allclose(bst.predict(X), bst2.predict(X), rtol=1e-6)


def test_categorical_nan_and_unseen():
    rng = np.random.default_rng(12)
    n = 2000
    cat = rng.integers(0, 12, size=n).astype(float)
    cat[rng.random(n) < 0.1] = np.nan
    effect = rng.normal(size=12)
    y = np.where(np.isnan(cat), 0.5, effect[np.nan_to_num(cat).astype(int)])
    y = (y + rng.normal(scale=0.3, size=n) > 0).astype(float)
    X = cat.reshape(-1, 1)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0], params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=15)
    # unseen category at predict time routes like the default and is finite
    Xq = np.array([[99.0], [np.nan], [3.0]])
    out = bst.predict(Xq)
    assert np.all(np.isfinite(out))


def test_reset_parameter(synthetic_binary):
    X, y = synthetic_binary
    ds = lgb.Dataset(X, label=y, params=FAST)
    bst = lgb.train({**FAST, "objective": "binary"}, ds, num_boost_round=10,
                    callbacks=[lgb.reset_parameter(
                        learning_rate=lambda i: 0.2 * (0.9 ** i))])
    assert bst.num_trees() == 10


@pytest.mark.parametrize("objective,extra", [
    ("regression", {}),
    ("regression_l1", {}),
    ("huber", {}),
    ("poisson", {}),
    ("quantile", {"alpha": 0.7}),
    ("binary", {}),
    ("multiclass", {"num_class": 3}),
    ("multiclassova", {"num_class": 3}),
    ("cross_entropy", {}),
])
def test_save_load_all_objectives(objective, extra, tmp_path):
    """Model-reload prediction equivalence for every objective family
    (reference test_engine.py asserts exact reload parity per objective)."""
    rng = np.random.default_rng(11)
    n, f = 900, 5
    X = rng.normal(size=(n, f))
    raw = X @ rng.normal(size=f)
    if objective in ("multiclass", "multiclassova"):
        y = np.digitize(raw, np.quantile(raw, [0.33, 0.66]))
    elif objective == "binary":
        y = (raw > 0).astype(float)
    elif objective == "cross_entropy":
        y = 1.0 / (1.0 + np.exp(-raw))
    else:
        y = raw + rng.normal(scale=0.1, size=n)
        if objective == "poisson":
            y = np.exp(y / 4)
    params = {"objective": objective, "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, **extra}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=8)
    p1 = bst.predict(X)
    path = tmp_path / f"{objective}.txt"
    bst.save_model(str(path))
    p2 = lgb.Booster(model_file=str(path)).predict(X)
    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-6)


def test_init_score_training(synthetic_binary):
    """init_score offsets gradients (reference Metadata init_score path);
    a strong init_score should yield better early logloss than none."""
    X, y = synthetic_binary
    base = np.where(y > 0, 2.0, -2.0) * 0.9   # informative margin
    d0 = lgb.Dataset(X, label=y, params={"verbose": -1})
    d1 = lgb.Dataset(X, label=y, init_score=base, params={"verbose": -1})
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "metric": ["binary_logloss"]}
    r0, r1 = {}, {}
    lgb.train(p, d0, num_boost_round=3, valid_sets=[d0], valid_names=["t"],
              callbacks=[lgb.record_evaluation(r0)])
    lgb.train(p, d1, num_boost_round=3, valid_sets=[d1], valid_names=["t"],
              callbacks=[lgb.record_evaluation(r1)])
    key0 = next(iter(r0))
    key1 = next(iter(r1))
    assert r1[key1]["binary_logloss"][0] < r0[key0]["binary_logloss"][0]


def test_linear_tree_score_cache_rebuild(synthetic_regression):
    """ADVICE r3: invalidate_score_cache must include the per-leaf linear
    terms — a rebuilt cache has to match the incrementally-maintained
    train scores, or continued training after merge/shuffle computes
    gradients from wrong scores."""
    X, y = synthetic_regression
    p = {"objective": "regression", "num_leaves": 15, "verbose": -1,
         "min_data_in_leaf": 10, "linear_tree": True}
    ds = lgb.Dataset(X, label=y, params=p)
    bst = lgb.train(p, ds, num_boost_round=5, keep_training_booster=True)
    g = bst._gbdt
    assert any(t.is_linear for t in g.models)
    before = np.asarray(g.scores).copy()
    g.invalidate_score_cache()
    after = np.asarray(g.scores)
    np.testing.assert_allclose(after, before, rtol=2e-4, atol=2e-4)


def test_auto_speed_mode_at_scale():
    """Fast-by-default (VERDICT r3): plain params at >=100k rows resolve to
    the batched grower + exact quantized-grad int8 kernels; explicit
    settings and deterministic=true win; small data keeps exact f32."""
    rng = np.random.default_rng(0)
    n, f = 100_000, 4
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X @ rng.normal(size=f) > 0).astype(np.float32)
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config

    def make(params, n_rows=n):
        p = {"objective": "binary", "verbose": -1, **params}
        ds = lgb.Dataset(X[:n_rows], label=y[:n_rows], params=p)
        ds.construct()
        return GBDT(Config(p), ds.inner)

    g = make({"num_leaves": 255})
    assert int(g.config.tpu_split_batch) == 42
    assert g.config.use_quantized_grad is True
    assert g.config.tpu_hist_dtype == "int8"
    assert g.hp.hist_dtype == "int8"
    assert g.config.quant_train_renew_leaf is True

    g = make({"num_leaves": 15})
    assert int(g.config.tpu_split_batch) == 14

    # explicit choices win
    g = make({"num_leaves": 255, "tpu_split_batch": 4,
              "tpu_hist_dtype": "float32"})
    assert int(g.config.tpu_split_batch) == 4
    assert g.config.use_quantized_grad is False
    assert g.hp.hist_dtype == "float32"

    g = make({"num_leaves": 255, "use_quantized_grad": False})
    assert g.config.use_quantized_grad is False
    assert g.hp.hist_dtype == "float32"

    # deterministic pins the exact path
    g = make({"num_leaves": 255, "deterministic": True})
    assert g.config.use_quantized_grad is False
    assert g.hp.hist_dtype == "float32"

    # small data: exact f32 strict path
    g = make({"num_leaves": 255}, n_rows=5000)
    assert int(g.config.tpu_split_batch) == 1
    assert g.config.use_quantized_grad is False
    assert g.hp.hist_dtype == "float32"

    # linear trees need true gradients (no int8/quantized auto) but ARE
    # batched-capable since the round-4 lift, so they get the auto K
    g = make({"num_leaves": 255, "linear_tree": True})
    assert g.config.use_quantized_grad is False
    assert int(g.config.tpu_split_batch) == 42


# ---------------------------------------------------------------------------
# Objective x boosting-mode x feature matrix (round 4; reference analogue:
# tests/python_package_test/test_engine.py's per-objective/mode coverage).
# Every cell asserts BOTH a learning-quality metric threshold and exact
# save/load prediction equivalence, the two invariants the reference's
# engine tests lean on throughout.

def _matrix_data(objective, seed=0):
    """Learnable synthetic task + (metric fn, base threshold) per
    objective family.  Metric convention: smaller is better, and the
    threshold is a fraction of the trivial predictor's score — so a cell
    only passes when the model genuinely learned."""
    rng = np.random.default_rng(seed)
    n, f = 900, 6
    X = rng.normal(size=(n, f))
    extra = {}
    if objective in ("binary", "cross_entropy"):
        margin = X @ rng.normal(size=f) + 0.4 * X[:, 0] * X[:, 1]
        y = (margin + 0.4 * rng.normal(size=n) > 0).astype(np.float64)
        if objective == "cross_entropy":
            y = 1.0 / (1.0 + np.exp(-2.0 * margin))     # soft labels

        def metric(y_, p_):
            return float(np.mean((p_ - y_) ** 2))       # Brier

        base = metric(y, np.full(n, y.mean()))
    elif objective in ("regression", "regression_l1", "huber", "fair",
                       "quantile", "mape"):
        y = X @ rng.normal(size=f) + np.sin(2 * X[:, 0]) \
            + 0.1 * rng.normal(size=n)
        if objective == "mape":
            y = np.abs(y) + 1.0                          # mape needs y != 0

        def metric(y_, p_):
            return float(np.mean(np.abs(p_ - y_)))

        base = metric(y, np.full(n, np.median(y)))
    elif objective in ("poisson", "gamma", "tweedie"):
        lam = np.exp(0.6 * X[:, 0] - 0.4 * X[:, 1])
        y = rng.poisson(lam).astype(np.float64)
        if objective in ("gamma", "tweedie"):
            y = y + rng.gamma(1.0, 0.3, size=n) + 0.05   # positive

        def metric(y_, p_):
            return float(np.mean((p_ - y_) ** 2))

        base = metric(y, np.full(n, y.mean()))
    elif objective in ("multiclass", "multiclassova"):
        centers = rng.normal(size=(3, f)) * 1.6
        cls = rng.integers(0, 3, size=n)
        X = centers[cls] + rng.normal(size=(n, f))
        y = cls.astype(np.float64)
        extra["num_class"] = 3

        def metric(y_, p_):
            return float(np.mean(np.argmax(p_, axis=1) != y_))  # error rate

        base = 2.0 / 3.0
    elif objective in ("lambdarank", "rank_xendcg"):
        nq, per_q = 40, 15
        X = rng.normal(size=(nq * per_q, f))
        rel = X @ rng.normal(size=f) + 0.3 * rng.normal(size=nq * per_q)
        y = np.zeros(nq * per_q)
        for q in range(nq):
            s = slice(q * per_q, (q + 1) * per_q)
            y[s] = np.digitize(rel[s], np.quantile(rel[s], [0.6, 0.85, 0.97]))
        extra["group"] = np.full(nq, per_q)

        def metric(y_, p_):
            # mean within-query fraction of top-3 predictions that are
            # relevant (>=1): HIGHER is better, so return 1 - frac
            ok = []
            for q in range(nq):
                s = slice(q * per_q, (q + 1) * per_q)
                top = np.argsort(-p_[s])[:3]
                ok.append(float((y_[s][top] >= 1).mean()))
            return 1.0 - float(np.mean(ok))

        base = metric(y, rng.normal(size=nq * per_q))
    else:
        raise AssertionError(objective)
    return X, y, extra, metric, base


MATRIX_MODES = {
    "plain": {},
    "bagging": {"bagging_fraction": 0.7, "bagging_freq": 1},
    "goss": {"data_sample_strategy": "goss"},
    "dart": {"boosting": "dart", "drop_rate": 0.2},
    "rf": {"boosting": "rf", "bagging_fraction": 0.8, "bagging_freq": 1},
}
# rf averages unshrunk trees and dart drops trees: both learn less in 12
# rounds, so their cells pass at a looser fraction of the base score
_MODE_FRAC = {"plain": 0.75, "bagging": 0.8, "goss": 0.8, "dart": 0.9,
              "rf": 0.95}

MATRIX_OBJECTIVES = ["binary", "regression", "regression_l1", "poisson",
                     "multiclass", "lambdarank"]


def _train_cell(objective, mode_params, feature_params=None, seed=0,
                rounds=12):
    X, y, extra, metric, base = _matrix_data(objective, seed)
    p = {**FAST, "objective": objective, **mode_params,
         **(feature_params or {})}
    p.update({k: v for k, v in extra.items() if k == "num_class"})
    dkw = {}
    if "group" in extra:
        dkw["group"] = extra["group"]
    weights = None
    if feature_params and feature_params.get("_weights"):
        p = {k: v for k, v in p.items() if k != "_weights"}
        weights = np.linspace(0.5, 1.5, len(y))
        dkw["weight"] = weights
    cat = None
    if feature_params and feature_params.get("_categorical"):
        p = {k: v for k, v in p.items() if k != "_categorical"}
        rng = np.random.default_rng(seed + 1)
        X = X.copy()
        catcol = rng.integers(0, 8, size=len(y)).astype(np.float64)
        if objective in ("binary",):
            y = ((y > 0.5) ^ (catcol < 2)).astype(np.float64)
        X[:, -1] = catcol
        cat = [X.shape[1] - 1]
    if feature_params and feature_params.get("_efb"):
        p = {k: v for k, v in p.items() if k != "_efb"}
        rng = np.random.default_rng(seed + 2)
        onehot = np.zeros((len(y), 6))
        sel = rng.integers(0, 6, size=len(y))
        onehot[np.arange(len(y)), sel] = 1.0
        X = np.concatenate([X, onehot], axis=1)  # exclusive -> bundles
    ds = lgb.Dataset(X, label=y, params=p, categorical_feature=cat, **dkw)
    bst = lgb.train(p, ds, num_boost_round=rounds)
    pred = bst.predict(X)
    return bst, X, y, metric, base, pred


@pytest.mark.parametrize("mode", list(MATRIX_MODES))
@pytest.mark.parametrize("objective", MATRIX_OBJECTIVES)
def test_objective_mode_matrix(objective, mode):
    """Every (objective, boosting-mode) cell learns past a fraction of the
    trivial predictor AND survives a model text round-trip bit-for-bit in
    prediction."""
    if objective == "lambdarank" and mode == "goss":
        pytest.skip("goss resampling breaks query blocks (reference "
                    "requires bagging_by_query for ranking subsamples)")
    bst, X, y, metric, base, pred = _train_cell(objective,
                                                MATRIX_MODES[mode])
    score = metric(y, pred)
    frac = _MODE_FRAC[mode]
    assert score < frac * base, (objective, mode, score, base)
    # save -> load -> identical predictions (model text is the contract)
    s = bst.model_to_string()
    bst2 = lgb.Booster(model_str=s)
    pred2 = bst2.predict(X)
    np.testing.assert_allclose(pred2, pred, rtol=1e-5, atol=1e-7,
                               err_msg=f"{objective}/{mode}")


MATRIX_FEATURES = {
    "quantized": {"use_quantized_grad": True,
                  "quant_train_renew_leaf": True},
    "weights": {"_weights": True},
    "categorical": {"_categorical": True},
    "efb": {"_efb": True},
    "bf16": {"tpu_hist_dtype": "bfloat16"},
    # int8 MXU histograms: requires quantized levels (gbdt.py
    # _resolve_hist_dtype); CPU runs the exact XLA fallback so the cell
    # checks config plumbing + learning, the kernel parity lives in
    # tests/test_int8_kernels.py
    "int8": {"tpu_hist_dtype": "int8", "use_quantized_grad": True,
             "quant_train_renew_leaf": True},
}


@pytest.mark.parametrize("feature", list(MATRIX_FEATURES))
@pytest.mark.parametrize("objective", ["binary", "regression",
                                       "multiclass", "lambdarank"])
def test_objective_feature_matrix(objective, feature):
    """Every (objective, feature) cell: quantized gradients, sample
    weights, categorical splits, EFB bundling and the bf16 kernel path
    each keep the model learnable and round-trippable."""
    if objective == "lambdarank" and feature == "quantized":
        pytest.skip("ranking gradients are pair-normalized; the reference "
                    "quantizes them too but at 30x our test rounds")
    bst, X, y, metric, base, pred = _train_cell(
        objective, {}, MATRIX_FEATURES[feature])
    score = metric(y, pred)
    assert score < 0.85 * base, (objective, feature, score, base)
    s = bst.model_to_string()
    bst2 = lgb.Booster(model_str=s)
    np.testing.assert_allclose(bst2.predict(X), pred, rtol=1e-5,
                               atol=1e-7, err_msg=f"{objective}/{feature}")


@pytest.mark.parametrize("objective", ["binary", "regression", "multiclass"])
def test_init_score_paths(objective):
    """init_score seeds training (reference boost-from-init-score): a
    booster continued from another model's scores must beat one trained
    from scratch with the same SMALL round budget."""
    X, y, extra, metric, base = _matrix_data(objective, seed=3)
    k = int(extra.get("num_class", 1))
    p = {**FAST, "objective": objective}
    p.update({kk: v for kk, v in extra.items() if kk == "num_class"})
    ds0 = lgb.Dataset(X, label=y, params=p)
    warm = lgb.train(p, ds0, num_boost_round=10)
    init = warm.predict(X, raw_score=True)
    ds1 = lgb.Dataset(X, label=y, params=p,
                      init_score=init.reshape(-1, order="F")
                      if k > 1 else init)
    cold = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                     num_boost_round=3)
    hot = lgb.train(p, ds1, num_boost_round=3)
    hot_pred = hot.predict(X, raw_score=True)
    # continued predictions = init + new trees: add init back for scoring
    full = hot_pred + init
    if k > 1:
        e = np.exp(full.reshape(-1, k) - full.reshape(-1, k).max(
            axis=1, keepdims=True))
        full_prob = e / e.sum(axis=1, keepdims=True)
        score_hot = metric(y, full_prob)
        score_cold = metric(y, cold.predict(X))
    elif objective == "binary":
        score_hot = metric(y, 1.0 / (1.0 + np.exp(-full)))
        score_cold = metric(y, cold.predict(X))
    else:
        score_hot = metric(y, full)
        score_cold = metric(y, cold.predict(X))
    assert score_hot < score_cold, (objective, score_hot, score_cold)


# ---- three-way mode x feature crosses (the combinations users actually
# run together; each cell still carries threshold + round-trip)

@pytest.mark.parametrize("objective,mode,feature", [
    ("binary", "dart", "categorical"),
    ("binary", "bagging", "quantized"),
    ("binary", "goss", "bf16"),
    ("regression", "dart", "weights"),
    ("regression", "bagging", "efb"),
    ("multiclass", "bagging", "weights"),
    ("regression", "rf", "categorical"),
    ("binary", "rf", "efb"),
])
def test_mode_feature_cross_matrix(objective, mode, feature):
    bst, X, y, metric, base, pred = _train_cell(
        objective, MATRIX_MODES[mode], MATRIX_FEATURES[feature])
    score = metric(y, pred)
    frac = min(_MODE_FRAC[mode] + 0.05, 0.97)
    assert score < frac * base, (objective, mode, feature, score, base)
    bst2 = lgb.Booster(model_str=bst.model_to_string())
    np.testing.assert_allclose(bst2.predict(X), pred, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("objective", ["binary", "regression", "multiclass"])
def test_predict_variants_per_objective(objective):
    """pred_leaf shapes/index-validity, SHAP additivity, and
    start/num_iteration slicing across objective families (reference
    test_engine.py predict-variant blocks)."""
    X, y, extra, metric, base = _matrix_data(objective, seed=5)
    k = int(extra.get("num_class", 1))
    p = {**FAST, "objective": objective}
    p.update({kk: v for kk, v in extra.items() if kk == "num_class"})
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=10)
    n = 80
    leaves = bst.predict(X[:n], pred_leaf=True)
    assert leaves.shape == (n, 10 * k)
    assert leaves.dtype.kind in "iu"
    assert (leaves >= 0).all() and (leaves < FAST["num_leaves"]).all()
    contrib = bst.predict(X[:n], pred_contrib=True)
    raw = bst.predict(X[:n], raw_score=True)
    f = X.shape[1]
    assert contrib.shape == (n, (f + 1) * k)
    # SHAP additivity: per-class contributions + bias == raw margin
    csum = contrib.reshape(n, k, f + 1).sum(axis=2)
    np.testing.assert_allclose(csum, raw.reshape(n, k, order="F")
                               if k > 1 else csum * 0 + raw[:, None],
                               rtol=1e-5, atol=1e-5)
    # iteration slicing: first 4 + remaining 6 raw contributions compose
    raw4 = bst.predict(X[:n], raw_score=True, num_iteration=4)
    raw_rest = bst.predict(X[:n], raw_score=True, start_iteration=4,
                           num_iteration=6)
    np.testing.assert_allclose(np.asarray(raw4) + np.asarray(raw_rest),
                               raw, rtol=1e-4, atol=1e-5)


def test_class_imbalance_params(synthetic_binary):
    """is_unbalance / scale_pos_weight upweight the positive class
    (reference binary objective label weights)."""
    X, y = synthetic_binary
    # make it imbalanced: drop 80% of positives
    rng = np.random.default_rng(0)
    keep = (y < 0.5) | (rng.random(len(y)) < 0.2)
    Xi, yi = X[keep], y[keep]
    preds = {}
    for name, extra in (("plain", {}), ("unbal", {"is_unbalance": True}),
                        ("spw", {"scale_pos_weight": 4.0})):
        p = {**FAST, "objective": "binary", **extra}
        bst = lgb.train(p, lgb.Dataset(Xi, label=yi, params=p),
                        num_boost_round=15)
        preds[name] = bst.predict(Xi)
    # upweighting positives raises mean predicted probability
    assert preds["unbal"].mean() > preds["plain"].mean() * 1.05
    assert preds["spw"].mean() > preds["plain"].mean() * 1.05


def test_boost_from_average_toggle(synthetic_binary):
    """boost_from_average=false starts from 0 margin; true from log-odds —
    single-tree raw predictions must differ by roughly the prior's
    log-odds (reference gbdt.cpp BoostFromAverage)."""
    X, y = synthetic_binary
    p1 = {**FAST, "objective": "binary", "boost_from_average": True}
    p0 = {**FAST, "objective": "binary", "boost_from_average": False}
    b1 = lgb.train(p1, lgb.Dataset(X, label=y, params=p1), num_boost_round=1)
    b0 = lgb.train(p0, lgb.Dataset(X, label=y, params=p0), num_boost_round=1)
    prior = float(y.mean())
    logodds = np.log(prior / (1 - prior))
    d = np.mean(b1.predict(X, raw_score=True) - b0.predict(X, raw_score=True))
    assert abs(d - logodds) < 0.25 * abs(logodds) + 0.05


def test_sigmoid_parameter(synthetic_binary):
    """The sigmoid slope enters gradients AND the output transform
    (reference binary_objective.hpp sigmoid_): raw margins scale ~1/s so
    the predicted probabilities are near-invariant — the same geometry
    the reference exhibits."""
    X, y = synthetic_binary
    p1 = {**FAST, "objective": "binary", "sigmoid": 1.0}
    p2 = {**FAST, "objective": "binary", "sigmoid": 3.0}
    b1 = lgb.train(p1, lgb.Dataset(X, label=y, params=p1), num_boost_round=8)
    b2 = lgb.train(p2, lgb.Dataset(X, label=y, params=p2), num_boost_round=8)
    r1 = b1.predict(X, raw_score=True)
    r2 = b2.predict(X, raw_score=True)
    assert not np.allclose(r1, r2)                  # raw margins differ
    np.testing.assert_allclose(3.0 * np.median(np.abs(r2)),
                               np.median(np.abs(r1)), rtol=0.25)
    pr1, pr2 = b1.predict(X), b2.predict(X)
    assert ((pr1 > 0) & (pr1 < 1)).all() and ((pr2 > 0) & (pr2 < 1)).all()
    assert _auc(y, pr1) > 0.85 and _auc(y, pr2) > 0.85


@pytest.mark.parametrize("objective", ["binary", "regression"])
def test_cv_objectives(objective):
    """lgb.cv returns per-iteration mean/stdv arrays that improve
    (reference engine.py cv)."""
    X, y, extra, metric, base = _matrix_data(objective, seed=7)
    p = {**FAST, "objective": objective,
         "metric": "binary_logloss" if objective == "binary" else "l2"}
    res = lgb.cv(p, lgb.Dataset(X, label=y, params=p), num_boost_round=12,
                 nfold=3, stratified=objective == "binary", seed=3)
    mkey = [k for k in res if k.endswith("-mean")][0]
    skey = [k for k in res if k.endswith("-stdv")][0]
    assert len(res[mkey]) == 12 and len(res[skey]) == 12
    assert res[mkey][-1] < res[mkey][0]
    assert all(s >= 0 for s in res[skey])


@pytest.mark.parametrize("objective", ["binary", "regression"])
def test_early_stopping_min_delta_matrix(objective):
    """early_stopping(min_delta) stops sooner than plain early stopping
    (reference callback.py min_delta support, test_callback.py)."""
    X, y, extra, metric, base = _matrix_data(objective, seed=9)
    half = len(y) // 2
    p = {**FAST, "objective": objective,
         "metric": "binary_logloss" if objective == "binary" else "l2"}
    ds = lgb.Dataset(X[:half], label=y[:half], params=p)
    dv = ds.create_valid(X[half:], label=y[half:])

    def run(cb):
        return lgb.train(p, ds, num_boost_round=200, valid_sets=[dv],
                         callbacks=[cb])

    b_plain = run(lgb.early_stopping(5, verbose=False))
    b_delta = run(lgb.early_stopping(5, min_delta=0.05, verbose=False))
    assert b_delta.best_iteration <= b_plain.best_iteration
    assert b_plain.best_iteration < 200


def test_max_depth_respected_in_model():
    """max_depth caps every tree's leaf depth (reference config check +
    serial_tree_learner depth gating), verified from the dumped model."""
    X, y, *_ = _matrix_data("binary", seed=11)
    p = {**FAST, "objective": "binary", "max_depth": 3, "num_leaves": 31}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=5)
    dump = bst.dump_model()

    def depth(node, d=0):
        if "split_feature" not in node:
            return d
        return max(depth(node["left_child"], d + 1),
                   depth(node["right_child"], d + 1))

    for t in dump["tree_info"]:
        assert depth(t["tree_structure"]) <= 3


def test_min_gain_to_split_prunes():
    X, y, *_ = _matrix_data("regression", seed=12)
    p0 = {**FAST, "objective": "regression", "min_gain_to_split": 0.0}
    p1 = {**FAST, "objective": "regression", "min_gain_to_split": 1e3}
    b0 = lgb.train(p0, lgb.Dataset(X, label=y, params=p0), num_boost_round=3)
    b1 = lgb.train(p1, lgb.Dataset(X, label=y, params=p1), num_boost_round=3)
    n0 = sum(t["num_leaves"] for t in b0.dump_model()["tree_info"])
    n1 = sum(t["num_leaves"] for t in b1.dump_model()["tree_info"])
    assert n1 < n0


def test_feature_importance_split_vs_gain():
    """split/gain importances agree on the dominant feature and match the
    dumped model's split counts (reference Booster.feature_importance)."""
    rng = np.random.default_rng(13)
    n = 900
    X = rng.normal(size=(n, 5))
    y = (X[:, 2] > 0).astype(np.float64)      # single informative feature
    p = {**FAST, "objective": "binary"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=5)
    imp_split = bst.feature_importance(importance_type="split")
    imp_gain = bst.feature_importance(importance_type="gain")
    # the informative feature dominates GAIN (split counts include the
    # tiny noise splits under the pure root partition)
    assert int(np.argmax(imp_gain)) == 2
    root = bst.dump_model()["tree_info"][0]["tree_structure"]
    assert root["split_feature"] == 2
    assert imp_split.sum() == sum(
        t["num_leaves"] - 1 for t in bst.dump_model()["tree_info"])


def test_monotone_constraint_with_bagging():
    """Monotone constraints hold under bagging (matrix cross; reference
    monotone_constraints.hpp under any sampling): predictions must be
    nondecreasing along the constrained feature."""
    rng = np.random.default_rng(14)
    n = 1200
    X = rng.uniform(-2, 2, size=(n, 4))
    y = 1.5 * X[:, 0] + np.sin(X[:, 1] * 3) + 0.2 * rng.normal(size=n)
    p = {**FAST, "objective": "regression",
         "monotone_constraints": [1, 0, 0, 0],
         "bagging_fraction": 0.7, "bagging_freq": 1}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=20)
    grid = np.linspace(-2, 2, 60)
    base_rows = X[:20].copy()
    for r in base_rows:
        probe = np.tile(r, (60, 1))
        probe[:, 0] = grid
        pr = bst.predict(probe)
        assert (np.diff(pr) >= -1e-10).all()


def test_cv_early_stopping_truncates_to_best():
    """cv + early_stopping truncates histories at the aggregate best
    iteration and sets CVBooster.best_iteration (reference cv contract:
    len(res[...]) is the round count to retrain with)."""
    X, y, extra, metric, base = _matrix_data("regression", seed=21)
    p = {**FAST, "objective": "regression", "metric": "l2"}
    res = lgb.cv(p, lgb.Dataset(X, label=y, params=p), num_boost_round=400,
                 nfold=3, callbacks=[lgb.early_stopping(5, verbose=False)],
                 seed=2, return_cvbooster=True)
    curve = res["valid l2-mean"]
    assert len(curve) < 400
    assert res["cvbooster"].best_iteration == len(curve)
    # the last entry is the minimum of the truncated curve
    assert curve[-1] == min(curve)
    assert len(res["valid l2-stdv"]) == len(curve)


# ---------------------------------------------------------------- round 4
# breadth additions (VERDICT r3 weak #4): objective variants the matrix
# missed, metric-ordering contracts, and edge geometries.


@pytest.mark.parametrize("objective", ["gamma", "tweedie"])
def test_regression_positive_objectives(objective):
    """gamma/tweedie on strictly-positive targets: deviance improves on
    the mean predictor and predictions stay positive (log-link,
    reference regression_objective.hpp Gamma/Tweedie)."""
    rng = np.random.default_rng(5)
    n = 1200
    X = rng.normal(size=(n, 6))
    mu = np.exp(0.8 * X[:, 0] - 0.5 * X[:, 1])
    y = rng.gamma(shape=2.0, scale=mu / 2.0) + 1e-3
    p = {**FAST, "objective": objective}
    if objective == "tweedie":
        p["tweedie_variance_power"] = 1.3
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=40)
    pred = bst.predict(X)
    assert (pred > 0).all()
    # squared error in log space beats the constant-mean predictor
    err = np.mean((np.log(pred) - np.log(mu)) ** 2)
    base = np.mean((np.log(np.full(n, y.mean())) - np.log(mu)) ** 2)
    assert err < 0.5 * base, (err, base)
    s = bst.model_to_string()
    np.testing.assert_allclose(lgb.Booster(model_str=s).predict(X), pred,
                               rtol=1e-5, atol=1e-7)


def test_quantile_alpha_ordering():
    """alpha=0.1 predictions sit below alpha=0.9 on heteroscedastic data
    and roughly bracket the right coverage fraction."""
    rng = np.random.default_rng(11)
    n = 3000
    X = rng.uniform(-1, 1, size=(n, 4))
    y = X[:, 0] + (0.5 + 0.5 * np.abs(X[:, 1])) * rng.normal(size=n)
    preds = {}
    for alpha in (0.1, 0.9):
        p = {**FAST, "objective": "quantile", "alpha": alpha}
        bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                        num_boost_round=60)
        preds[alpha] = bst.predict(X)
    assert (preds[0.9] >= preds[0.1] - 1e-6).mean() > 0.97
    cov_lo = (y <= preds[0.1]).mean()
    cov_hi = (y <= preds[0.9]).mean()
    assert 0.03 < cov_lo < 0.25, cov_lo
    assert 0.75 < cov_hi < 0.97, cov_hi


def test_first_metric_only_early_stopping(synthetic_binary):
    """first_metric_only: stopping follows the FIRST metric even when a
    second keeps improving (reference callback.py first_metric_only)."""
    X, y = synthetic_binary
    Xt, yt = X[:600], y[:600]
    Xv, yv = X[600:], y[600:]
    p = {**FAST, "objective": "binary", "metric": ["auc", "binary_logloss"],
         "first_metric_only": True}
    ds = lgb.Dataset(Xt, label=yt, params=p)
    dv = ds.create_valid(Xv, label=yv)
    ev = {}
    bst = lgb.train(p, ds, num_boost_round=200, valid_sets=[dv],
                    callbacks=[lgb.early_stopping(8, verbose=False,
                                                  first_metric_only=True),
                               lgb.record_evaluation(ev)])
    assert bst.best_iteration > 0
    aucs = ev["valid_0"]["auc"]
    # stopped 8 rounds after the auc peak, not the logloss one
    assert len(aucs) <= int(np.argmax(aucs)) + 1 + 8 + 1


def test_shap_additivity_regression(synthetic_regression):
    X, y = synthetic_regression
    p = {**FAST, "objective": "regression"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=25)
    contrib = bst.predict(X[:100], pred_contrib=True)
    assert contrib.shape == (100, X.shape[1] + 1)
    np.testing.assert_allclose(contrib.sum(axis=1), bst.predict(X[:100]),
                               rtol=1e-5, atol=1e-6)


def test_stump_and_tiny_geometries():
    """num_leaves=2 stumps and max_depth=1 both produce single-split
    trees that still learn; predictions reload exactly."""
    rng = np.random.default_rng(3)
    n = 800
    X = rng.normal(size=(n, 5))
    y = (X[:, 2] > 0.3).astype(np.float64)
    for geom in ({"num_leaves": 2}, {"max_depth": 1, "num_leaves": 15}):
        p = {**FAST, **geom, "objective": "binary"}
        bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                        num_boost_round=20)
        assert _auc(y, bst.predict(X)) > 0.9
        d = bst.dump_model()
        for t in d["tree_info"]:
            assert t["num_leaves"] <= 2
        s = bst.model_to_string()
        np.testing.assert_allclose(lgb.Booster(model_str=s).predict(X),
                                   bst.predict(X), rtol=1e-6)


def test_constant_label_stops_cleanly():
    """All-identical labels: no splittable gain anywhere; training still
    returns a usable model predicting the constant."""
    rng = np.random.default_rng(4)
    X = rng.normal(size=(500, 4))
    y = np.full(500, 3.25)
    p = {**FAST, "objective": "regression"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=5)
    np.testing.assert_allclose(bst.predict(X), 3.25, atol=1e-6)


def test_constant_feature_never_split():
    """A zero-variance column must never be chosen as a split feature
    (the reference drops it at bin-mapping time)."""
    rng = np.random.default_rng(6)
    n = 1500
    X = rng.normal(size=(n, 5))
    X[:, 3] = 7.0
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    p = {**FAST, "objective": "binary"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=15)
    assert bst.feature_importance()[3] == 0
    assert _auc(y, bst.predict(X)) > 0.85


def test_multi_valid_sets_independent_eval(synthetic_binary):
    """Two validation sets are evaluated independently each round and
    recorded under their own names."""
    X, y = synthetic_binary
    p = {**FAST, "objective": "binary", "metric": "binary_logloss"}
    ds = lgb.Dataset(X[:500], label=y[:500], params=p)
    v1 = ds.create_valid(X[500:750], label=y[500:750])
    v2 = ds.create_valid(X[750:], label=y[750:])
    ev = {}
    lgb.train(p, ds, num_boost_round=10, valid_sets=[v1, v2],
              valid_names=["a", "b"],
              callbacks=[lgb.record_evaluation(ev)])
    assert set(ev) == {"a", "b"}
    assert len(ev["a"]["binary_logloss"]) == 10
    assert ev["a"]["binary_logloss"] != ev["b"]["binary_logloss"]


def test_min_data_in_leaf_bounds_leaf_counts():
    """Every trained leaf respects min_data_in_leaf (reference
    CheckSplit min_data_in_leaf contract)."""
    rng = np.random.default_rng(8)
    n = 2000
    X = rng.normal(size=(n, 6))
    y = (X @ rng.normal(size=6) > 0).astype(np.float64)
    p = {**FAST, "objective": "binary", "min_data_in_leaf": 120}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=10)
    d = bst.dump_model()

    def leaf_counts(node, out):
        if "leaf_count" in node:
            out.append(node["leaf_count"])
        for k in ("left_child", "right_child"):
            if isinstance(node.get(k), dict):
                leaf_counts(node[k], out)
    for t in d["tree_info"]:
        out = []
        leaf_counts(t["tree_structure"], out)
        assert all(c >= 120 for c in out if c is not None), out


def test_bagging_fraction_counts_rows():
    """bagging_fraction=0.5: per-tree training row count is about half
    of n (visible through leaf_count sums at the root)."""
    rng = np.random.default_rng(9)
    n = 4000
    X = rng.normal(size=(n, 5))
    y = (X[:, 0] > 0).astype(np.float64)
    p = {**FAST, "objective": "binary", "bagging_fraction": 0.5,
         "bagging_freq": 1, "bagging_seed": 3}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=6)
    d = bst.dump_model()
    for t in d["tree_info"][1:]:   # tree 0 may boost from score
        out = []

        def walk(node):
            if "leaf_count" in node:
                out.append(node["leaf_count"])
            for k in ("left_child", "right_child"):
                if isinstance(node.get(k), dict):
                    walk(node[k])
        walk(t["tree_structure"])
        total = sum(out)
        assert 0.4 * n < total < 0.6 * n, total


def test_prediction_iteration_slicing_additive(synthetic_binary):
    """raw predictions over [0, a) + [a, b) slices equal the full [0, b)
    raw prediction (tree contributions are additive in raw space)."""
    X, y = synthetic_binary
    p = {**FAST, "objective": "binary"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=12)
    full = bst.predict(X[:200], raw_score=True, num_iteration=12)
    head = bst.predict(X[:200], raw_score=True, num_iteration=5)
    tail = bst.predict(X[:200], raw_score=True, start_iteration=5,
                       num_iteration=7)
    np.testing.assert_allclose(head + tail, full, rtol=1e-5, atol=1e-6)


def test_learning_rate_schedule_callback(synthetic_binary):
    """reset_parameter with a per-round learning-rate list: later trees
    shrink, visible through the leaf values of the dumped model."""
    X, y = synthetic_binary
    p = {**FAST, "objective": "binary"}
    rates = [0.3] * 5 + [0.003] * 5
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=10,
                    callbacks=[lgb.reset_parameter(learning_rate=rates)])
    d = bst.dump_model()

    def max_abs_leaf(t):
        out = []

        def walk(node):
            if "leaf_value" in node and "left_child" not in node:
                out.append(abs(node["leaf_value"]))
            for k in ("left_child", "right_child"):
                if isinstance(node.get(k), dict):
                    walk(node[k])
        walk(t["tree_structure"])
        return max(out)
    early = max(max_abs_leaf(t) for t in d["tree_info"][1:5])
    late = max(max_abs_leaf(t) for t in d["tree_info"][6:])
    assert late < early * 0.2, (early, late)


def test_pandas_categorical_roundtrip_prediction():
    """DataFrame categoricals: training categories are stored in the
    model, predict on a frame with the SAME categories in a different
    order maps through the stored list (reference pandas_categorical)."""
    pd = pytest.importorskip("pandas")
    rng = np.random.default_rng(7)
    n = 1200
    cat = rng.choice(["red", "green", "blue", "violet"], size=n)
    num = rng.normal(size=n)
    y = ((cat == "red") * 1.0 + 0.3 * num +
         0.1 * rng.normal(size=n) > 0.5).astype(np.float64)
    df = pd.DataFrame({"c": pd.Categorical(cat), "x": num})
    p = {**FAST, "objective": "binary"}
    bst = lgb.train(p, lgb.Dataset(df, label=y, params=p,
                                   categorical_feature=["c"]),
                    num_boost_round=15)
    pred = bst.predict(df)
    assert _auc(y, pred) > 0.85
    # same data, categories declared in a different order
    df2 = df.copy()
    df2["c"] = pd.Categorical(cat, categories=["violet", "blue", "green",
                                               "red"])
    np.testing.assert_allclose(bst.predict(df2), pred, rtol=1e-6)
    # save/load keeps the category mapping
    s = bst.model_to_string()
    np.testing.assert_allclose(lgb.Booster(model_str=s).predict(df2), pred,
                               rtol=1e-6)


def test_cv_custom_folds(synthetic_binary):
    """cv accepts explicit (train_idx, test_idx) folds and reports one
    curve over them."""
    X, y = synthetic_binary
    n = len(y)
    idx = np.arange(n)
    folds = [(idx[: n // 2], idx[n // 2:]), (idx[n // 2:], idx[: n // 2])]
    p = {**FAST, "objective": "binary", "metric": "binary_logloss"}
    res = lgb.cv(p, lgb.Dataset(X, label=y, params=p), num_boost_round=8,
                 folds=folds)
    assert len(res["valid binary_logloss-mean"]) == 8
    assert res["valid binary_logloss-mean"][-1] < \
        res["valid binary_logloss-mean"][0]


def test_dart_drop_rate_extremes(synthetic_binary):
    """drop_rate=0 behaves like gbdt (no drops); skip_drop=1 likewise."""
    X, y = synthetic_binary
    base = {**FAST, "objective": "binary", "learning_rate": 0.1}
    p_gbdt = {**base}
    p_skip = {**base, "boosting": "dart", "skip_drop": 1.0}
    ds = lambda pp: lgb.Dataset(X, label=y, params=pp)
    b1 = lgb.train(p_gbdt, ds(p_gbdt), num_boost_round=10)
    b2 = lgb.train(p_skip, ds(p_skip), num_boost_round=10)
    np.testing.assert_allclose(b2.predict(X[:50]), b1.predict(X[:50]),
                               rtol=1e-5, atol=1e-6)


def test_feature_name_plumbing(synthetic_binary):
    X, y = synthetic_binary
    names = [f"col_{i}" for i in range(X.shape[1])]
    p = {**FAST, "objective": "binary"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p,
                                   feature_name=names),
                    num_boost_round=5)
    assert bst.feature_name() == names
    d = bst.dump_model()
    assert d["feature_names"] == names
    s = bst.model_to_string()
    assert lgb.Booster(model_str=s).feature_name() == names


# ---------------------------------------------------------------------------
# combined-mode stress cells: features that each work alone must also
# compose (reference test_engine.py exercises these pairings across its
# grid; the failure mode is silent interaction bugs, e.g. a sampling
# mask not reaching the quantized-histogram path)

@pytest.mark.parametrize("boosting", ["gbdt", "dart"])
def test_weights_categorical_quantized_compose(boosting):
    """weights x categorical x quantized-gradients x {gbdt, dart} in one
    run, with metric floor + save/load equivalence (the widest single
    cell in the composition grid)."""
    rng = np.random.default_rng(11)
    n = 2000
    Xn = rng.normal(size=(n, 4)).astype(np.float32)
    Xc = rng.integers(0, 12, size=(n, 2)).astype(np.float32)
    X = np.concatenate([Xn, Xc], axis=1)
    logits = Xn[:, 0] + 0.8 * (Xc[:, 0] % 3 == 1) - 0.6 * (Xc[:, 1] > 7)
    y = (logits + rng.normal(scale=0.4, size=n) > 0).astype(np.float32)
    w = np.where(y > 0, 2.0, 1.0)
    params = {**FAST, "objective": "binary", "boosting": boosting,
              "categorical_feature": [4, 5],
              "use_quantized_grad": True, "num_grad_quant_bins": 16}
    if boosting == "dart":
        params["drop_rate"] = 0.2
    ds = lgb.Dataset(X, label=y, weight=w, params=params)
    bst = lgb.train(params, ds, num_boost_round=40)
    p = bst.predict(X)
    assert _auc(y, p) > 0.85
    s = bst.model_to_string()
    p2 = lgb.Booster(model_str=s).predict(X)
    np.testing.assert_allclose(p2, p, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("objective", ["multiclass", "regression"])
def test_init_score_nonbinary(objective):
    """init_score offsets the boosting start for multiclass (per-class
    column layout, reference Metadata::Init init_score n*k) and
    regression, not just binary (test_init_score_training above)."""
    rng = np.random.default_rng(5)
    n = 1200
    X = rng.normal(size=(n, 5)).astype(np.float32)
    if objective == "multiclass":
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(int) + \
            (X[:, 2] > 0.5).astype(int)
        params = {**FAST, "objective": "multiclass", "num_class": 3,
                  "metric": ["multi_logloss"]}
        # a deliberately WRONG init pushes everything toward class 0;
        # training must still recover (gradients see the offset).
        # Flatten CLASS-MAJOR (order="F"): the engine un-flattens n*k
        # init_score as reshape(-1, k, order="F"), the reference's
        # init_score[class * num_data + row] layout — a C-order flatten
        # here would stripe the bias across classes and cancel under
        # softmax
        init = np.zeros((n, 3), np.float64)
        init[:, 0] = 2.0
        ds = lgb.Dataset(X, label=y,
                         init_score=init.reshape(-1, order="F"),
                         params=params)
        bst = lgb.train(params, ds, num_boost_round=40)
        p = bst.predict(X)
        acc = float(np.mean(np.argmax(p, axis=1) == y))
        assert acc > 0.8
    else:
        y = (X[:, 0] * 2.0 + X[:, 1]).astype(np.float32) + 10.0
        params = {**FAST, "objective": "regression"}
        init = np.full(n, 10.0)
        ds = lgb.Dataset(X, label=y, init_score=init, params=params)
        bst = lgb.train(params, ds, num_boost_round=25)
        # like the reference, predict() does NOT include the
        # user-supplied init_score — the model learned the RESIDUAL
        # (y - 10); the caller re-adds the offset
        mse = float(np.mean((bst.predict(X) + 10.0 - y) ** 2))
        assert mse < 0.3 * float(np.var(y))


def test_goss_weights_saveload_equivalence():
    """GOSS's amplified small-gradient rows compose with user weights,
    and the trained model round-trips (reference GOSS strategy applies
    on TOP of metadata weights, sample_strategy.cpp)."""
    rng = np.random.default_rng(17)
    n = 3000
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + rng.normal(scale=0.3, size=n) > 0
         ).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n)
    params = {**FAST, "objective": "binary", "boosting": "goss",
              "top_rate": 0.3, "other_rate": 0.2}
    ds = lgb.Dataset(X, label=y, weight=w, params=params)
    bst = lgb.train(params, ds, num_boost_round=30)
    p = bst.predict(X)
    assert _auc(y, p) > 0.9
    p2 = lgb.Booster(model_str=bst.model_to_string()).predict(X)
    np.testing.assert_allclose(p2, p, rtol=1e-5, atol=1e-6)


def test_efb_quantized_compose():
    """EFB-bundled sparse exclusives train under quantized gradients:
    the bundle expansion tables and the integer histogram path must
    agree on bin offsets (dataset.cpp:246 bundling x quantized
    histograms — distinct subsystems in the reference too)."""
    rng = np.random.default_rng(23)
    n = 2500
    dense = rng.normal(size=(n, 3)).astype(np.float32)
    # 9 mutually-exclusive indicator columns -> EFB bundles them
    which = rng.integers(0, 9, size=n)
    sparse = np.zeros((n, 9), np.float32)
    sparse[np.arange(n), which] = 1.0
    X = np.concatenate([dense, sparse], axis=1)
    y = (dense[:, 0] + 0.7 * (which % 3 == 0) > 0.3).astype(np.float32)
    params = {**FAST, "objective": "binary", "enable_bundle": True,
              "use_quantized_grad": True}
    ds = lgb.Dataset(X, label=y, params=params)
    bst = lgb.train(params, ds, num_boost_round=30)
    assert _auc(y, bst.predict(X)) > 0.9


def test_device_predict_parity_paths(monkeypatch):
    """Large predictions batch on the device (GBDT._device_predict_raw):
    the matmul path-aggregation predictor (numeric models) and the
    frontier-walk fallback (categorical models) must both reproduce the
    host f64 walk within f32 rounding, NaN rows included."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 0)
    rng = np.random.default_rng(21)
    n = 4000

    # numeric (matmul predictor)
    X = rng.normal(size=(n, 6)).astype(np.float64)
    X[::41, 2] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 2])
         + rng.normal(scale=0.4, size=n) > 0).astype(np.float64)
    p = {**FAST, "objective": "binary"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=12)
    gb = bst._gbdt
    dev = gb.predict_raw(X)
    monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 1 << 62)
    host = gb.predict_raw(X)
    np.testing.assert_allclose(dev, host, rtol=2e-5, atol=2e-6)

    # categorical models take the BITSET device path (round 5): unseen
    # categories, negative codes and NaN rows must match the host
    # raw-space walk (sentinel bins in bin_external_pred)
    monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 0)
    Xc = np.concatenate(
        [rng.normal(size=(n, 3)),
         rng.integers(0, 9, size=(n, 1)).astype(float)], axis=1)
    yc = (Xc[:, 0] + (Xc[:, 3] % 3 == 1)
          + rng.normal(scale=0.4, size=n) > 0.5).astype(np.float64)
    pc = {**FAST, "objective": "binary", "categorical_feature": [3]}
    bc = lgb.train(pc, lgb.Dataset(Xc, label=yc, params=pc),
                   num_boost_round=12)
    Xc_test = Xc.copy()
    Xc_test[::7, 3] = 50.0          # category unseen at training time
    Xc_test[::11, 3] = np.nan
    Xc_test[::13, 3] = -3.0         # negative code -> NaN-like (right)
    gbc = bc._gbdt
    devc = gbc.predict_raw(Xc_test)
    monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 1 << 62)
    hostc = gbc.predict_raw(Xc_test)
    np.testing.assert_allclose(devc, hostc, rtol=2e-5, atol=2e-6)

    # EFB-bundled numeric model: the bitset device path over LOGICAL bins
    monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 0)
    which = rng.integers(0, 9, size=n)
    Xb = np.zeros((n, 9 + 2))
    Xb[:, :2] = rng.normal(size=(n, 2))
    Xb[np.arange(n), 2 + which] = 1.0
    yb = (Xb[:, 0] + 0.6 * (which % 3 == 0)
          + rng.normal(scale=0.3, size=n) > 0.3).astype(np.float64)
    pb = {**FAST, "objective": "binary", "enable_bundle": True}
    bb = lgb.train(pb, lgb.Dataset(Xb, label=yb, params=pb),
                   num_boost_round=12)
    gbb = bb._gbdt
    if gbb.bundle is not None:
        devb = gbb.predict_raw(Xb)
        monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 1 << 62)
        hostb = gbb.predict_raw(Xb)
        np.testing.assert_allclose(devb, hostb, rtol=2e-5, atol=2e-6)

    # linear-leaf model: const + coeff·x with per-leaf NaN fallback
    monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 0)
    pl = {**FAST, "objective": "regression", "linear_tree": True}
    yl = X[:, 0] * 1.5 + np.nan_to_num(X[:, 1]) * 0.5 \
        + rng.normal(scale=0.2, size=n)
    bl = lgb.train(pl, lgb.Dataset(X, label=yl, params=pl),
                   num_boost_round=8)
    gbl = bl._gbdt
    devl = gbl.predict_raw(X)
    monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 1 << 62)
    hostl = gbl.predict_raw(X)
    np.testing.assert_allclose(devl, hostl, rtol=2e-4, atol=2e-4)

    # multiclass columns route to the right classes
    monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 0)
    ym = ((X[:, 0] > 0).astype(int) + (np.nan_to_num(X[:, 1]) > 0.5)
          .astype(int))
    pm = {**FAST, "objective": "multiclass", "num_class": 3}
    bm = lgb.train(pm, lgb.Dataset(X, label=ym, params=pm),
                   num_boost_round=8)
    gbm = bm._gbdt
    devm = gbm.predict_raw(X)
    monkeypatch.setattr(GBDT, "DEVICE_PREDICT_MIN_WORK", 1 << 62)
    hostm = gbm.predict_raw(X)
    np.testing.assert_allclose(devm, hostm, rtol=2e-5, atol=2e-6)
