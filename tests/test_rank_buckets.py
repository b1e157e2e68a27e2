"""Query-bucketed device-resident ranking (objectives.py bucket plan).

Acceptance surface for the bucketed lambdarank/xendcg kernels: bucketed
gradients match the pad-to-max layout (``rank_query_buckets=[qmax]``)
across the truncation x norm x position-bias x xendcg grid,
a skewed query-length fixture pads strictly fewer rows than pad-to-max,
identical bucket geometry across boosters is a pure
``rank_compile_hits`` path, position-debiased training stays on the
jitted program with bias factors surviving kill/resume bit-identically.

The parity contract is tight allclose, NOT bitwise: XLA reassociates
the pairwise reductions shape-dependently, so bucketed and pad-to-max
programs sum identical pair lambdas in different orders (observed max
|delta g| ~5e-7 on integer-valued-f32 fixtures).
"""

import contextlib

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.obs import compile_events
from lightgbm_tpu.obs.metrics import global_metrics


GRAD_TOL = dict(rtol=3e-6, atol=6e-7)


def _pad_to_max(bounds):
    """``rank_query_buckets`` for the pad-to-max layout: one bucket at the
    longest query's length, stated through the registered key (the
    ``LGBMTPU_NO_RANK_BUCKETS`` hatch that used to state it is gone)."""
    return [int(np.diff(np.asarray(bounds)).max())]


def _skewed(n=900, f=4, seed=0):
    """Skewed (lognormal) query lengths with integer-valued-f32 labels
    0..4 — every input exactly representable, so any parity drift is the
    kernels', not the fixture's."""
    rng = np.random.RandomState(seed)
    sizes = []
    rem = n
    while rem > 0:
        s = int(np.clip(rng.lognormal(2.2, 0.8), 2, 120))
        s = min(s, rem)
        sizes.append(s)
        rem -= s
    sizes = np.asarray(sizes, np.int64)
    bounds = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    y = np.concatenate([
        np.minimum(4, (rng.permutation(s) * 5) // max(s, 1))
        for s in sizes]).astype(np.float32)
    X = rng.standard_normal((n, f)).astype(np.float32)
    return X, y, sizes, bounds


class _Meta:
    pass


def _make_obj(objective, bounds, y, *, trunc=30, norm=True, position=None,
              no_buckets=False, buckets="auto", seed=5, verbose=-1):
    if no_buckets:
        buckets = _pad_to_max(bounds)
    cfg = Config({"objective": objective, "verbose": verbose,
                  "lambdarank_truncation_level": trunc,
                  "lambdarank_norm": norm,
                  "rank_query_buckets": buckets,
                  "objective_seed": seed})
    m = _Meta()
    m.label = y
    m.weight = None
    m.query_boundaries = np.asarray(bounds)
    m.position = position
    obj = create_objective(cfg)
    obj.init(m, len(y))
    return obj


def _positions_for(sizes, seed=11):
    rng = np.random.RandomState(seed)
    return np.concatenate([rng.permutation(int(s)) % 10 for s in sizes])


# ------------------------------------------------------------ parity grid

@pytest.mark.parametrize("objective,trunc,norm,with_pos", [
    ("lambdarank", 5, True, False),
    ("lambdarank", 5, False, False),
    ("lambdarank", 30, True, False),
    ("lambdarank", 30, False, False),
    ("lambdarank", 10, True, True),
    ("rank_xendcg", 30, True, False),
])
def test_bucketed_matches_pad_to_max(objective, trunc, norm, with_pos):
    """Bucketed gradients == pad-to-max gradients at tight allclose over
    three gradient iterations (the third exercises carried state: the
    Newton position-bias carry for lambdarank, the RNG stream for
    xendcg)."""
    _, y, sizes, bounds = _skewed(seed=trunc)
    pos = _positions_for(sizes) if with_pos else None
    a = _make_obj(objective, bounds, y, trunc=trunc, norm=norm,
                  position=pos, no_buckets=False)
    b = _make_obj(objective, bounds, y, trunc=trunc, norm=norm,
                  position=pos, no_buckets=True)
    assert a._rank_bucket_count > 1, "fixture produced a trivial ladder"
    assert b._rank_bucket_count == 1
    rng = np.random.RandomState(3)
    score = jnp.asarray(rng.standard_normal(len(y)).astype(np.float32))
    for _ in range(3):
        ga, ha = a.jitted_gradients(score)
        gb, hb = b.jitted_gradients(score)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                   **GRAD_TOL)
        np.testing.assert_allclose(np.asarray(ha), np.asarray(hb),
                                   **GRAD_TOL)
        score = score - 0.1 * ga
    if with_pos:
        np.testing.assert_allclose(np.asarray(a._pos_biases_dev),
                                   np.asarray(b._pos_biases_dev),
                                   rtol=3e-6, atol=2e-6)
        assert np.abs(np.asarray(a._pos_biases_dev)).max() > 0


def test_explicit_bucket_list_extends_to_qmax():
    """An explicit ``rank_query_buckets`` ladder that undershoots the
    longest query is extended to cover it, and the gradients still match
    the auto ladder."""
    _, y, _, bounds = _skewed(seed=2)
    qmax = int(np.diff(bounds).max())
    pinned = _make_obj("lambdarank", bounds, y, buckets=[8, 64])
    auto = _make_obj("lambdarank", bounds, y, buckets="auto")
    caps = [cap for cap, _, _ in pinned._buckets]
    assert set(caps) <= {8, 64, qmax} and caps[-1] >= qmax
    score = jnp.asarray(np.linspace(-1, 1, len(y), dtype=np.float32))
    gp, hp = pinned.jitted_gradients(score)
    ga, ha = auto.jitted_gradients(score)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(ga), **GRAD_TOL)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(ha), **GRAD_TOL)


# --------------------------------------------------- pad-waste telemetry

def test_skewed_fixture_pads_strictly_less_than_pad_to_max():
    _, y, sizes, bounds = _skewed(seed=4)
    bucketed = _make_obj("lambdarank", bounds, y, no_buckets=False)
    padded = _make_obj("lambdarank", bounds, y, no_buckets=True)
    qmax = int(sizes.max())
    assert padded._rank_pad_rows == len(sizes) * qmax - int(sizes.sum())
    assert bucketed._rank_pad_rows < padded._rank_pad_rows
    assert bucketed._rank_bucket_count > 1
    # the process gauges mirror the most recent plan
    assert global_metrics.gauge("rank_pad_rows") == \
        padded._rank_pad_rows
    assert global_metrics.gauge("rank_bucket_count") == 1


# ------------------------------------------------------ compile caching

def test_identical_geometry_is_pure_cache_hit():
    """A second objective over identical bucket geometry re-enters the
    cached rank program: zero new ``rank_compile_misses``."""
    _, y, _, bounds = _skewed(seed=6)
    score = jnp.asarray(np.linspace(-0.5, 0.5, len(y), dtype=np.float32))
    first = _make_obj("lambdarank", bounds, y, trunc=12)
    first.jitted_gradients(score)
    misses = global_metrics.counter("rank_compile_misses")
    hits = global_metrics.counter("rank_compile_hits")
    second = _make_obj("lambdarank", bounds, y, trunc=12)
    for _ in range(2):
        second.jitted_gradients(score)
    assert global_metrics.counter("rank_compile_misses") == misses
    assert global_metrics.counter("rank_compile_hits") >= hits + 2


def test_xendcg_identical_geometry_is_pure_cache_hit():
    _, y, _, bounds = _skewed(seed=7)
    score = jnp.zeros(len(y), jnp.float32)
    _make_obj("rank_xendcg", bounds, y).jitted_gradients(score)
    misses = global_metrics.counter("rank_compile_misses")
    _make_obj("rank_xendcg", bounds, y).jitted_gradients(score)
    assert global_metrics.counter("rank_compile_misses") == misses


# ----------------------------------------- jit-safe position debiasing

def test_position_debiased_training_is_jit_stable(synthetic_ranking):
    """Position-debiased lambdarank trains entirely under the cached
    jitted program: after the first iteration's lowerings, iterations
    2..N lower ZERO new XLA programs (the bias carry is a traced
    argument, not a re-trace trigger)."""
    assert compile_events.install() or compile_events.installed()
    X, y, group = synthetic_ranking
    rng = np.random.default_rng(11)
    position = np.concatenate([rng.permutation(20) % 10 for _ in group])
    p = {"objective": "lambdarank", "num_leaves": 15, "min_data_in_leaf": 5,
         "verbose": -1, "learning_rate": 0.15,
         "lambdarank_position_bias_regularization": 0.1}
    ds = lgb.Dataset(X, label=y, group=group, position=position, params=p)
    bst = lgb.train(p, ds, num_boost_round=2)
    g = bst._gbdt
    assert g.objective._positions is not None
    base = global_metrics.counter("xla_program_lowerings")
    for _ in range(3):
        g.train_one_iter()
    delta = int(global_metrics.counter("xla_program_lowerings") - base)
    assert delta == 0, \
        f"iterations 2..N lowered {delta} new programs — the " \
        "position-bias carry is re-tracing the rank gradient program"
    # the Newton carry moved and the host mirror tracks the device array
    dev = np.asarray(g.objective._pos_biases_dev)
    assert np.abs(dev).max() > 0
    np.testing.assert_array_equal(dev, g.objective._pos_biases
                                  .astype(np.float32))


def test_checkpoint_resume_restores_bias_bit_identical(
        synthetic_ranking, tmp_path):
    """Kill/resume restores the position-bias factors bit-identically:
    the checkpoint carries the device f32 carry verbatim and
    ``resume='auto'`` reinstalls it without a round-trip through f64."""
    from lightgbm_tpu.robustness import load_latest_checkpoint
    X, y, group = synthetic_ranking
    rng = np.random.default_rng(23)
    position = np.concatenate([rng.permutation(20) % 10 for _ in group])
    ck = str(tmp_path / "ck")
    p = {"objective": "lambdarank", "num_leaves": 7, "min_data_in_leaf": 5,
         "verbose": -1, "seed": 7, "checkpoint_dir": ck,
         "checkpoint_interval": 2,
         "lambdarank_position_bias_regularization": 0.1}
    ds = lgb.Dataset(X, label=y, group=group, position=position, params=p)
    bst = lgb.train(p, ds, num_boost_round=4)
    want = np.asarray(bst._gbdt.objective._pos_biases_dev)
    assert np.abs(want).max() > 0
    st = load_latest_checkpoint(ck)
    assert st is not None and st.iteration == 4
    assert st.pos_biases is not None
    np.testing.assert_array_equal(
        np.asarray(st.pos_biases, np.float32), want)
    # a fresh process resuming at the checkpointed round count carries
    # the exact bias vector (bitwise — no arithmetic ran in between)
    ds2 = lgb.Dataset(X, label=y, group=group, position=position, params=p)
    bst2 = lgb.train(p, ds2, num_boost_round=4, resume="auto")
    got = np.asarray(bst2._gbdt.objective._pos_biases_dev)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------- qmax warning

@contextlib.contextmanager
def capture_logs():
    from lightgbm_tpu.utils.log import get_verbosity, set_verbosity
    msgs = []
    prev = get_verbosity()
    set_verbosity(0)  # a prior verbose=-1 Config must not mute warnings
    lgb.register_logger(msgs.append)
    try:
        yield msgs
    finally:
        lgb.register_logger(None)
        set_verbosity(prev)


def test_long_query_warning_only_when_bucketing_disabled():
    n = 2100 + 60
    sizes = np.asarray([2100] + [20] * 3, np.int64)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    y = (np.arange(n) % 5).astype(np.float32)
    with capture_logs() as msgs:
        _make_obj("lambdarank", bounds, y, no_buckets=False,
                  verbose=0)
    assert not any("pad-to-max" in m for m in msgs)
    with capture_logs() as msgs:
        _make_obj("lambdarank", bounds, y, no_buckets=True, verbose=0)
    warned = [m for m in msgs if "pad-to-max" in m]
    assert warned and "rank_query_buckets" in warned[0]


# --------------------------------------------------- end-to-end parity

def test_ndcg_history_matches_across_arms(synthetic_ranking):
    """Training + the fused ndcg eval agree between the bucketed and
    pad-to-max arms (loose tolerance: per-round f32 ulp drift in the
    gradients can compound through split selection)."""
    X, y, group = synthetic_ranking
    hists = {}
    for arm, flag in (("bucketed", False), ("padded", True)):
        p = {"objective": "lambdarank", "num_leaves": 15,
             "min_data_in_leaf": 5, "verbose": -1, "learning_rate": 0.15,
             "metric": ["ndcg"], "eval_at": [5], "seed": 7}
        if flag:
            p["rank_query_buckets"] = [int(np.max(group))]
        ds = lgb.Dataset(X, label=y, group=group, params=p)
        res = {}
        lgb.train(p, ds, num_boost_round=5, valid_sets=[ds],
                  callbacks=[lgb.record_evaluation(res)])
        hists[arm] = np.asarray(res["training"]["ndcg@5"])
    np.testing.assert_allclose(hists["bucketed"], hists["padded"],
                               rtol=1e-3, atol=1e-4)


# ------------------------------------- the slot layout, against the parent's

def _parents_pair_accum(score, label, gain_doc, qidx, inv_dcg,
                           g_acc, h_acc, *, sigmoid: float, trunc: int,
                           norm: bool):
    """The gradients of one bucket as the parent of PR 38 (a645479)
    formulated them, word for word: gains and labels gathered through
    ``qidx``, two ``argsort``s, six ``take_along_axis``, two scatter-adds.
    Kept here as what the slot layout is held against."""
    s = sigmoid
    valid = qidx >= 0
    safe = jnp.maximum(qidx, 0)
    sc = jnp.where(valid, score[safe], -jnp.inf)      # [nq_b, Q]
    gains = jnp.where(valid, gain_doc[safe], 0.0)
    lbl = jnp.where(valid, label[safe], -1.0)

    # rank of each doc by descending score (ties by index, like ref sort)
    order = jnp.argsort(-sc, axis=1, stable=True)      # positions -> doc slot
    rank = jnp.argsort(order, axis=1)                  # doc slot -> position

    # -- truncation-aware pair enumeration in SORTED space.  The
    # reference (rank_objective.hpp:138-292) iterates i over sorted
    # positions [0, trunc) and j over (i, cnt): every pair has its
    # higher-scored member inside the truncation level, so the pair set
    # is O(Q * trunc), not O(Q^2).  Materializing [nq, T, Q] instead of
    # [nq, Q, Q] is what makes MS-LTR-scale query lengths (thousands of
    # docs) fit in memory (VERDICT r1 #7).
    Q = sc.shape[1]
    T = int(min(trunc, Q))
    s_srt = jnp.take_along_axis(sc, order, axis=1)      # [nq_b, Q] desc
    g_srt = jnp.take_along_axis(gains, order, axis=1)
    l_srt = jnp.take_along_axis(lbl, order, axis=1)
    v_srt = jnp.take_along_axis(valid, order, axis=1)
    disc = 1.0 / jnp.log2(jnp.arange(Q, dtype=jnp.float32) + 2.0)  # [Q]
    inv = inv_dcg[:, None, None]                         # [nq_b, 1, 1]

    sa = s_srt[:, :T, None]                              # [nq_b, T, 1]
    sb = s_srt[:, None, :]                               # [nq_b, 1, Q]
    ga_ = g_srt[:, :T, None]
    gb_ = g_srt[:, None, :]
    la_ = l_srt[:, :T, None]
    lb_ = l_srt[:, None, :]
    delta = jnp.abs((ga_ - gb_)
                    * (disc[None, :T, None] - disc[None, None, :])) \
        * inv                                            # [nq_b, T, Q]
    # each unordered pair once: position b strictly below position a
    tri = (jnp.arange(Q)[None, None, :]
           > jnp.arange(T)[None, :, None])
    pair_ok = (la_ != lb_) & tri & v_srt[:, :T, None] & v_srt[:, None, :]

    a_better = la_ > lb_
    diff_hl = jnp.where(a_better, sa - sb, sb - sa)      # s_high - s_low
    diff_hl = jnp.clip(diff_hl, -50.0 / s, 50.0 / s)
    rho = 1.0 / (1.0 + jnp.exp(s * diff_hl))
    lam = -s * rho * delta                    # dL/ds for the better doc
    hes = s * s * rho * (1.0 - rho) * delta
    lam = jnp.where(pair_ok, lam, 0.0)
    hes = jnp.where(pair_ok, hes, 0.0)

    # accumulate onto sorted positions: a gets +/-lam per label order,
    # b the negation; hessians add on both ends
    g_a = jnp.where(a_better, lam, -lam)
    g_pos = jnp.zeros_like(s_srt).at[:, :T].add(jnp.sum(g_a, axis=2))
    g_pos = g_pos - jnp.sum(g_a, axis=1)
    h_pos = jnp.zeros_like(s_srt).at[:, :T].add(jnp.sum(hes, axis=2))
    h_pos = h_pos + jnp.sum(hes, axis=1)

    if norm:
        # reference norm_: scale by log2(1 + |sum lambda|) / |sum lambda|
        sum_lam = jnp.sum(jnp.abs(lam), axis=(1, 2))
        nf = jnp.where(sum_lam > 0,
                       jnp.log2(1.0 + sum_lam) / jnp.maximum(sum_lam, 1e-20),
                       1.0)
        g_pos = g_pos * nf[:, None]
        h_pos = h_pos * nf[:, None]

    # sorted positions back to padded doc slots
    g_doc = jnp.take_along_axis(g_pos, rank, axis=1)
    h_doc = jnp.take_along_axis(h_pos, rank, axis=1)

    g_acc = g_acc.at[safe.reshape(-1)].add(
        jnp.where(valid, g_doc, 0.0).reshape(-1))
    h_acc = h_acc.at[safe.reshape(-1)].add(
        jnp.where(valid, h_doc, 0.0).reshape(-1))
    return g_acc, h_acc




def _parents_gradients(obj, score):
    y = np.asarray(obj.metadata.label)
    lbl = jnp.asarray(y, jnp.float32)
    gain_doc = jnp.asarray(obj._label_gain[y.astype(int)], jnp.float32)
    g = jnp.zeros_like(score)
    h = jnp.zeros_like(score)
    for _, qidx, inv in obj._buckets:
        g, h = _parents_pair_accum(
            score, lbl, gain_doc, qidx, inv, g, h,
            sigmoid=float(obj.config.sigmoid),
            trunc=int(obj.config.lambdarank_truncation_level),
            norm=bool(obj.config.lambdarank_norm))
    return g, h


def _slot_case(name):
    """``(sizes, labels, scores)`` of a small ranking set that holds the
    named corner."""
    rng = np.random.RandomState(17)
    if name == "ties":
        # many equal scores, also between docs of different labels: the
        # order among them is the index order, in both formulations
        sizes = [7, 12, 33, 5]
        y = rng.randint(0, 5, sum(sizes))
        s = rng.randint(0, 3, sum(sizes)).astype(np.float32)
    elif name == "single_doc_queries":
        sizes = [1, 9, 1, 1, 20, 1]
        y = rng.randint(0, 5, sum(sizes))
        s = rng.standard_normal(sum(sizes)).astype(np.float32)
    elif name == "no_relevant_doc":
        sizes = [6, 11, 40, 3]
        y = rng.randint(0, 5, sum(sizes))
        y[6:17] = 0                     # the second query: all labels 0
        y[57:60] = 2                    # the last: all equal, no pair either
        s = rng.standard_normal(sum(sizes)).astype(np.float32)
    else:
        assert name == "bucket_with_one_query"
        sizes = [4, 5, 6, 100, 7, 3]    # the 128 rung holds one query
        y = rng.randint(0, 5, sum(sizes))
        s = rng.standard_normal(sum(sizes)).astype(np.float32)
    return np.asarray(sizes), y.astype(np.float32), s


@pytest.mark.parametrize("trunc,norm", [(30, True), (3, False)])
@pytest.mark.parametrize("case", ["ties", "single_doc_queries",
                                  "no_relevant_doc",
                                  "bucket_with_one_query"])
def test_slot_layout_matches_the_parents_formulation(case, trunc, norm):
    """Gains and labels by slot, one variadic sort a bucket and one gather
    through ``slot_of_doc`` give the gradients of the parent's
    gather / argsort / scatter-add formulation, on the corners where a
    layout goes wrong first."""
    sizes, y, s = _slot_case(case)
    bounds = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    obj = _make_obj("lambdarank", bounds, y, trunc=trunc, norm=norm)
    if case == "bucket_with_one_query":
        assert 1 in [int(q.shape[0]) for _, q, _ in obj._buckets]
    if case == "single_doc_queries":
        assert obj._rank_bucket_count > 1
    score = jnp.asarray(s)
    g, h = obj.get_gradients(score)
    g0, h0 = _parents_gradients(obj, score)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g0), **GRAD_TOL)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h0), **GRAD_TOL)
    # every doc's slot is its own: what comes back is a permutation
    slots = np.asarray(obj._rank_state[1])
    assert len(np.unique(slots)) == len(y)
    if case == "no_relevant_doc":
        assert not np.asarray(g)[6:17].any() and not np.asarray(h)[6:17].any()
        assert not np.asarray(g)[57:60].any()
    if case == "single_doc_queries":
        assert float(np.asarray(g)[0]) == 0.0 == float(np.asarray(h)[0])
