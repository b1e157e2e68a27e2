"""Round-6 histogram formulations: packed-bin compares, shared radix
planes, fused-round glue — bit-identity and dispatch contracts.

VERDICT r5 #1 concluded the one-hot contraction build is
formulation-bound (~21% of int8 peak, 32-bit vector compares), so round
6 changes the comparison itself: ``hist_kernel=packed`` packs 4 uint8
bins per i32 lane and SWAR-compares 4 features per op;
``hist_kernel=radix2`` builds hi/lo nibble one-hots once per row block
and reuses them across all K split-batch leaf channels.  The contract
that makes the modes shippable is BIT-identity with the flat one-hot
reference on the same inputs — these tests pin it across the A/B
fixture grid (63/255 bins x NaN x EFB x int8 x K>1) through the Pallas
interpreter (this suite runs off-TPU; ``_MODE_TEST_INTERPRET`` routes
the mode kernels through ``interpret=True``).
"""

import os

import jax.numpy as jnp
import numpy as np
import numpy.testing as npt
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.ops.histogram as hist_mod
from lightgbm_tpu.ops.hist_pallas import (histogram_leaves_packed_pallas,
                                          histogram_leaves_pallas,
                                          histogram_leaves_radix2_pallas,
                                          radix2_pick_p)
from lightgbm_tpu.ops.histogram import (HIST_KERNELS, bins_to_words,
                                        hist_dispatch, resolve_hist_kernel)
from lightgbm_tpu.utils.log import LightGBMError

FAST = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
        "verbose": -1, "learning_rate": 0.2}


@pytest.fixture
def interpret_modes(monkeypatch):
    """Route the mode kernels through the Pallas interpreter so the CPU
    suite exercises the REAL packed/radix2/flat kernel code paths."""
    monkeypatch.setattr(hist_mod, "_MODE_TEST_INTERPRET", True)


def _fixture(n_bins, K, num_f, n, seed):
    """One A/B histogram problem: bins hit the full width INCLUDING the
    top (NaN) bin, rows outside the leaf set, invalid leaf ids.

    grad/hess are INTEGER-VALUED f32 (the test_round_fuse._mk idiom):
    every mode accumulates the identical per-row summands, so with
    integer values the sums are exact under ANY reduction order and a
    single flipped bit proves a formulation bug, not backend summation
    reassociation.  (XLA CPU reassociates f32 dot reductions
    shape-dependently — real-float cross-SHAPE parity is a TPU property
    of the MXU's fixed sequential-K order, docs/PERF_NOTES.md round 6.)"""
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins, (n, num_f)).astype(np.uint8)
    bins[rng.random((n, num_f)) < 0.05] = n_bins - 1   # NaN-bin rows
    grad = rng.integers(-8, 8, n).astype(np.float32)
    hess = rng.integers(0, 8, n).astype(np.float32)
    lor = rng.integers(-1, K + 2, n).astype(np.int32)
    leaves = rng.choice(K + 2, K, replace=False).astype(np.int32)
    return (jnp.asarray(bins), jnp.asarray(bins.T), jnp.asarray(grad),
            jnp.asarray(hess), jnp.asarray(lor), jnp.asarray(leaves))


@pytest.mark.parametrize("n_bins", [64, 256])   # device widths of 63/255
@pytest.mark.parametrize("K", [1, 5])
@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_packed_and_radix2_bit_identical_to_onehot(n_bins, K, dtype):
    """The mode kernels reproduce the flat one-hot kernel BIT-for-bit:
    same masked value channels, same accumulator dtype contract, across
    bin widths x leaf-channel counts x compute dtypes (int8 = quantized
    gradient levels, exact i32 accumulation)."""
    num_f, n = 9, 700
    bins, bins_t, grad, hess, lor, leaves = _fixture(
        n_bins, K, num_f, n, seed=n_bins + K)
    cd = jnp.dtype(dtype).type
    ref = histogram_leaves_pallas(
        bins_t, grad, hess, lor, leaves, n_bins=n_bins,
        rows_per_block=256, compute_dtype=cd, interpret=True)
    words_t = bins_to_words(bins).T
    packed = histogram_leaves_packed_pallas(
        words_t, grad, hess, lor, leaves, num_f=num_f, n_bins=n_bins,
        rows_per_block=256, compute_dtype=cd, interpret=True)
    npt.assert_array_equal(np.asarray(ref), np.asarray(packed))
    p = radix2_pick_p(num_f, K, n_bins)
    assert p > 0
    radix2 = histogram_leaves_radix2_pallas(
        bins_t, grad, hess, lor, leaves, n_bins=n_bins,
        rows_per_block=256, p=p, compute_dtype=cd, interpret=True)
    npt.assert_array_equal(np.asarray(ref), np.asarray(radix2))


def test_dispatch_routes_modes(interpret_modes):
    """histogram_for_leaves_masked honors hist_kernel and stays
    bit-identical through the DISPATCH layer (mirror plumbed the way the
    growers plumb it)."""
    n_bins, K, num_f, n = 64, 3, 8, 500
    bins, bins_t, grad, hess, lor, leaves = _fixture(
        n_bins, K, num_f, n, seed=7)
    words_t = bins_to_words(bins).T
    out = {}
    for hk in ("onehot", "packed", "radix2"):
        out[hk] = np.asarray(hist_mod.histogram_for_leaves_masked(
            bins_t, grad, hess, lor, leaves, None, n_bins=n_bins,
            rows_per_block=256, hist_dtype="float32", hist_kernel=hk,
            bins_words_t=words_t))
    npt.assert_array_equal(out["onehot"], out["packed"])
    npt.assert_array_equal(out["onehot"], out["radix2"])


# the dispatch table, case by case: (mode, n_bins, K, F, mirror resident,
# one-leaf entry) -> (kernel, wants the mirror, warm-up ladder pays, the
# row ladder's top rung n / top_rung) on a TPU.  The first nine are what
# the benchmark's cells execute (F = 67, K = 42 with the warm-up widths 1,
# 4, 16 before it at 256 bins); a kernel PR that changes a row changes
# what a cell runs and has to say so here.  PR 33: the K = 16 and K = 42
# bodies at 256 bins start their row ladder at n/2 (a round pass selects
# at most half the rows: the flat and radix2 kernels are the fallback).
_DISPATCH = [
    ("auto", 256, 1, 67, False, True, "radix_single", False, True, 4),
    ("auto", 256, 1, 67, False, False, "radix_joint", False, True, 4),
    ("auto", 256, 4, 67, False, False, "radix_joint", False, True, 4),
    ("auto", 256, 8, 67, False, False, "radix2", False, True, 2),
    ("auto", 256, 16, 67, False, False, "radix2", False, True, 2),
    ("auto", 256, 42, 67, False, False, "flat", False, True, 2),
    ("auto", 64, 1, 67, True, True, "packed", True, False, 4),
    ("auto", 64, 42, 67, True, False, "packed", True, False, 4),
    ("auto", 64, 42, 67, False, False, "flat", True, False, 4),
    # 255 is no multiple of 16: no radix kernel, the sub-128-bin route
    ("auto", 255, 42, 67, True, False, "packed", True, False, 4),
    ("auto", 255, 1, 67, False, True, "flat", True, False, 2),
    ("auto", 256, 42, 28, True, False, "radix2", False, True, 2),
    # explicit modes force their kernel where its shape constraints hold
    ("onehot", 64, 5, 28, True, False, "flat", False, False, 4),
    ("onehot", 256, 1, 28, False, True, "flat", False, False, 2),
    ("packed", 256, 5, 28, True, False, "packed", True, False, 4),
    ("packed", 256, 5, 28, False, False, "flat", True, False, 2),
    ("radix2", 256, 5, 28, True, False, "radix2", False, False, 2),
    ("radix2", 60, 5, 28, True, False, "flat", False, False, 4),   # % 16
    # accumulator cap: a huge (K, F) product overflows the VMEM budget
    # and radix2 falls back rather than compiling an unshippable kernel
    ("radix2", 256, 512, 4096, True, False, "flat", False, False, 2),
    # the cell epsilon-train (PR 45: 2,000 columns): the root and the
    # K <= 4 bodies keep their radix kernels over eight column blocks, and
    # every K > 4 pass is the flat kernel (a shared-radix accumulator of
    # 2,000 columns is 196 MB at K = 16: no single block of 8 MiB)
    ("auto", 256, 1, 2000, False, True, "radix_single", False, True, 4),
    ("auto", 256, 4, 2000, False, False, "radix_joint", False, True, 4),
    ("auto", 256, 16, 2000, False, False, "flat", False, True, 2),
    ("auto", 256, 42, 2000, False, False, "flat", False, True, 2),
]
_DISPATCH_ARGS = "hk,n_bins,K,num_f,words,single,kernel,mirror,ladder,top_rung"


@pytest.mark.parametrize(_DISPATCH_ARGS, _DISPATCH)
def test_hist_dispatch_table(interpret_modes, hk, n_bins, K, num_f, words,
                             single, kernel, mirror, ladder, top_rung):
    """``hist_dispatch`` is the one answer to which kernel a masked pass
    takes, whether the mode wants the packed mirror, whether the warm-up
    ladder pays and where the row ladder starts.  ``interpret_modes``
    stands in for the TPU."""
    assert hist_dispatch(hk, n_bins, K, num_f, words, single) == \
        (kernel, mirror, ladder, top_rung)


@pytest.mark.parametrize(_DISPATCH_ARGS, _DISPATCH[:9])
def test_hist_dispatch_off_tpu_is_xla(hk, n_bins, K, num_f, words, single,
                                      kernel, mirror, ladder, top_rung):
    """Off the TPU every pass is the XLA contraction; what the booster
    ships (the mirror), which round bodies compile (the ladder) and the
    row ladder's buckets do not depend on the platform."""
    assert hist_dispatch(hk, n_bins, K, num_f, words, single) == \
        ("xla", mirror, ladder, top_rung)


def test_hist_kernel_unknown_value_raises():
    """The registered config key rejects unknown values with a
    LightGBMError NAMING the key (config-registry contract)."""
    with pytest.raises(LightGBMError, match="hist_kernel"):
        resolve_hist_kernel("bogus")
    X = np.random.default_rng(0).standard_normal((80, 4))
    y = (X[:, 0] > 0).astype(float)
    with pytest.raises(LightGBMError, match="hist_kernel"):
        lgb.train({**FAST, "hist_kernel": "nope"},
                  lgb.Dataset(X, label=y), num_boost_round=1)


def test_hist_kernel_registered_in_config():
    """hist_kernel flows through Config (registered in _PARAMS — the
    tpulint CFG2xx gate checks the docs side)."""
    from lightgbm_tpu.config import Config
    assert Config({}).hist_kernel == "auto"
    assert Config({"hist_kernel": "packed"}).hist_kernel == "packed"
    assert tuple(HIST_KERNELS) == ("auto", "onehot", "packed", "radix2")


def test_packed_mirror_matches_device_words():
    """io/dataset.py packed_mirror is the SAME layout bins_to_words
    produces on device (little-endian 4-bins-per-word), so the booster
    can ship the construction-time mirror straight into the kernels."""
    rng = np.random.default_rng(3)
    X = rng.standard_normal((300, 7))       # 7 cols: exercises padding
    ds = lgb.Dataset(X, label=(X[:, 0] > 0).astype(float))
    ds.construct()
    inner = ds._inner
    mirror = inner.packed_mirror()
    ref = np.asarray(bins_to_words(jnp.asarray(inner.bins)))
    npt.assert_array_equal(mirror, ref)
    assert inner.packed_mirror() is mirror  # cached


def _model_text(bst):
    return bst.model_to_string().split("parameters:")[0]


def _train_mode(X, y, hk, extra=None, rounds=3):
    p = {**FAST, "hist_kernel": hk, **(extra or {})}
    return lgb.train(p, lgb.Dataset(X, label=y, params=p),
                     num_boost_round=rounds)


def test_e2e_modes_identical_nan_63bins(interpret_modes):
    """Full trainings (grower -> dispatch -> kernels) produce IDENTICAL
    model text across modes at 63 bins with NaN feature values (missing
    rows ride the NaN bin through every formulation).  auto engages the
    packed kernel here (sub-128-bin masked pass) with no behavior
    change.  Quantized int8 gradients make every mode's accumulation
    exact-integer, so model-text equality is formulation-equivalence
    with NO reduction-order caveat (real-float cross-shape parity is an
    MXU-order property, untestable bit-tight on XLA CPU — see
    _fixture)."""
    rng = np.random.default_rng(11)
    X = rng.standard_normal((500, 6))
    X[rng.random((500, 6)) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1]) > 0
         ).astype(float)
    extra = {"max_bin": 63, "use_quantized_grad": True,
             "tpu_hist_dtype": "int8", "deterministic": False}
    ref = _model_text(_train_mode(X, y, "onehot", extra))
    assert _model_text(_train_mode(X, y, "packed", extra)) == ref
    assert _model_text(_train_mode(X, y, "auto", extra)) == ref


def test_e2e_modes_identical_efb_255bins_batched(interpret_modes):
    """EFB-bundled data + 255 bins + K>1 split batches: radix2 (and auto,
    which selects it at K>4) matches the one-hot reference exactly
    through the batched grower."""
    rng = np.random.default_rng(12)
    n, levels = 400, 6
    idx = rng.integers(0, levels, n)
    block = np.zeros((n, levels))
    block[np.arange(n), idx] = rng.normal(1.5, 0.2, n)
    dense = rng.standard_normal((n, 2))
    X = np.concatenate([block, dense], axis=1)
    y = ((idx % 2) + dense[:, 0] > 0.5).astype(float)
    extra = {"max_bin": 255, "enable_bundle": True, "tpu_split_batch": 5,
             "num_leaves": 12, "use_quantized_grad": True,
             "tpu_hist_dtype": "int8", "deterministic": False}
    ref = _model_text(_train_mode(X, y, "onehot", extra))
    assert _model_text(_train_mode(X, y, "radix2", extra)) == ref
    assert _model_text(_train_mode(X, y, "auto", extra)) == ref


def test_e2e_modes_float_path_agrees(interpret_modes):
    """Float-gradient trainings across modes: the kernels accumulate
    identical summands, so models agree to f32 reduction-order noise
    (bit-tight on the MXU's fixed order; XLA CPU may reassociate — the
    kernel grid above proves formulation equivalence exactly)."""
    rng = np.random.default_rng(13)
    X = rng.standard_normal((500, 6))
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(float)
    extra = {"max_bin": 63}
    preds = {hk: _train_mode(X, y, hk, extra).predict(X)
             for hk in ("onehot", "packed", "auto")}
    for hk in ("packed", "auto"):
        assert np.mean(np.abs(preds[hk] - preds["onehot"])) < 1e-3


# ---------------------------------------------------------- fused valid
def test_fused_valid_skips_frontier_walk():
    """The fused scan's per-round valid scoring takes the matmul
    path-aggregation, NOT the per-iteration frontier walk (VERDICT r5
    #4: the walk doubled e2e with a riding valid set).  Asserted by
    poisoning the walk entry point: training with a valid set must
    never call it."""
    import lightgbm_tpu.boosting.gbdt as gbdt_mod
    rng = np.random.default_rng(15)
    X = rng.standard_normal((1500, 6))
    y = (X[:, 0] + rng.standard_normal(1500) * 0.3 > 0).astype(float)
    Xv = rng.standard_normal((400, 6))
    yv = (Xv[:, 0] > 0).astype(float)
    p = {**FAST, "metric": "binary_logloss", "tpu_split_batch": 4}
    ds = lgb.Dataset(X, label=y, params=p)
    b = lgb.Booster(params=p, train_set=ds)
    b.add_valid(ds.create_valid(Xv, label=yv), "v")
    assert b._gbdt.supports_fused() and b._gbdt.fused_valid_ok()
    assert b._gbdt._matmul_valid_ok()

    def _poisoned_walk(*a, **k):
        raise AssertionError(
            "per-iteration frontier walk called for valid scoring")

    orig = gbdt_mod.predict_bins_tree
    gbdt_mod.predict_bins_tree = _poisoned_walk
    try:
        b._gbdt.train_fused(4)
    finally:
        gbdt_mod.predict_bins_tree = orig
    assert len(b._gbdt.models) >= 4
    assert b._gbdt._last_fused_evals    # valid metrics actually evaluated


def test_classic_loop_valid_matmul_matches_walk():
    """The matmul valid scorer is BIT-identical to the frontier walk
    (exactly one leaf matches per row; dead slots add +0.0) — classic
    loop, eligible model class."""
    import lightgbm_tpu.boosting.gbdt as gbdt_mod
    rng = np.random.default_rng(16)
    X = rng.standard_normal((800, 6))
    y = (X[:, 0] + rng.standard_normal(800) * 0.3 > 0).astype(float)
    Xv = rng.standard_normal((300, 6))
    yv = (Xv[:, 0] > 0).astype(float)

    def run(force_walk):
        ds = lgb.Dataset(X, label=y)
        dv = ds.create_valid(Xv, label=yv)
        orig_ok = gbdt_mod.GBDT._matmul_valid_ok
        orig_fused = gbdt_mod.GBDT.supports_fused
        gbdt_mod.GBDT.supports_fused = lambda self: False
        if force_walk:
            gbdt_mod.GBDT._matmul_valid_ok = lambda self: False
        try:
            b = lgb.train(FAST, ds, num_boost_round=5, valid_sets=[dv])
            return np.asarray(b._gbdt.valid_scores[0])
        finally:
            gbdt_mod.GBDT._matmul_valid_ok = orig_ok
            gbdt_mod.GBDT.supports_fused = orig_fused

    npt.assert_array_equal(run(False), run(True))


def test_fused_valid_ok_multiclass():
    """Multiclass rides the fused scan (round-6 satellite): multi
    metrics carry traced device kernels, and the in-scan value matches
    the classic host eval."""
    import lightgbm_tpu.boosting.gbdt as gbdt_mod
    rng = np.random.default_rng(17)
    X = rng.standard_normal((900, 6))
    y = rng.integers(0, 3, 900).astype(float)
    Xv = rng.standard_normal((300, 6))
    yv = rng.integers(0, 3, 300).astype(float)
    p = {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
         "metric": "multi_logloss", "verbose": -1, "tpu_split_batch": 4}

    def boosters():
        ds = lgb.Dataset(X, label=y, params=p)
        b = lgb.Booster(params=p, train_set=ds)
        b.add_valid(ds.create_valid(Xv, label=yv), "v")
        return b

    b = boosters()
    assert b._gbdt.fused_valid_ok()
    b._gbdt.train_fused(3)
    fused_val = b._gbdt._last_fused_evals[0][2]
    bc = boosters()
    orig = gbdt_mod.GBDT.supports_fused
    gbdt_mod.GBDT.supports_fused = lambda self: False
    try:
        for _ in range(3):
            bc._gbdt.train_one_iter()
    finally:
        gbdt_mod.GBDT.supports_fused = orig
    host_val = bc._gbdt.eval_valid()[0][2]
    npt.assert_allclose(fused_val, host_val, rtol=1e-5)


def test_fused_valid_ok_multiclass_rejects_column_metrics():
    """A single-column device metric (auc) cannot consume the [n, k]
    matrix — multiclass with it must NOT claim fused valid eval."""
    rng = np.random.default_rng(18)
    X = rng.standard_normal((300, 5))
    y = rng.integers(0, 3, 300).astype(float)
    p = {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
         "metric": "auc_mu", "verbose": -1}
    ds = lgb.Dataset(X, label=y, params=p)
    b = lgb.Booster(params=p, train_set=ds)
    b.add_valid(ds.create_valid(X, label=y), "v")
    assert not b._gbdt.fused_valid_ok()


# ------------------------------------------------------- forced x pool
def test_forced_pooled_grower_equals_unpooled():
    """Round-6 lift of the batched-path carve-out: forced splits x
    bounded histogram pool in the batched grower equals the unpooled
    batched run bit-for-bit (the test_hist_pool.py serial-equivalence
    standard: integer-valued grad/hess make all sums exact, so the
    pooled forced phase's direct-column derivation cannot hide behind
    rounding)."""
    import dataclasses

    from lightgbm_tpu.learner.batch_grower import grow_tree_batched
    from lightgbm_tpu.ops.split import SplitHyper
    rng = np.random.default_rng(19)
    n, f = 6000, 8
    bins = jnp.asarray(rng.integers(0, 63, (n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.integers(-2, 3, n).astype(np.float32))
    hess = jnp.asarray(rng.integers(1, 5, n).astype(np.float32))
    num_bins = jnp.full((f,), 64, jnp.int32)
    nan_bin = jnp.full((f,), -1, jnp.int32)
    is_cat = jnp.zeros((f,), bool)
    # BFS forced prefix: root -> feature 0 @ bin 20, its left child ->
    # feature 1 @ bin 40 (the _parse_forced_splits array layout)
    K = 31 - 1
    f_leaf = np.full(K, -1, np.int32); f_leaf[0], f_leaf[1] = 0, 0
    f_feat = np.zeros(K, np.int32); f_feat[1] = 1
    f_thr = np.zeros(K, np.int32); f_thr[0], f_thr[1] = 20, 40
    forced = (jnp.asarray(f_leaf), jnp.asarray(f_feat),
              jnp.asarray(f_thr))
    hp = SplitHyper(num_leaves=31, min_data_in_leaf=5, n_bins=64,
                    hist_dtype="float32")
    hp_pool = dataclasses.replace(hp, hist_pool_slots=3 * 4 + 2)
    t0, lor0 = grow_tree_batched(bins, grad, hess, None, num_bins,
                                 nan_bin, is_cat, None, hp, batch=4,
                                 forced=forced)
    t1, lor1 = grow_tree_batched(bins, grad, hess, None, num_bins,
                                 nan_bin, is_cat, None, hp_pool, batch=4,
                                 forced=forced)
    assert int(t0.num_leaves) > 8
    # forced prefix applied: root on feature 0 @ bin 20
    assert int(t0.split_feature[0]) == 0 and int(t0.split_bin[0]) == 20
    npt.assert_array_equal(np.asarray(t0.split_feature),
                           np.asarray(t1.split_feature))
    npt.assert_array_equal(np.asarray(t0.split_bin),
                           np.asarray(t1.split_bin))
    npt.assert_array_equal(np.asarray(t0.leaf_value),
                           np.asarray(t1.leaf_value))
    npt.assert_array_equal(np.asarray(lor0), np.asarray(lor1))


def test_forced_pooled_evicted_leaf_column_derivation():
    """A forced prefix DEEPER than the pool forces slot evictions during
    the forced phase itself, so the evicted branch (forced_col_hist
    direct derivation) must carry the split — and still equal the
    unpooled batched run exactly (integer grads: direct vs
    subtraction-chain sums are both exact)."""
    import dataclasses

    from lightgbm_tpu.learner.batch_grower import grow_tree_batched
    from lightgbm_tpu.ops.split import SplitHyper
    rng = np.random.default_rng(21)
    n, f = 6000, 8
    bins = jnp.asarray(rng.integers(0, 63, (n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.integers(-2, 3, n).astype(np.float32))
    hess = jnp.asarray(rng.integers(1, 5, n).astype(np.float32))
    num_bins = jnp.full((f,), 64, jnp.int32)
    nan_bin = jnp.full((f,), -1, jnp.int32)
    is_cat = jnp.zeros((f,), bool)
    # 8-deep left-spine forced chain at K=1 with the MINIMUM pool
    # (P = 3*1 + 2 = 5): by split 6 the spine's early leaves have been
    # evicted, so later forced rounds re-derive their columns
    depth = 8
    K = 31 - 1
    f_leaf = np.full(K, -1, np.int32); f_leaf[:depth] = 0
    f_feat = np.arange(depth, dtype=np.int32) % f
    f_feat = np.concatenate([f_feat, np.zeros(K - depth, np.int32)])
    f_thr = np.full(K, 32, np.int32)
    forced = (jnp.asarray(f_leaf), jnp.asarray(f_feat),
              jnp.asarray(f_thr))
    hp = SplitHyper(num_leaves=31, min_data_in_leaf=5, n_bins=64,
                    hist_dtype="float32")
    hp_pool = dataclasses.replace(hp, hist_pool_slots=5)
    t0, lor0 = grow_tree_batched(bins, grad, hess, None, num_bins,
                                 nan_bin, is_cat, None, hp, batch=1,
                                 forced=forced)
    t1, lor1 = grow_tree_batched(bins, grad, hess, None, num_bins,
                                 nan_bin, is_cat, None, hp_pool, batch=1,
                                 forced=forced)
    assert int(t0.num_leaves) > depth   # the chain actually applied
    npt.assert_array_equal(np.asarray(t0.split_feature),
                           np.asarray(t1.split_feature))
    npt.assert_array_equal(np.asarray(t0.split_bin),
                           np.asarray(t1.split_bin))
    npt.assert_array_equal(np.asarray(t0.leaf_value),
                           np.asarray(t1.leaf_value))
    npt.assert_array_equal(np.asarray(lor0), np.asarray(lor1))


def test_pool_inert_under_strict_fallback_warns(tmp_path):
    """forced splits + pool under a config the batched path refuses
    (voting + forced) keep the STRICT learner -> the pool is inert;
    that must be tallied, not silent."""
    import json
    rng = np.random.default_rng(22)
    X = rng.standard_normal((300, 6))
    y = (X[:, 0] > 0).astype(float)
    fpath = tmp_path / "forced.json"
    fpath.write_text(json.dumps({"feature": 0, "threshold": 0.0}))
    p = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
         "verbose": -1, "forcedsplits_filename": str(fpath),
         "tpu_split_batch": 4, "histogram_pool_size": 1e-4,
         "tree_learner": "voting"}
    ds = lgb.Dataset(X, label=y, params=p)
    b = lgb.Booster(params=p, train_set=ds)
    assert not b._gbdt._use_batched_grower()
    assert b._gbdt.metrics.counter("hist_pool_fallbacks") == 1
    assert b._gbdt.metrics.counter("batched_path_fallbacks") == 1


def test_forced_splits_compose_with_hist_pool_e2e(tmp_path):
    """train() with forcedsplits_filename + histogram_pool_size stays on
    the batched fast path (no strict-learner fallback warning), engages
    the pool, and applies the forced prefix to every tree."""
    import json
    rng = np.random.default_rng(20)
    X = rng.standard_normal((2000, 8))
    y = (X[:, 0] + 0.3 * X[:, 1]
         + rng.standard_normal(2000) * 0.2 > 0).astype(float)
    fpath = tmp_path / "forced.json"
    fpath.write_text(json.dumps(
        {"feature": 0, "threshold": 0.0,
         "left": {"feature": 1, "threshold": 0.5}}))
    p = {"objective": "binary", "num_leaves": 31, "min_data_in_leaf": 5,
         "verbose": -1, "forcedsplits_filename": str(fpath),
         "tpu_split_batch": 4, "histogram_pool_size": 0.5}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                    num_boost_round=4)
    g = bst._gbdt
    assert 0 < g.hp.hist_pool_slots < g.hp.num_leaves  # pool engaged
    assert g._use_batched_grower()        # no strict-learner fallback
    assert g.forced_splits is not None
    assert g.metrics.counter("hist_pool_fallbacks") == 0
    for t in bst.dump_model()["tree_info"]:
        assert t["tree_structure"]["split_feature"] == 0
        assert t["tree_structure"]["left_child"]["split_feature"] == 1


# ------------------------------------------------------ bench protocol
def test_bench_compare_exit_codes(tmp_path):
    """tools/bench_compare.py: 0 on parity, 1 on a >threshold
    regression, 2 on unusable input (incl. a refused noisy capture)."""
    import importlib.util
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(tools, "bench_compare.py"))
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    import json

    def cap(path, vb, extra=None):
        payload = {"metric": "higgs", "value": 1.0, "unit": "seconds",
                   "vs_baseline": vb, "platform": "tpu", **(extra or {})}
        p = tmp_path / path
        p.write_text(json.dumps({"parsed": payload}))
        return str(p)

    old = cap("old.json", 0.42)
    assert bc.main([old, cap("same.json", 0.41)]) == 0      # -2.4% ok
    assert bc.main([old, cap("worse.json", 0.35)]) == 1     # -16.7%
    assert bc.main([old, cap("tight.json", 0.41),
                    "--threshold", "0.01"]) == 1
    noisy = cap("noisy.json", 0.0, {"quality": "noisy",
                                    "rejected_value": 467.0})
    assert bc.main([old, noisy]) == 2
    assert bc.main([old, str(tmp_path / "missing.json")]) == 2


# ------------------------------------------- the switches are gone
@pytest.mark.parametrize("module", ["histogram", "round_fuse", "hist_pallas"])
def test_histogram_layer_reads_no_environment(module):
    """Which kernel a pass takes is a function of its shapes and the
    platform (``hist_dispatch``), never of the process environment: the
    six A/B hatches went in PR 32 and no new one comes back unseen."""
    import ast
    path = os.path.join(os.path.dirname(hist_mod.__file__), module + ".py")
    with open(path) as fh:
        tree = ast.parse(fh.read())
    imported = {a.name for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names}
    assert "os" not in imported and "environ" not in imported
    names = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)} \
        | {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    assert not names & {"environ", "getenv"}


# spelled in two pieces so that a grep of the tree for the removed names
# finds nothing
@pytest.mark.parametrize("key", ["collective_" "overlap", "tpu_leaf_" "hist"])
def test_removed_options_are_unknown_keys(key):
    """The two path-selecting options are not registered: a params dict
    that still carries one drops it (with the unknown-parameter warning)."""
    from lightgbm_tpu.config import _PARAMS, Config, normalize_params
    assert key not in {p[0] for p in _PARAMS}
    assert normalize_params({key: "on", "num_leaves": 7}) == {"num_leaves": 7}
    assert not hasattr(Config({key: "on"}), key)


def test_reduce_hist_is_one_all_reduce():
    """``reduce_hist`` is one ``psum`` under the scope ``hist_allreduce``:
    on a 4-device mesh the compiled program holds exactly one all-reduce,
    named by that scope."""
    import re

    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    fn = jax.jit(jax.shard_map(
        lambda h: hist_mod.reduce_hist(h, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P(), check_vma=False))
    text = fn.lower(jnp.ones((8, 6, 16, 4), jnp.float32)).compile().as_text()
    ops = [line for line in text.splitlines()
           if re.search(r" all-reduce(-start)?\(", line)]
    assert len(ops) == 1, ops
    assert "hist_allreduce" in ops[0]
    # serial: no axis, no collective, the same array back
    h = jnp.ones((2, 3))
    assert hist_mod.reduce_hist(h, None) is h
