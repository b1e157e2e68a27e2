"""Benchmark: Higgs-shaped GBDT training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline anchor (BASELINE.md): reference LightGBM CPU trains HIGGS
(10.5M rows x 28 features, num_leaves=255, max_bin=255, 500 iters) in
130.094 s on 2x E5-2690v4 => 2.477e-8 s per row-iteration.  This bench
trains on BENCH_ROWS x 28 synthetic rows for BENCH_ITERS iterations with the
same num_leaves/max_bin and reports seconds normalized to the reference's
per-row-iteration cost:

    vs_baseline = (baseline_s_per_row_iter * rows * iters) / measured_s

(> 1.0 means faster than the reference CPU run per unit work).

One process, no children: it measures on the device JAX gives it and
names that device in the payload (``platform``, ``device_kind``,
``device_count``), or it exits non-zero.  Nothing is cached between runs
and a number that was not just measured is never printed.
"""

import json
import os
import time

import numpy as np

BENCH_ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
BENCH_ITERS = int(os.environ.get("BENCH_ITERS", 20))
NUM_LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
MAX_BIN = int(os.environ.get("BENCH_BIN", 255))
# Bin widths follow lightgbm_tpu.io.dataset.device_bins_pow2 (the same
# rounding rule as Dataset.device_n_bins).  BENCH_BIN=63 makes the
# 63-bin speed configuration the primary measurement.
# splits per histogram pass (learner/batch_grower.py); 1 = strict leaf-wise.
# Round-4 int8 K sweep on the live chip: 28 -> 83.2, 36 -> 89.0(noisy),
# 42 -> 76.9 ms/tree — with K-independent kernel cost, fewer rounds win;
# 3K = 126 <= 128 keeps the flat kernel inside one MXU channel tile.
SPLIT_BATCH = int(os.environ.get("BENCH_SPLIT_BATCH", 42))
# histogram build formulation under test (the hist_kernel config key —
# auto|onehot|packed|radix2).  Round-6 capture protocol for BENCH_r06.json:
# run once per mode (BENCH_HIST_KERNEL=onehot / packed / radix2) at
# BENCH_BIN=255 and 63 so the packed-compare and shared-radix claims carry
# their own on-chip A/B next to the auto headline (docs/PERF_NOTES.md r6).
HIST_KERNEL = os.environ.get("BENCH_HIST_KERNEL", "auto")
# capture_quality probe spread above which a capture is REFUSED a headline
# number (VERDICT r5 #2: a 467 s flagship later re-ran at 924-1108 s and
# nothing in the JSON distinguished the congested window) — the payload
# then reports {"quality": "noisy"} with the seconds demoted to
# rejected_value.
SPREAD_MAX = float(os.environ.get("BENCH_SPREAD_MAX", "1.5"))
BASELINE_S_PER_ROW_ITER = 130.094 / (10_500_000 * 500)


def _device_result():
    """The device the numbers were taken on, as JAX reports it."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _capture_quality(repeats=3):
    """Capture-quality preamble for the emitted JSON (VERDICT Weak #2:
    the flagship e2e number failed to reproduce — 467 s vs 924-1108 s
    re-runs — with nothing in BENCH_*.json to tell a clean window from a
    congested one).  Reports a 3-repeat timing of a fixed small device
    computation (compile excluded) whose spread exposes a congested
    host, plus host RSS and device memory stats at capture time."""
    import jax.numpy as jnp
    from lightgbm_tpu.obs import memory as obs_memory

    x = jnp.ones((2048, 2048))
    (x @ x).block_until_ready()          # compile outside the probe
    probes = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        (x @ x).block_until_ready()
        probes.append(round(time.perf_counter() - t0, 6))
    out = {
        "probe_matmul_s": probes,
        "probe_spread": round(max(probes) / max(min(probes), 1e-9), 3),
    }
    out.update(obs_memory.memory_snapshot())
    return out


def _quality_gate(payload):
    """Refuse a headline number from a congested capture window.

    A capture whose 3-repeat probe spread exceeds ``SPREAD_MAX`` is not
    reproducible evidence: the headline fields are zeroed (so no verdict
    or cache can quote them), ``quality`` says why, and the raw seconds
    survive only as ``rejected_value`` for forensics."""
    spread = (payload.get("capture_quality") or {}).get("probe_spread", 1.0)
    if spread <= SPREAD_MAX:
        payload["quality"] = "ok"
        return payload
    payload["quality"] = "noisy"
    payload["rejected_value"] = payload.get("value")
    payload["value"] = -1.0
    payload["vs_baseline"] = 0.0
    # sub-measurements timed in the same congested window are equally
    # refused — a quotable 63-bin number would defeat the gate
    sub = payload.get("speed_mode_bins63")
    if isinstance(sub, dict):
        sub["rejected_value"] = sub.get("value")
        sub["value"] = -1.0
        sub["vs_baseline"] = 0.0
    return payload


def _memory_result():
    """Post-measurement memory stats for the payload (closes VERDICT
    Missing #3: peak RAM is a headline result in the reference's
    Experiments.rst but BENCH_*.json never carried it)."""
    from lightgbm_tpu.obs import memory as obs_memory
    return obs_memory.memory_snapshot()


def _collective_result():
    """Collective-overlap probe (obs/collective.py) next to the headline:
    per-pass reduce time and how much of it the split-psum overlap hides
    (``overlap_efficiency`` drops to 0.0 under ``LGBMTPU_NO_OVERLAP=1``,
    the same A/B knob the training path honors).  ``None`` on a 1-device
    mesh — there is no collective to measure."""
    import jax
    if jax.device_count() < 2:
        return None
    from lightgbm_tpu.obs.collective import measure_collective
    from lightgbm_tpu.parallel.mesh import make_mesh
    res = measure_collective(make_mesh(), (256, MAX_BIN + 1, 4))
    return {k: round(float(v), 9) for k, v in res.items()}


def _synth_higgs(n, f, rng, w=None):
    """Higgs-shaped synthetic binary data (separable-ish continuous
    features; BASELINE.md pairs its 130.094 s with AUC 0.845724 on the real
    set — the synthetic task reports ITS OWN auc next to wall-clock so perf
    is always gated on accuracy).  Pass ``w`` to draw train/test sets from
    the SAME task."""
    if w is None:
        w = rng.normal(size=f)
    feat = rng.normal(size=(n, f)).astype(np.float32)
    logits = feat @ w * 0.5
    label = (logits + rng.normal(scale=1.0, size=n) > 0).astype(np.float32)
    return feat, label, w


def main_e2e():
    """BENCH_E2E=1: the path a user calls — Dataset + train() + AUC.

    Times train() only (the reference's published numbers exclude data
    loading, docs/Experiments.rst) and reports held-out AUC in the JSON so
    the perf claim carries its accuracy (VERDICT r2 weak #3).
    """
    import lightgbm_tpu as lgb

    rng = np.random.default_rng(0)
    n, f = BENCH_ROWS, 28
    feat, label, w = _synth_higgs(n, f, rng)
    feat_te, label_te, _ = _synth_higgs(200_000, f, rng, w=w)
    params = {
        "objective": "binary", "metric": "auc", "verbose": -1,
        "num_leaves": NUM_LEAVES, "learning_rate": 0.1,
        "max_bin": MAX_BIN, "min_data_in_leaf": 0,
        "min_sum_hessian_in_leaf": 100.0,
    }
    params["tpu_hist_dtype"] = os.environ.get("BENCH_HIST_DTYPE", "int8")
    params["use_quantized_grad"] = True
    params["tpu_split_batch"] = SPLIT_BATCH
    params["hist_kernel"] = HIST_KERNEL
    # BENCH_VALID=1: register the held-out set as a valid set — scoring +
    # device AUC eval ride INSIDE the fused scan (round 5), the
    # reference HIGGS recipe's shape (train + eval each iteration)
    with_valid = bool(os.environ.get("BENCH_VALID"))
    capture = _capture_quality()
    ds = lgb.Dataset(feat, label=label, params=params)
    ds.construct()
    # warm the jit caches OUTSIDE the timed region: the one-time
    # tracing+XLA compile at 20 timed iters would swamp the steady-state
    # rate the reference's 500-iteration published number reflects (its
    # one-time setup is likewise excluded by measuring post-load).  The
    # fused-rounds runner
    # is compiled per-booster (its jit closes over the booster's device
    # state), so warm ONE chunk on a booster and time CONTINUED rounds on
    # that same booster — the steady-state path a long training run
    # spends all its time in.
    from lightgbm_tpu.boosting.gbdt import GBDT as _G

    bst = lgb.train(params, ds,
                    num_boost_round=_G.fused_chunk_for(BENCH_ITERS))
    gb = bst._gbdt
    if with_valid:
        dv = ds.create_valid(feat_te, label=label_te)
        bst.add_valid(dv, "valid")       # Booster-level (constructs)
    # the exact expression train_fused keys its cache with (aliases and
    # defaults resolved by the config, not the raw params dict)
    has_fm = float(gb.config.feature_fraction) < 1.0
    nv = len(gb.valid_sets)
    if gb.supports_fused():
        # compile every scan length the timed run will use (the first
        # warmup train covers fused_chunk_for(BENCH_ITERS) only when
        # BENCH_ITERS is divisible; ragged tails need their own runner)
        for L in sorted(set(_G.fused_chunks(BENCH_ITERS))):
            if (L, has_fm, nv, None) not in gb._fused_cache:
                gb.train_fused(L)
    t0 = time.time()
    if gb.supports_fused():
        gb.train_fused(BENCH_ITERS)
    else:
        for _ in range(BENCH_ITERS):
            gb.train_one_iter()
    elapsed = time.time() - t0
    # warmup + precompile rounds left extra trees on the booster; score
    # the FIRST BENCH_ITERS trees so the reported AUC is exactly the
    # named iteration count's model (trees 0..N-1 train identically
    # whatever follows them)
    pred = bst.predict(feat_te, num_iteration=BENCH_ITERS)
    order = np.argsort(pred)
    ranks = np.empty(len(order))
    ranks[order] = np.arange(1, len(order) + 1)
    npos = label_te.sum()
    nneg = len(label_te) - npos
    auc = (ranks[label_te > 0].sum() - npos * (npos + 1) / 2) / (npos * nneg)
    baseline_equiv = BASELINE_S_PER_ROW_ITER * n * BENCH_ITERS
    payload = {
        "metric": f"higgs_e2e_train_{n}rows_{BENCH_ITERS}iters_"
                  f"leaves{NUM_LEAVES}" + ("_valid" if with_valid else ""),
        "value": round(elapsed, 3),
        "unit": "seconds",
        "vs_baseline": round(baseline_equiv / elapsed, 4),
        "auc": round(float(auc), 6),
        **_device_result(),
        "hist_kernel": HIST_KERNEL,
        "capture_quality": capture,
        "memory": _memory_result(),
    }
    coll = _collective_result()
    if coll is not None:
        payload["collective"] = coll
    if with_valid and getattr(gb, "_last_fused_evals", None):
        # the in-scan device AUC of the final round (proof the valid set
        # actually rode the fused path)
        payload["valid_auc_in_scan"] = round(
            float(gb._last_fused_evals[0][2]), 6)
    print(json.dumps(_quality_gate(payload)))


def _synth_msltr(n, f, rng):
    """MS-LTR-shaped ranking task: skewed query lengths (lognormal —
    median ~120 docs with a tail past 1000, the WEB30K histogram shape
    that makes pad-to-max waste explode) and graded 0..4 relevance
    correlated with a linear score.  Returns (feat, label, sizes)."""
    sizes, tot = [], 0
    while tot < n:
        s = int(np.clip(rng.lognormal(mean=4.8, sigma=0.9), 4, 1333))
        s = min(s, n - tot)
        sizes.append(s)
        tot += s
    feat = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    score = feat @ w * 0.3 + rng.normal(scale=1.0, size=n)
    label = np.empty(n, np.float32)
    off = 0
    for s in sizes:
        r = np.argsort(np.argsort(score[off:off + s]))
        label[off:off + s] = np.minimum(4, (r * 5) // max(s, 1))
        off += s
    return feat, label, np.asarray(sizes, np.int64)


def _time_rank_arm(feat, label, sizes, params, no_buckets):
    """One lambdarank A/B arm: train 2 warm rounds (lowers the bucketed
    pairwise programs), then time BENCH_ITERS continued iterations on
    the warm booster.  ``no_buckets`` flips the production env hatch —
    the SAME code path degenerates to one pad-to-max bucket."""
    import lightgbm_tpu as lgb

    prior = os.environ.get("LGBMTPU_NO_RANK_BUCKETS")
    if no_buckets:
        os.environ["LGBMTPU_NO_RANK_BUCKETS"] = "1"
    else:
        os.environ.pop("LGBMTPU_NO_RANK_BUCKETS", None)
    try:
        ds = lgb.Dataset(feat, label=label, group=sizes, params=params)
        t0 = time.time()
        bst = lgb.train(params, ds, num_boost_round=2)
        warm_s = time.time() - t0
        gb = bst._gbdt
        t0 = time.time()
        for _ in range(BENCH_ITERS):
            gb.train_one_iter()
        elapsed = time.time() - t0
        obj = gb.objective
        pad = int(getattr(obj, "_rank_pad_rows", 0))
        n = len(label)
        return {
            "seconds": round(elapsed, 3),
            "iters_per_s": round(BENCH_ITERS / elapsed, 4),
            "pad_rows": pad,
            "pad_waste_ratio": round(pad / float(pad + n), 6),
            "bucket_count": int(getattr(obj, "_rank_bucket_count", 0)),
            "warm_s": round(warm_s, 3),
        }
    finally:
        if prior is None:
            os.environ.pop("LGBMTPU_NO_RANK_BUCKETS", None)
        else:
            os.environ["LGBMTPU_NO_RANK_BUCKETS"] = prior


def main_rank():
    """BENCH_RANK=1: lambdarank training throughput, bucketed vs
    pad-to-max (``kind="rank"`` payload, gated by bench_compare.py).

    Both arms run in ONE process on the same synthetic MS-LTR task so
    the A/B shares its capture window; the headline ``value`` is the
    bucketed arm's steady-state iters/s and ``padded`` carries the
    LGBMTPU_NO_RANK_BUCKETS=1 control next to it."""
    rng = np.random.default_rng(0)
    n, f = BENCH_ROWS, 28
    feat, label, sizes = _synth_msltr(n, f, rng)
    params = {
        "objective": "lambdarank", "verbose": -1,
        "num_leaves": NUM_LEAVES, "learning_rate": 0.1,
        "max_bin": MAX_BIN, "min_data_in_leaf": 0,
        "min_sum_hessian_in_leaf": 100.0,
        "lambdarank_truncation_level": 30,
    }
    capture = _capture_quality()
    bucketed = _time_rank_arm(feat, label, sizes, params,
                              no_buckets=False)
    padded = _time_rank_arm(feat, label, sizes, params, no_buckets=True)
    payload = {
        "metric": f"rank_synth_{n}rows_{len(sizes)}queries_"
                  f"{BENCH_ITERS}iters_leaves{NUM_LEAVES}",
        "kind": "rank",
        "value": bucketed["iters_per_s"],
        "unit": "iters_per_s",
        "vs_baseline": 0.0,
        "rows": n,
        "queries": len(sizes),
        "qmax": int(sizes.max()),
        "bucketed": bucketed,
        "padded": padded,
        "bucket_speedup": round(bucketed["iters_per_s"] /
                                max(padded["iters_per_s"], 1e-9), 4),
        **_device_result(),
        "capture_quality": capture,
        "memory": _memory_result(),
    }
    print(json.dumps(payload))


def _time_kernel_run(feat, label, max_bin, hist_dtype):
    """Scan-chained BENCH_ITERS training iterations at one bin width;
    returns ``(compile_s, run_s)`` — first-call wall minus steady run
    (trace + XLA compile + warmup dispatch), and the steady-state
    post-warmup wall.  Splitting the two makes compile-time regressions
    (ISSUE 7: recompiles that the process cache should absorb) visible
    in the BENCH line instead of hiding inside a single number."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.learner.batch_grower import grow_tree_batched
    from lightgbm_tpu.learner.grower import grow_tree
    from lightgbm_tpu.io.dataset import device_bins_pow2
    from lightgbm_tpu.ops.split import SplitHyper

    n, f = feat.shape
    # quantize host-side (binning is one-time preprocessing, excluded like
    # the reference excludes data loading from train timing)
    qs = np.quantile(feat[:100_000], np.linspace(0, 1, max_bin)[1:-1], axis=0)
    bins = np.empty((n, f), np.uint8)
    for j in range(f):
        bins[:, j] = np.searchsorted(qs[:, j], feat[:, j]).astype(np.uint8)

    hp = SplitHyper(num_leaves=NUM_LEAVES, min_data_in_leaf=0,
                    min_sum_hessian_in_leaf=100.0,
                    n_bins=device_bins_pow2(max_bin),
                    rows_per_block=8192, hist_dtype=hist_dtype,
                    hist_kernel=HIST_KERNEL)
    bins_d = jnp.asarray(bins)
    label_d = jnp.asarray(label)
    num_bins = jnp.full((f,), max_bin, jnp.int32)
    nan_bin = jnp.full((f,), -1, jnp.int32)
    is_cat = jnp.zeros((f,), bool)

    # All iterations inside ONE jit (a Python-side loop would time the
    # per-dispatch overhead, not the learner; scores carry a data
    # dependency across steps so iterations cannot be pipelined into an
    # optimistic overlap).  Big arrays are ARGUMENTS, not closure
    # constants — closure constants get embedded in the HLO on every
    # compilation (294 MB of bins at Higgs scale).
    quantize = hist_dtype == "int8"
    if quantize:
        from lightgbm_tpu.ops.quantize import discretize_gradients_levels

    @jax.jit
    def run(scores, bins_a, label_a):
        def step(scores, i):
            sign = jnp.where(label_a > 0, 1.0, -1.0)
            resp = -sign / (1.0 + jnp.exp(sign * scores))
            grad = resp
            hess = jnp.abs(resp) * (1.0 - jnp.abs(resp))
            hist_scale = None
            if quantize:
                # int8 kernels consume INTEGER levels (the production
                # use_quantized_grad path) — raw logistic grads would
                # truncate to zero and fantasy-collapse the trees
                key = jax.random.fold_in(jax.random.PRNGKey(7), i)
                grad, hess, gs, hs = discretize_gradients_levels(
                    grad, hess, key, n_levels=4, stochastic=True)
                hist_scale = jnp.stack([gs, hs])
            if SPLIT_BATCH > 1:
                tree, leaf_of_row = grow_tree_batched(
                    bins_a, grad, hess, None, num_bins, nan_bin, is_cat,
                    None, hp, batch=SPLIT_BATCH, hist_scale=hist_scale)
            else:
                tree, leaf_of_row = grow_tree(bins_a, grad, hess, None,
                                              num_bins, nan_bin, is_cat,
                                              None, hp)
            from lightgbm_tpu.ops.table import take_small_table
            return scores + 0.1 * take_small_table(tree.leaf_value,
                                                   leaf_of_row), None

        scores, _ = jax.lax.scan(step, scores, jnp.arange(BENCH_ITERS))
        return scores

    scores = jnp.zeros(n, jnp.float32)
    t0 = time.time()
    out = run(scores, bins_d, label_d)    # compile + warmup
    float(out[0])                  # force readback
    first_s = time.time() - t0
    t0 = time.time()
    out = run(scores, bins_d, label_d)
    float(out[0])
    run_s = time.time() - t0
    return max(first_s - run_s, 0.0), run_s


def main():
    if os.environ.get("BENCH_RANK"):
        main_rank()
        return
    if os.environ.get("BENCH_E2E"):
        main_e2e()
        return
    rng = np.random.default_rng(0)
    n, f = BENCH_ROWS, 28
    feat, label, _ = _synth_higgs(n, f, rng)

    # int8 histogram products over quantized-gradient levels: the shipped
    # auto-speed-mode configuration (gbdt.py _resolve_auto_params; exact —
    # see ops/quantize.py; the reference's own GPU guidance likewise trades
    # precision for speed, docs/GPU-Performance.rst single-precision + 63-bin
    # recommendation).  BENCH_HIST_DTYPE=bfloat16/float32 to A/B.
    hist_dtype = os.environ.get("BENCH_HIST_DTYPE", "int8")
    capture = _capture_quality()
    compile_s, elapsed = _time_kernel_run(feat, label, MAX_BIN, hist_dtype)
    baseline_equiv = BASELINE_S_PER_ROW_ITER * n * BENCH_ITERS
    payload = {
        "metric": f"higgs_synth_{n}rows_{BENCH_ITERS}iters_leaves{NUM_LEAVES}",
        "value": round(elapsed, 3),
        "unit": "seconds",
        "compile_s": round(compile_s, 3),
        "run_s": round(elapsed, 3),
        "vs_baseline": round(baseline_equiv / elapsed, 4),
        **_device_result(),
        "hist_kernel": HIST_KERNEL,
        "capture_quality": capture,
    }
    if MAX_BIN == 255 and not os.environ.get("BENCH_NO_SPEED_MODE"):
        # the reference GPU docs' speed configuration (max_bin=63,
        # docs/GPU-Performance.rst:100-123) as a secondary measurement in
        # the same line — vs_baseline stays normalized against the
        # published 255-bin CPU run, exactly like the reference's own
        # 63-bin GPU chart
        c63, e63 = _time_kernel_run(feat, label, 63, hist_dtype)
        payload["speed_mode_bins63"] = {
            "value": round(e63, 3),
            "compile_s": round(c63, 3),
            "vs_baseline": round(baseline_equiv / e63, 4),
        }
    # sampled AFTER the timed runs so peak covers the measurement itself
    payload["memory"] = _memory_result()
    coll = _collective_result()
    if coll is not None:
        payload["collective"] = coll
    print(json.dumps(_quality_gate(payload)))


if __name__ == "__main__":
    from lightgbm_tpu.ops.compile_cache import use_persistent_cache
    use_persistent_cache(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))
    main()
