"""Hardware perf sweep over grower configurations.

Usage:  python tools/sweep_perf.py k=28 k=28,dtype=float32

Each spec is comma-joined key=value pairs: k (split batch),
dtype (bfloat16/float32), warmup (0/1), iters, leaves.  Timing is
scan-chained inside one jit (docs/PERF_NOTES.md methodology).
"""
import json
import os
import sys
import time

import numpy as np

# run as `python tools/sweep_perf.py`: sys.path[0] is tools/, not the repo
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("BENCH_ROWS", "1000000")

import jax
import jax.numpy as jnp
from lightgbm_tpu.learner.batch_grower import grow_tree_batched
from lightgbm_tpu.ops.split import SplitHyper
from lightgbm_tpu.ops.table import take_small_table

N = int(os.environ["BENCH_ROWS"])
ITERS = int(os.environ.get("BENCH_ITERS", "5"))
# BENCH_BIN=63 exercises the reference GPU doc's speed configuration
# (docs/GPU-Performance.rst:100-123); bin width rounds up to a power of two
MAX_BIN = int(os.environ.get("BENCH_BIN", "255"))
from lightgbm_tpu.io.dataset import device_bins_pow2
N_BINS = device_bins_pow2(MAX_BIN)

rng = np.random.default_rng(0)
f = 28
w = rng.normal(size=f)
feat = rng.normal(size=(N, f)).astype(np.float32)
logits = feat @ w * 0.5
label = (logits + rng.normal(scale=1.0, size=N) > 0).astype(np.float32)
qs = np.quantile(feat[:100_000], np.linspace(0, 1, MAX_BIN)[1:-1], axis=0)
bins = np.empty((N, f), np.uint8)
for j in range(f):
    bins[:, j] = np.searchsorted(qs[:, j], feat[:, j]).astype(np.uint8)

bins_d = jnp.asarray(bins)
label_d = jnp.asarray(label)
num_bins = jnp.full((f,), MAX_BIN, jnp.int32)
nan_bin = jnp.full((f,), -1, jnp.int32)
is_cat = jnp.zeros((f,), bool)


def run_config(k, dtype="bfloat16", warmup=True, iters=ITERS,
               leaves=255):
    hp = SplitHyper(num_leaves=leaves, min_data_in_leaf=0,
                    min_sum_hessian_in_leaf=100.0, n_bins=N_BINS,
                    rows_per_block=8192, hist_dtype=dtype)

    # int8 kernels consume INTEGER gradient levels (the use_quantized_grad
    # contract) — raw logistic grads in (-1, 1) would truncate to zero,
    # collapse every tree and report a fantasy ms/tree.  Mirror the
    # production path: discretize to levels inside the step.
    quantize = dtype == "int8"
    if quantize:
        from lightgbm_tpu.ops.quantize import discretize_gradients_levels

    @jax.jit
    def run(scores, bins_a, label_a):
        def step(carry, i):
            scores = carry
            sign = jnp.where(label_a > 0, 1.0, -1.0)
            resp = -sign / (1.0 + jnp.exp(sign * scores))
            grad = resp
            hess = jnp.abs(resp) * (1.0 - jnp.abs(resp))
            hist_scale = None
            if quantize:
                key = jax.random.fold_in(jax.random.PRNGKey(7), i)
                grad, hess, gs, hs = discretize_gradients_levels(
                    grad, hess, key, n_levels=4, stochastic=True)
                hist_scale = jnp.stack([gs, hs])
            tree, leaf_of_row = grow_tree_batched(
                bins_a, grad, hess, None, num_bins, nan_bin, is_cat,
                None, hp, batch=k, warmup=warmup, hist_scale=hist_scale)
            return scores + 0.1 * take_small_table(tree.leaf_value,
                                                   leaf_of_row), None
        scores, _ = jax.lax.scan(step, scores, jnp.arange(iters))
        return scores

    scores = jnp.zeros(N, jnp.float32)
    t0 = time.time()
    out = run(scores, bins_d, label_d)
    float(out[0])
    compile_s = time.time() - t0
    t0 = time.time()
    out = run(scores, bins_d, label_d)
    float(out[0])
    elapsed = time.time() - t0
    ms_per_tree = elapsed / iters * 1000
    print(json.dumps({"k": k, "dtype": dtype,
                      "warmup": warmup, "ms_per_tree": round(ms_per_tree, 2),
                      "compile_s": round(compile_s, 1)}), flush=True)
    return ms_per_tree


if __name__ == "__main__":
    for spec in sys.argv[1:]:
        parts = dict(p.split("=") for p in spec.split(","))
        run_config(int(parts.get("k", 20)),
                   parts.get("dtype", "bfloat16"),
                   parts.get("warmup", "1") == "1",
                   int(parts.get("iters", ITERS)),
                   int(parts.get("leaves", 255)))
