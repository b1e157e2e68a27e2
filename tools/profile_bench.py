"""Profile the bench training body on a live chip and aggregate
device-lane HLO durations per tree.

Usage:  PK=28 PROWS=1000000 python tools/profile_bench.py

Knobs (env): PK split batch, PROWS rows, PLEAVES
leaves.  Methodology notes in docs/PERF_NOTES.md.
"""
import glob
import gzip
import json
import os
import sys
from collections import defaultdict

import numpy as np

# run as `python tools/profile_bench.py`: sys.path[0] is tools/, not the repo
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K = int(os.environ.get("PK", "20"))
N = int(os.environ.get("PROWS", "1000000"))
LEAVES = int(os.environ.get("PLEAVES", "255"))
PBIN = int(os.environ.get("PBIN", "255"))

import jax
import jax.numpy as jnp
from lightgbm_tpu.learner.batch_grower import grow_tree_batched
from lightgbm_tpu.ops.split import SplitHyper
from lightgbm_tpu.ops.table import take_small_table

rng = np.random.default_rng(0)
f = 28
MAX_BIN = PBIN
from lightgbm_tpu.io.dataset import device_bins_pow2
N_BINS = device_bins_pow2(MAX_BIN)
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

hp = SplitHyper(num_leaves=LEAVES, min_data_in_leaf=0,
                min_sum_hessian_in_leaf=100.0, n_bins=N_BINS,
                rows_per_block=8192,
                hist_dtype=os.environ.get("PDTYPE", "int8"))

ITERS = 3
QUANTIZE = hp.hist_dtype == "int8"
if QUANTIZE:
    from lightgbm_tpu.ops.quantize import discretize_gradients_levels


@jax.jit
def run(scores, bins_a, label_a):
    def step(scores, i):
        sign = jnp.where(label_a > 0, 1.0, -1.0)
        resp = -sign / (1.0 + jnp.exp(sign * scores))
        grad = resp
        hess = jnp.abs(resp) * (1.0 - jnp.abs(resp))
        hist_scale = None
        if QUANTIZE:
            key = jax.random.fold_in(jax.random.PRNGKey(7), i)
            grad, hess, gs, hs = discretize_gradients_levels(
                grad, hess, key, n_levels=4, stochastic=True)
            hist_scale = jnp.stack([gs, hs])
        tree, leaf_of_row = grow_tree_batched(
            bins_a, grad, hess, None, num_bins, nan_bin, is_cat,
            None, hp, batch=K, hist_scale=hist_scale)
        return scores + 0.1 * take_small_table(tree.leaf_value,
                                               leaf_of_row), None
    scores, _ = jax.lax.scan(step, scores, jnp.arange(ITERS))
    return scores


scores = jnp.zeros(N, jnp.float32)
out = run(scores, bins_d, label_d)
float(out[0])

tdir = "/tmp/jaxprof"
os.system(f"rm -rf {tdir}")
with jax.profiler.trace(tdir):
    out = run(scores, bins_d, label_d)
    float(out[0])

# parse trace
files = glob.glob(f"{tdir}/**/*.trace.json.gz", recursive=True)
assert files, os.popen(f"find {tdir} | head -50").read()
with gzip.open(files[0], "rt") as fh:
    trace = json.load(fh)

events = trace["traceEvents"]
# find device lanes: pid whose process name mentions TPU/device
pid_names = {}
tid_names = {}
for e in events:
    if e.get("ph") == "M":
        if e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
        if e.get("name") == "thread_name":
            tid_names[(e["pid"], e["tid"])] = e["args"].get("name", "")

agg = defaultdict(float)
cnt = defaultdict(int)
total = 0.0
for e in events:
    if e.get("ph") != "X":
        continue
    pname = pid_names.get(e["pid"], "")
    tname = tid_names.get((e["pid"], e["tid"]), "")
    if "TPU" not in pname and "tpu" not in pname.lower():
        continue
    if "step" in tname.lower():
        continue  # step lane duplicates
    name = e.get("name", "?")
    dur = e.get("dur", 0) / 1e3  # ms
    agg[name] += dur
    cnt[name] += 1
    total += dur

print(f"# lanes: {set(pid_names.values())}")
print(f"# total device time: {total:.1f} ms over {ITERS} iters "
      f"=> {total/ITERS:.1f} ms/tree  (K={K})")
rows = sorted(agg.items(), key=lambda kv: -kv[1])[:45]
for name, ms in rows:
    print(f"{ms/ITERS:9.2f} ms/tree  x{cnt[name]//ITERS:<5} {name[:110]}")
