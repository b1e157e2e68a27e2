"""Test configuration.

The suite is CPU-only: it runs on a virtual 8-device CPU mesh (the TPU
analogue of the reference's tests/distributed/_test_distributed.py
localhost-cluster mockup) — ``xla_force_host_platform_device_count=8``
gives shard_map/psum tests real multi-device semantics without hardware,
and every Pallas kernel runs in interpret mode.  The chip itself is
exercised by ``chip_smoke.py`` at the repo root, and
``tests/test_chip_compile.py`` compiles the main path's kernels for a
described (not attached) TPU v5e.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from lightgbm_tpu.ops.compile_cache import use_persistent_cache  # noqa: E402

# a plugin may have imported jax before the environment write above
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: XLA keys it by HLO hash, so reruns after
# unrelated edits skip most compiles.  The whole suite under the driver's
# command on the 8-core sandbox (PR 48): 935 s from an empty cache (1,069 in
# a second cold run), 743 s over the 77 MB that run leaves.  The driver's
# first run of a PR is always the cold one; what a warm cache cannot serve
# is memory (the CPU's one-hot histograms) and the children's own processes.
use_persistent_cache(os.path.join(os.path.dirname(__file__), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

#: small seeded stand-ins for the upstream examples/ tree
#: (tests/data/examples/make_examples.py wrote them)
EXAMPLES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "examples")


@pytest.fixture(scope="session")
def binary_example():
    """The binary_classification example data (TSV, label col 0)."""
    tr = np.loadtxt(f"{EXAMPLES}/binary_classification/binary.train")
    te = np.loadtxt(f"{EXAMPLES}/binary_classification/binary.test")
    return (tr[:, 1:], tr[:, 0].astype(np.float64),
            te[:, 1:], te[:, 0].astype(np.float64))


@pytest.fixture(scope="session")
def regression_example():
    tr = np.loadtxt(f"{EXAMPLES}/regression/regression.train")
    te = np.loadtxt(f"{EXAMPLES}/regression/regression.test")
    return (tr[:, 1:], tr[:, 0], te[:, 1:], te[:, 0])


@pytest.fixture(scope="session")
def synthetic_binary():
    rng = np.random.default_rng(42)
    n, f = 2000, 8
    X = rng.normal(size=(n, f))
    w = rng.normal(size=f)
    y = ((X @ w + 0.3 * X[:, 0] * X[:, 1] +
          rng.normal(scale=0.5, size=n)) > 0).astype(np.float64)
    return X, y


@pytest.fixture(scope="session")
def synthetic_regression():
    rng = np.random.default_rng(7)
    n, f = 2000, 6
    X = rng.normal(size=(n, f))
    y = X @ rng.normal(size=f) + np.sin(X[:, 0] * 2) + \
        rng.normal(scale=0.1, size=n)
    return X, y


@pytest.fixture(scope="session")
def synthetic_ranking():
    rng = np.random.default_rng(3)
    nq, per_q = 60, 20
    X = rng.normal(size=(nq * per_q, 6))
    rel = (X @ rng.normal(size=6)) + rng.normal(scale=0.5, size=nq * per_q)
    # labels 0..4 by within-query rank of relevance
    y = np.zeros(nq * per_q)
    for q in range(nq):
        s = slice(q * per_q, (q + 1) * per_q)
        y[s] = np.digitize(rel[s], np.quantile(rel[s], [0.5, 0.75, 0.9, 0.97]))
    group = np.full(nq, per_q)
    return X, y, group
