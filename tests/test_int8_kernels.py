"""int8 MXU histogram mode (tpu_hist_dtype=int8, round 4).

With use_quantized_grad the gradients are small-integer levels, so the
int8 kernels' products are exact int32 — every kernel must match the
float32 path BIT-EXACTLY on integer inputs.  Exercised through the
Pallas interpreter on CPU; the on-chip speed claim (~1.6x bf16) lives in
docs/PERF_NOTES.md.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.hist_pallas import (
    _histogram_leaves_impl, histogram_payload_pallas,
    histogram_radix_joint_pallas, histogram_radix_single_pallas)
import lightgbm_tpu.ops.histogram as H


def _mk(n=4096, f=9, n_bins=64, k=4, seed=0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, n_bins - 1, size=(n, f)).astype(np.uint8)
    grad = rng.integers(-3, 4, size=n).astype(np.float32)   # int levels
    hess = rng.integers(0, 5, size=n).astype(np.float32)
    lor = rng.integers(-1, 7, size=n).astype(np.int32)
    leaves = np.array([0, 2, 5, 6][:k], np.int32)
    return (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(lor), jnp.asarray(leaves))


def _assert_same(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flat_masked_int8_matches_f32():
    bins, grad, hess, lor, leaves = _mk()
    kw = dict(n_bins=64, rows_per_block=512, interpret=True)
    got = _histogram_leaves_impl(bins.T, grad, hess, lor, leaves,
                                 compute_dtype=jnp.int8, **kw)
    want = _histogram_leaves_impl(bins.T, grad, hess, lor, leaves,
                                  compute_dtype=jnp.float32, **kw)
    _assert_same(got, want)


@pytest.mark.parametrize("stop_at", [None, 0, 700],
                         ids=["every_block", "none", "second_block"])
def test_payload_int8(stop_at):
    """int8 against float32 over the whole bucket, and with the count
    cut short so that the grid stops inside the second block."""
    bins, grad, hess, lor, leaves = _mk()
    n, f = bins.shape
    words = H.bins_to_words(bins)
    member = jnp.any(lor[None, :] == leaves[:, None], axis=0)
    cnt = jnp.sum(member.astype(jnp.int32))
    if stop_at is not None:
        cnt = jnp.int32(stop_at)
    key = jnp.where(member, jnp.arange(n, dtype=jnp.int32),
                    jnp.arange(n, dtype=jnp.int32) | (1 << 30))
    S = 2560
    payload = jnp.concatenate([
        words,
        jax.lax.bitcast_convert_type(grad, jnp.int32)[:, None],
        jax.lax.bitcast_convert_type(hess, jnp.int32)[:, None],
        lor[:, None]], axis=1)
    pc = payload[jnp.sort(key, stable=False)[:S] & ((1 << 30) - 1)]
    kw = dict(num_f=f, n_bins=64, rows_per_block=512, interpret=True)
    got = histogram_payload_pallas(pc.T, leaves, cnt,
                                   compute_dtype=jnp.int8, **kw)
    want = histogram_payload_pallas(pc.T, leaves, cnt,
                                    compute_dtype=jnp.float32, **kw)
    _assert_same(got, want)


def test_radix_single_int8():
    bins, grad, hess, lor, _ = _mk()
    kw = dict(n_bins=64, rows_per_block=512, interpret=True)
    got = histogram_radix_single_pallas(bins.T, grad, hess, lor,
                                        compute_dtype=jnp.int8, **kw)
    want = histogram_radix_single_pallas(bins.T, grad, hess, lor,
                                         compute_dtype=jnp.float32, **kw)
    _assert_same(got, want)


def test_radix_joint_int8():
    bins, grad, hess, lor, leaves = _mk(k=2)
    kw = dict(n_bins=64, rows_per_block=512, interpret=True)
    got = histogram_radix_joint_pallas(bins.T, grad, hess, lor, leaves,
                                       compute_dtype=jnp.int8, **kw)
    want = histogram_radix_joint_pallas(bins.T, grad, hess, lor, leaves,
                                        compute_dtype=jnp.float32, **kw)
    _assert_same(got, want)


def test_hist_dtype_gating():
    """int8 without quantized gradients degrades to bfloat16 (warned)."""
    from lightgbm_tpu.boosting.gbdt import _resolve_hist_dtype
    from lightgbm_tpu.config import Config

    c = Config({"tpu_hist_dtype": "int8"})
    assert _resolve_hist_dtype(c) == "bfloat16"
    c = Config({"tpu_hist_dtype": "int8", "use_quantized_grad": True})
    assert _resolve_hist_dtype(c) == "int8"
    c = Config({"tpu_hist_dtype": "int8", "use_quantized_grad": True,
                "num_grad_quant_bins": 255})
    assert _resolve_hist_dtype(c) == "bfloat16"
    c = Config({"tpu_hist_dtype": "int8", "use_quantized_grad": True,
                "deterministic": True})
    assert _resolve_hist_dtype(c) == "float32"
