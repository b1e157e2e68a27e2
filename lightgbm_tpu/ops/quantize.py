"""Gradient quantization (``use_quantized_grad``).

TPU-native re-design of the reference gradient discretizer (reference:
src/treelearner/gradient_discretizer.cpp ``DiscretizeGradients`` — scales
gradients to ``num_grad_quant_bins`` integer levels, grad to
[-bins/2, bins/2] and hessian to [0, bins], with optional stochastic
rounding; histograms then accumulate int16/int32 integers,
feature_histogram.hpp:177 ``FindBestThresholdInt``).

The TPU realization: gradients are carried as INTEGER LEVELS in f32.
Small integers are exactly representable in bfloat16, so the fast bf16
MXU histogram kernel (ops/hist_pallas.py) accumulates them EXACTLY — f32
accumulation of integer sums is exact below 2^24 — and one deterministic
scale multiply on the [K, F, B, 4] histogram restores real units.  This
is the reference's int-accumulation design mapped to the MXU: the speed
of the bf16 mode with bit-deterministic split sums across devices and
meshes.  ``tpu_hist_dtype=int8`` additionally rides the v5e int8
systolic path (~1.6x the bf16 rate; int32 product accumulation —
round-4 toolchains legalize i8 casts and dots, unlike round 3's).
Exactness bound: n_rows * (num_grad_quant_bins/2) < 2^24,
i.e. ~8.3M rows at the default 4 levels — beyond that, sums round at
1 ulp f32 (the reference's int32 histograms overflow-guard similarly by
bit-width selection, gradient_discretizer.hpp).

``quant_train_renew_leaf`` recomputes final leaf outputs from the TRUE
gradients (reference ``RenewIntGradTreeOutput``): per-leaf sums of the
f32 gradients and hessians by ``leaf_of_row``.  On the TPU they come from
``ops/table.py sum_small_table``, the second of a pair of one-hot MXU
kernels over a table bounded by ``num_leaves``: ``take_small_table``
looks leaf values up FROM the table (the score update),
``sum_small_table`` sums row values INTO it (this renewal).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .table import sum_small_table


def _pow2_ceil(x: jax.Array) -> jax.Array:
    """Smallest power of two >= x (positive finite x)."""
    return jnp.exp2(jnp.ceil(jnp.log2(x)))


@functools.partial(jax.jit, static_argnames=("n_levels", "stochastic",
                                             "constant_hessian", "axis_name"))
def discretize_gradients(grad: jax.Array, hess: jax.Array,
                         key: jax.Array, *, n_levels: int = 4,
                         stochastic: bool = True,
                         constant_hessian: bool = False,
                         axis_name: Optional[str] = None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Quantize (grad, hess) to n_levels integer steps (fake-quant f32).

    Scales follow gradient_discretizer.cpp: g_scale = max|g| / (levels/2),
    h_scale = max|h| / levels (max|h| alone for constant-hessian
    objectives).  Under ``shard_map`` the maxima are psum-maxed so every
    shard quantizes on the same grid (the reference's GlobalSyncUpByMax).
    """
    max_g = jnp.max(jnp.abs(grad))
    max_h = jnp.max(jnp.abs(hess))
    if axis_name is not None:
        max_g = lax.pmax(max_g, axis_name)
        max_h = lax.pmax(max_h, axis_name)
    # scales round UP to a power of two: scale * level is then EXACT in
    # f32 (the scale only shifts the exponent), so histogram bin values
    # stay order-independent under summation and the matmul-cumsum split
    # scan (ops/split.py _cumsum_bins), histogram subtraction and the
    # bf16==f32 decision-parity contract are all exact.  Grid at most 2x
    # coarser than max/levels; stochastic rounding keeps it unbiased.
    g_scale = _pow2_ceil(jnp.maximum(max_g / (n_levels // 2), 1e-20))
    h_scale = _pow2_ceil(jnp.maximum(max_h if constant_hessian
                                     else max_h / n_levels, 1e-20))
    kg, kh = jax.random.split(key)
    if stochastic:
        ug = jax.random.uniform(kg, grad.shape)
        uh = jax.random.uniform(kh, hess.shape)
        gi = jnp.floor(grad / g_scale + ug)
        hi = jnp.floor(hess / h_scale + uh)
    else:
        gi = jnp.round(grad / g_scale)
        hi = jnp.round(hess / h_scale)
    return gi * g_scale, hi * h_scale


@functools.partial(jax.jit, static_argnames=("n_levels", "stochastic",
                                             "constant_hessian", "axis_name"))
def discretize_gradients_levels(grad: jax.Array, hess: jax.Array,
                                key: jax.Array, *, n_levels: int = 4,
                                stochastic: bool = True,
                                constant_hessian: bool = False,
                                axis_name: Optional[str] = None):
    """Quantize to INTEGER LEVELS (f32) plus per-tree scales.

    Returns (g_levels, h_levels, g_scale, h_scale): g_levels in
    [-n_levels/2, n_levels/2], h_levels in [0, n_levels] — exactly
    representable in bfloat16, the property the exact-bf16 histogram path
    relies on.  real_value ~= level * scale.
    """
    max_g = jnp.max(jnp.abs(grad))
    max_h = jnp.max(jnp.abs(hess))
    if axis_name is not None:
        max_g = lax.pmax(max_g, axis_name)
        max_h = lax.pmax(max_h, axis_name)
    # scales round UP to a power of two: scale * level is then EXACT in
    # f32 (the scale only shifts the exponent), so histogram bin values
    # stay order-independent under summation and the matmul-cumsum split
    # scan (ops/split.py _cumsum_bins), histogram subtraction and the
    # bf16==f32 decision-parity contract are all exact.  Grid at most 2x
    # coarser than max/levels; stochastic rounding keeps it unbiased.
    g_scale = _pow2_ceil(jnp.maximum(max_g / (n_levels // 2), 1e-20))
    h_scale = _pow2_ceil(jnp.maximum(max_h if constant_hessian
                                     else max_h / n_levels, 1e-20))
    kg, kh = jax.random.split(key)
    if stochastic:
        ug = jax.random.uniform(kg, grad.shape)
        uh = jax.random.uniform(kh, hess.shape)
        gi = jnp.floor(grad / g_scale + ug)
        hi = jnp.floor(hess / h_scale + uh)
    else:
        gi = jnp.round(grad / g_scale)
        hi = jnp.round(hess / h_scale)
    return gi, hi, g_scale, h_scale


@jax.jit
def _leaf_outputs(gsum, hsum, lambda_l1, lambda_l2):
    t = jnp.sign(gsum) * jnp.maximum(jnp.abs(gsum) - lambda_l1, 0.0)
    return -t / (hsum + lambda_l2 + 1e-15)


def renew_leaf_values(leaf_of_row: jax.Array, grad: jax.Array,
                      hess: jax.Array, row_mask: Optional[jax.Array],
                      num_leaves: int, lambda_l1: float,
                      lambda_l2: float) -> jax.Array:
    """Exact leaf outputs from TRUE gradients after a quantized-structure
    tree (reference gradient_discretizer.hpp RenewIntGradTreeOutput):
    out[l] = -T(sum g_l) / (sum h_l + l2) with L1 soft-threshold T.

    Not jitted as a whole: ``sum_small_table`` picks its path from where
    the rows live (backend, devices, sharding), which a trace hides."""
    gsum, hsum = sum_small_table(leaf_of_row, grad, hess, row_mask,
                                 num_leaves)
    return _leaf_outputs(gsum, hsum, lambda_l1, lambda_l2)
