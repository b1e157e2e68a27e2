"""ops/table.py: the pair of one-hot kernels over a table bounded by
``num_leaves`` — ``take_small_table`` (lookup from it, the score update)
and ``sum_small_table`` (sums into it, leaf renewal) — in interpret mode
against their XLA references, and the routing that picks a path from
where the rows live.

The error bound held for the sums.  One entry's sum is a three-level f32
sum: the MXU adds ``rows_per_dot`` exact products (a bfloat16 part times
0 or 1), the kernel adds the dots of a block, the resident output block
adds the grid's blocks, and two more adds join the three parts.  So
``|sum - exact| <= depth * 2**-24 * sum|x|`` to first order, with
``depth = rows_per_dot + blocks_dots + grid + 2``, and the parts' own
magnitudes sum to at most ``(1 + 2**-8) * sum|x|``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import quantize as Q, table as T
from lightgbm_tpu.parallel.mesh import DATA_AXIS, make_mesh

BLK, DOT = 256, 128           # two dots a block, so every level is walked
U = 2.0 ** -24


def _rows(n, size, seed, negatives=True):
    """Indices with strays on either side of ``[0, size)`` and one entry
    no row names; values over eleven decades.  ``negatives=False`` for
    the scatter-add: jnp's ``.at[]`` wraps a negative index round to the
    table's end (the leaf map has none); past the end it drops, as the
    kernel does on both sides."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(-2, size + 3, size=n).astype(np.int32)
    empty = size // 2
    idx[idx == empty] = -1
    if not negatives:
        idx[idx < 0] = size + 5
    mag = 10.0 ** rng.uniform(-8, 3, size=n)
    g = (rng.normal(size=n) * mag).astype(np.float32)
    h = (np.abs(rng.normal(size=n)) * mag[::-1]).astype(np.float32)
    return idx, g, h, empty


def _exact(idx, v, keep, size):
    ok = keep & (idx >= 0) & (idx < size)
    out = np.zeros(size)
    np.add.at(out, idx[ok], v[ok].astype(np.float64))
    mass = np.zeros(size)
    np.add.at(mass, idx[ok], np.abs(v[ok]).astype(np.float64))
    return out, mass


def _mask(kind, n, seed):
    if kind == "none":
        return None
    if kind == "all_false":
        return np.zeros(n, bool)
    return np.random.default_rng(seed + 1).random(n) < 0.6


@pytest.mark.parametrize("mask_kind", ["none", "random", "all_false"])
@pytest.mark.parametrize("size", [2, 31, 255, 2048])
@pytest.mark.parametrize("n", [2 * BLK, 2 * BLK + 1, 13, 1])
def test_sum_kernel_against_scatter_add_and_f64(n, size, mask_kind):
    seed = n * 7 + size
    idx, g, h, empty = _rows(n, size, seed)
    mask = _mask(mask_kind, n, seed)
    got = T._sum_pallas(idx, g, h, mask, size=size, rows_per_block=BLK,
                        rows_per_dot=DOT, interpret=True)
    ref = T._sum_scatter(np.where(idx < 0, size + 5, idx), g, h, mask,
                         size=size)
    keep = np.ones(n, bool) if mask is None else mask
    depth = DOT + BLK // DOT + -(-n // BLK) + 2
    for v, mine, xla in zip((g, h), got, ref):
        mine, xla = np.asarray(mine, np.float64), np.asarray(xla, np.float64)
        assert mine.shape == (size,)
        exact, mass = _exact(idx, v, keep, size)
        assert np.all(np.abs(mine - exact) <= 1.01 * depth * U * mass)
        # the scatter-add is one sequential f32 sum: n roundings at most
        assert np.all(np.abs(mine - xla) <= (1.01 * depth + n) * U * mass)
        assert mine[empty] == 0.0
        if mask_kind == "all_false":
            assert not mine.any()


def test_sum_kernel_is_exact_where_f32_sums_are():
    """Small integers: every partial sum is exact in f32, so the order
    of the sum cannot show and the kernel equals the scatter-add."""
    rng = np.random.default_rng(0)
    n, size = 5 * BLK + 77, 255
    idx = rng.integers(0, size, size=n).astype(np.int32)
    g = rng.integers(-8, 9, size=n).astype(np.float32)
    h = rng.integers(0, 5, size=n).astype(np.float32)
    got = T._sum_pallas(idx, g, h, None, size=size, rows_per_block=BLK,
                        rows_per_dot=DOT, interpret=True)
    ref = T._sum_scatter(idx, g, h, None, size=size)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sum_kernel_keeps_all_24_bits_of_a_value():
    """No bfloat16 rounding of g or h: one row a leaf gives the value
    back bit for bit, whatever its low bits."""
    rng = np.random.default_rng(1)
    size = 255
    idx = rng.permutation(size).astype(np.int32)
    g = (rng.normal(size=size) * 10.0 ** rng.uniform(-8, 3, size=size)
         ).astype(np.float32)
    h = np.nextafter(np.float32(1.0), np.float32(2.0)) * np.ones(
        size, np.float32)
    gs, hs = T._sum_pallas(idx, g, h, None, size=size, interpret=True)
    np.testing.assert_array_equal(np.asarray(gs)[idx], g)
    np.testing.assert_array_equal(np.asarray(hs)[idx], h)


def test_sum_kernel_default_blocks_walk_the_tail():
    """The shipped block shape on a row count that no block divides."""
    n, size = 3 * 16384 + 1250, 255
    idx, g, h, _ = _rows(n, size, 3)
    gs, hs = T._sum_pallas(idx, g, h, None, size=size, interpret=True)
    for v, mine in ((g, gs), (h, hs)):
        exact, mass = _exact(idx, v, np.ones(n, bool), size)
        assert np.all(np.abs(np.asarray(mine, np.float64) - exact)
                      <= 1.01 * (1024 + 16 + 4 + 2) * U * mass)


# ----------------------------------------------------------------- routing
@pytest.fixture
def kernel_on_cpu(monkeypatch):
    """What a TPU process sees, with the kernels in interpret mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(T, "_sum_pallas",
                        functools.partial(T._sum_pallas, interpret=True))
    monkeypatch.setattr(T, "_take_pallas",
                        functools.partial(T._take_pallas, interpret=True))


@pytest.fixture
def no_kernel(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("the kernel ran where the fallback must")
    monkeypatch.setattr(T, "_sum_pallas", refuse)
    monkeypatch.setattr(T, "_take_pallas", refuse)


def _close(got, idx, g, h, keep, size):
    for v, mine in zip((g, h), got):
        exact, mass = _exact(idx, v, keep, size)
        assert np.all(np.abs(np.asarray(mine, np.float64) - exact)
                      <= (idx.shape[0] + 1100) * U * mass)


def test_sums_take_the_scatter_add_off_the_tpu(no_kernel):
    idx, g, h, _ = _rows(700, 31, 4, negatives=False)
    got = T.sum_small_table(jnp.asarray(idx), jnp.asarray(g), jnp.asarray(h),
                            None, 31)
    _close(got, idx, g, h, np.ones(700, bool), 31)


def test_sums_take_the_kernel_on_one_tpu_device_and_when_traced(
        kernel_on_cpu, monkeypatch):
    idx, g, h, _ = _rows(700, 31, 5)
    mask = _mask("random", 700, 5)
    monkeypatch.setattr(T, "_sum_scatter", None)      # must not be reached
    got = T.sum_small_table(jnp.asarray(idx), jnp.asarray(g), jnp.asarray(h),
                            jnp.asarray(mask), 31)
    _close(got, idx, g, h, mask, 31)
    traced = jax.jit(lambda i, a, b: T.sum_small_table(i, a, b, None, 31))(
        idx, g, h)
    _close(traced, idx, g, h, np.ones(700, bool), 31)


def test_sums_over_2048_entries_take_the_scatter_add(kernel_on_cpu,
                                                    no_kernel):
    idx, g, h, _ = _rows(300, 2049, 6, negatives=False)
    got = T.sum_small_table(jnp.asarray(idx), jnp.asarray(g), jnp.asarray(h),
                            None, 2049)
    _close(got, idx, g, h, np.ones(300, bool), 2049)


@pytest.mark.parametrize("masked", [False, True])
def test_sums_run_per_shard_with_a_psum_on_row_sharded_operands(
        kernel_on_cpu, masked):
    """tree_learner=data renews leaves from the row-sharded leaf map a
    shard_map grower returns; a Mosaic kernel cannot be partitioned
    automatically (PR 24's four-chip failure at the score update)."""
    mesh = make_mesh()
    n, size = mesh.devices.size * 192, 255
    idx, g, h, _ = _rows(n, size, 7)
    mask = _mask("random", n, 7) if masked else None
    rows = NamedSharding(mesh, P(DATA_AXIS))
    put = lambda a: None if a is None else jax.device_put(a, rows)
    got = T.sum_small_table(put(idx), put(g), put(h), put(mask), size)
    assert all(o.sharding.is_fully_replicated for o in got)
    _close(got, idx, g, h, np.ones(n, bool) if mask is None else mask, size)


def test_sums_on_another_multi_device_placement_take_the_scatter_add(
        kernel_on_cpu, monkeypatch):
    mesh = make_mesh()
    idx, g, h, _ = _rows(mesh.devices.size * 64, 31, 8, negatives=False)
    everywhere = NamedSharding(mesh, P())
    put = lambda a: jax.device_put(a, everywhere)
    monkeypatch.setattr(T, "_sum_pallas", None)       # must not be reached
    got = T.sum_small_table(put(idx), put(g), put(h), None, 31)
    _close(got, idx, g, h, np.ones(idx.shape[0], bool), 31)


# ------------------------------------------------------------ leaf renewal
@pytest.mark.parametrize("l1,l2", [(0.0, 0.0), (0.7, 0.0), (0.7, 2.5)])
def test_renew_leaf_values_on_the_kernel_path(kernel_on_cpu, l1, l2):
    n, leaves = 3000, 31
    idx, g, h, empty = _rows(n, leaves, 9)
    g = (g / (1.0 + np.abs(g))).astype(np.float32)      # some |sum| < l1
    mask = _mask("random", n, 9)
    got = np.asarray(Q.renew_leaf_values(
        jnp.asarray(idx), jnp.asarray(g), jnp.asarray(h), jnp.asarray(mask),
        num_leaves=leaves, lambda_l1=l1, lambda_l2=l2), np.float64)
    gs, _ = _exact(idx, g, mask, leaves)
    hs, _ = _exact(idx, h, mask, leaves)
    want = -np.sign(gs) * np.maximum(np.abs(gs) - l1, 0.0) / (hs + l2 + 1e-15)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-6)
    assert got[empty] == 0.0
    if l1:
        assert np.any((np.abs(gs) < l1) & (got == 0.0) & (gs != 0.0))


def test_renew_leaf_values_off_the_tpu_is_the_scatter_add(no_kernel):
    n, leaves = 900, 15
    idx, g, h, _ = _rows(n, leaves, 10, negatives=False)
    got = Q.renew_leaf_values(jnp.asarray(idx), jnp.asarray(g),
                              jnp.asarray(h), None, num_leaves=leaves,
                              lambda_l1=0.1, lambda_l2=1.0)
    gs = np.zeros(leaves, np.float32)
    hs = np.zeros(leaves, np.float32)
    ok = (idx >= 0) & (idx < leaves)
    np.add.at(gs, idx[ok], g[ok])
    np.add.at(hs, idx[ok], h[ok])
    want = -np.sign(gs) * np.maximum(np.abs(gs) - np.float32(0.1), 0) / (
        hs + np.float32(1.0) + np.float32(1e-15))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-7)


PARAMS = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
          "verbose": -1, "tpu_split_batch": 4, "use_quantized_grad": True,
          "quant_train_renew_leaf": True, "lambda_l1": 0.5}


def _train(rounds=6, **extra):
    rng = np.random.default_rng(21)
    X = rng.normal(size=(3000, 8))
    y = ((X @ rng.normal(size=8) + rng.normal(scale=0.5, size=3000)) > 0
         ).astype(np.float64)
    params = {**PARAMS, **extra}
    bst = lgb.train(params, lgb.Dataset(X, label=y, params=params),
                    num_boost_round=rounds)
    return bst, X


@pytest.mark.parametrize("extra", [{}, {"bagging_fraction": 0.6,
                                        "bagging_freq": 1}],
                         ids=["fused", "fused_bagging"])
def test_training_on_the_kernel_sums_grows_the_same_trees(monkeypatch, extra):
    """End to end with ``lambda_l1 > 0``: the sums in another order move
    leaf values in their last bits and nothing else over a few rounds."""
    plain, X = _train(**extra)

    def kernel_sums(idx, g, h, mask, size):
        return T._sum_pallas(idx, g, h, mask, size=size, rows_per_block=512,
                             rows_per_dot=256, interpret=True)
    monkeypatch.setattr(Q, "sum_small_table", kernel_sums)
    # the runner is cached by its configuration, not by this patch
    from lightgbm_tpu.ops.compile_cache import GLOBAL_COMPILE_CACHE
    GLOBAL_COMPILE_CACHE.clear()
    kernel, _ = _train(**extra)
    GLOBAL_COMPILE_CACHE.clear()
    assert kernel._gbdt.metrics.counter("fused_rounds") == 6
    a, b = plain.dump_model(), kernel.dump_model()

    def walk(x, y):
        if "leaf_value" in x:
            assert "leaf_value" in y
            assert x["leaf_value"] == pytest.approx(y["leaf_value"],
                                                    rel=1e-4, abs=1e-7)
            return
        assert (x["split_feature"], x["threshold"]) == (
            y["split_feature"], y["threshold"])
        walk(x["left_child"], y["left_child"])
        walk(x["right_child"], y["right_child"])
    for ta, tb in zip(a["tree_info"], b["tree_info"]):
        walk(ta["tree_structure"], tb["tree_structure"])
    np.testing.assert_allclose(kernel.predict(X), plain.predict(X),
                               rtol=1e-4, atol=1e-6)


def test_data_parallel_training_renews_leaves_per_shard(monkeypatch):
    """The per-iteration loop under ``tree_learner=data``: the leaf map
    the shard_map grower returns and the gradients beside it are row-
    sharded as the per-shard route needs them (a placement it does not
    know falls back to the scatter-add in silence, so count)."""
    extra = {"tree_learner": "data", "lambda_l1": 0.0}
    rng = np.random.default_rng(22)
    X = rng.normal(size=(4096, 8))
    y = ((X @ rng.normal(size=8)) > 0).astype(np.float64)
    params = {**PARAMS, **extra}

    def train():
        return lgb.train(params, lgb.Dataset(X, label=y, params=params),
                         num_boost_round=4)
    plain = train()
    routes = []
    real_per_shard, real_sums = T._sum_per_shard, T.sum_small_table

    def per_shard(mesh, spec, masked, size):
        routes.append((mesh.devices.size, masked, size))
        return real_per_shard(mesh, spec, masked, size)

    def as_on_a_tpu(*args):
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            return real_sums(*args)
    monkeypatch.setattr(T, "_sum_pallas",
                        functools.partial(T._sum_pallas, interpret=True))
    monkeypatch.setattr(T, "_sum_per_shard", per_shard)
    monkeypatch.setattr(Q, "sum_small_table", as_on_a_tpu)
    kernel = train()
    assert kernel._gbdt.parallel_mode == "data"
    assert routes == [(jax.device_count(), False, 15)] * 4
    np.testing.assert_allclose(kernel.predict(X), plain.predict(X),
                               rtol=1e-4, atol=1e-6)


# ------------------------------------------------------------- the lookup
def _lookup_case(n, size, seed):
    rng = np.random.default_rng(seed)
    idx = rng.integers(-2, size + 3, size=n).astype(np.int32)
    table = (rng.normal(size=size)
             * 10.0 ** rng.uniform(-8, 3, size=size)).astype(np.float32)
    ok = (idx >= 0) & (idx < size)
    want = np.where(ok, table[np.clip(idx, 0, size - 1)], np.float32(0))
    return idx, table, want


# n, rows_per_block, rows_per_dot: whole blocks, a tail of one row, n below
# a chunk, one row; a block of four chunks whose last block ends in its
# third chunk; n below 128 under the shipped block shape
TAKE_SHAPES = [(256, 128, 128), (257, 128, 128), (13, 128, 128),
               (1, 128, 128), (1337, 512, 128), (127, 8192, 1024)]


@pytest.mark.parametrize("size", [2, 8, 9, 16, 17, 31, 255, 256, 257, 1024,
                                  2048])
@pytest.mark.parametrize("n,blk,dot", TAKE_SHAPES)
def test_take_kernel_against_indexing(n, blk, dot, size):
    """Bit for bit at every size (one entry short of, on and past a
    radix digit and a one-hot tile), strays on either side reading 0."""
    idx, table, want = _lookup_case(n, size, n + size)
    got = T._take_pallas(idx, table, rows_per_block=blk, rows_per_dot=dot,
                         interpret=True)
    assert got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("size", [31, 255])
def test_take_kernel_default_blocks_walk_the_tail(size):
    """The shipped block shape on a row count that no block divides."""
    n = 4 * 8192 + 1250
    idx, table, want = _lookup_case(n, size, size)
    got = T._take_pallas(idx, table, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_take_off_the_tpu_is_the_xla_lookup(no_kernel):
    idx = np.array([-1, 0, 3, 4, 5, 2], np.int32)
    table = np.arange(5, dtype=np.float32) + 0.5
    got = T.take_small_table(jnp.asarray(table), jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got),
                                  [0.0, 0.5, 3.5, 4.5, 0.0, 2.5])


def test_take_on_one_tpu_device_and_when_traced_is_the_kernel(kernel_on_cpu):
    rng = np.random.default_rng(2)
    idx = rng.integers(-1, 255, size=700).astype(np.int32)
    table = rng.normal(size=255).astype(np.float32)
    want = np.where(idx >= 0, table[np.clip(idx, 0, 254)], np.float32(0))
    np.testing.assert_array_equal(
        np.asarray(T.take_small_table(jnp.asarray(table), jnp.asarray(idx))),
        want)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(T.take_small_table)(table, idx)), want)


def test_take_over_2048_entries_is_the_xla_lookup(kernel_on_cpu, no_kernel):
    rng = np.random.default_rng(3)
    idx = rng.integers(0, 2049, size=300).astype(np.int32)
    table = rng.normal(size=2049).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(T.take_small_table(jnp.asarray(table), jnp.asarray(idx))),
        table[idx])
