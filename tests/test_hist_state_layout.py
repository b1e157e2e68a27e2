"""The per-leaf histogram state of the batched grower has ONE form for the
whole tree loop, ``[rows + 1, C, F, B]`` (``batch_grower.write_children``
says which and why).  That is data movement only: the trees it grows are
held here BIT FOR BIT to those of an update written in the form the state
had before (channel last, gathers, a ``where`` and two ``.at[].set``), on
the jobs whose paths read the state: 2,000 columns as the cell
``epsilon-train`` rehearses them, 67 columns, the bounded pool, a
categorical column and forced splits.  An invalid slot leaves its two
places as they were, which ``benchmark/tools/faults_wide.py``
``skip_state_update`` plants its fault by."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import batch_grower
from lightgbm_tpu.ops.split import SplitHyper


def _channel_last_write(hist, parents, new_leaves, valid, h_left, h_right):
    """The update in the form the state had before: on ``[rows, F, B, C]``,
    an invalid slot reads back what was there and writes it again."""
    old = jnp.moveaxis(hist[:-1], 1, -1)
    v = valid[:, None, None, None]
    new = old.at[parents].set(jnp.where(v, h_left, old[parents]))
    new = new.at[new_leaves].set(jnp.where(v, h_right, new[new_leaves]))
    return jnp.concatenate([jnp.moveaxis(new, -1, 1), hist[-1:]])


def _dataset(X, y, **kw):
    ds = lgb.Dataset(X, label=y, params={"verbose": -1}, **kw)
    ds = ds.construct()._inner
    return ds, (jnp.asarray(ds.bins),
                jnp.asarray((0.5 - y).astype(np.float32)),
                jnp.full((len(y),), 0.25, jnp.float32), None,
                jnp.asarray(ds.num_bins_array()),
                jnp.asarray(ds.nan_bin_array()),
                jnp.asarray(ds.categorical_array()), None)


def _dense(n, f, seed):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    w = rng.normal(size=f) * (rng.random(f) < 0.3)
    y = (X @ w + rng.normal(size=n) > 0).astype(np.float64)
    return X, y


def _epsilon_rehearsal():
    """1,024 x 2,000 from the cell's own generator (the state's shape, which
    this file is about, follows from the columns and the leaves; the rows
    only size the CPU's one-hot: 2 GB a histogram here), the cell's rehearsal
    parameters: 15 leaves, 8 splits a pass, quantised gradients as
    integer levels with their scales."""
    import cells
    cfg = cells.find("epsilon-train", rows=1024, valid_rows=128)[1]
    (_, x64, y), _ = cells.data(cfg)
    ds, args = _dataset(x64.T, y)         # the generator is feature-major
    levels = jnp.asarray(np.where(y > 0, -2.0, 2.0).astype(np.float32))
    args = (args[0], levels, jnp.ones_like(levels)) + args[3:]
    hp = SplitHyper(num_leaves=15, min_data_in_leaf=1,
                    min_sum_hessian_in_leaf=1.0, n_bins=ds.device_n_bins(),
                    hist_dtype="int8")
    return args, dict(hp=hp, batch=8,
                      hist_scale=jnp.asarray([0.25, 0.25], jnp.float32))


def _narrow():
    ds, args = _dataset(*_dense(6000, 67, 1))
    hp = SplitHyper(num_leaves=31, min_data_in_leaf=5,
                    n_bins=ds.device_n_bins())
    return args, dict(hp=hp, batch=8)


def _pooled():
    ds, args = _dataset(*_dense(6000, 12, 2))
    hp = SplitHyper(num_leaves=63, min_data_in_leaf=5,
                    n_bins=ds.device_n_bins(), hist_pool_slots=14)
    assert 0 < hp.hist_pool_slots < hp.num_leaves       # the pool binds
    return args, dict(hp=hp, batch=4)


def _categorical():
    rng = np.random.default_rng(3)
    X, y = _dense(5000, 6, 3)
    X[:, 2] = rng.integers(0, 40, size=len(y))
    y = np.where(np.isin(X[:, 2], [3, 7, 11, 30]), 1.0 - y, y)
    ds, args = _dataset(X, y, categorical_feature=[2])
    hp = SplitHyper(num_leaves=15, min_data_in_leaf=5,
                    n_bins=ds.device_n_bins(), has_categorical=True,
                    cat_subset_cols=ds.cat_subset_columns())
    assert hp.cat_subset_cols == (2,)
    return args, dict(hp=hp, batch=8)


def _forced():
    ds, args = _dataset(*_dense(5000, 8, 4))
    hp = SplitHyper(num_leaves=15, min_data_in_leaf=5,
                    n_bins=ds.device_n_bins())
    # the root on column 0, then each of its children: the second and
    # third entries read a column of a leaf's slab out of the state
    leaf = np.full(14, -1, np.int32)
    leaf[:3] = [0, 0, 1]
    feat = np.zeros(14, np.int32)
    feat[:3] = [0, 1, 2]
    thr = np.full(14, int(ds.num_bins_array()[0]) // 2, np.int32)
    return args, dict(hp=hp, batch=4,
                      forced=tuple(map(jnp.asarray, (leaf, feat, thr))))


JOBS = {"2000_columns": _epsilon_rehearsal, "67_columns": _narrow,
        "bounded_pool": _pooled, "categorical_column": _categorical,
        "forced_splits": _forced}


def _gather(hist, rows):
    """The parents' slabs as the state was read before: one gather."""
    return hist[rows]


def _grow(monkeypatch, write, read, args, kw):
    """One tree, traced anew with ``write`` as the state's update and
    ``read`` as its reader."""
    monkeypatch.setattr(batch_grower, "write_children", write)
    monkeypatch.setattr(batch_grower, "read_slabs", read)
    arrays = {k: v for k, v in kw.items() if k not in ("hp", "batch")}
    static = {k: kw[k] for k in ("hp", "batch")}
    tree, lor = jax.jit(
        lambda a, k: batch_grower.grow_tree_batched.__wrapped__(
            *a, **static, **k))(args, arrays)
    return jax.device_get(tree), np.asarray(lor)


@pytest.mark.parametrize("job", list(JOBS))
def test_trees_equal_the_channel_last_updates_bit_for_bit(monkeypatch, job):
    args, kw = JOBS[job]()
    real = batch_grower.write_children
    calls = []

    def counted(*a):
        calls.append(a[0].shape)
        return real(*a)

    tree, lor = _grow(monkeypatch, counted, batch_grower.read_slabs, args, kw)
    ref, ref_lor = _grow(monkeypatch, _channel_last_write, _gather, args, kw)
    hp = kw["hp"]
    rows = hp.hist_pool_slots or hp.num_leaves
    # every round body writes the state through write_children, in its
    # one form: a spare row, the channel planes ahead of the columns
    assert calls and set(calls) == {
        (rows + 1, 4, args[0].shape[1], hp.n_bins)}
    assert int(tree.num_leaves) == hp.num_leaves
    if job == "categorical_column":
        assert bool(np.asarray(tree.split_cat).any())
    if job == "forced_splits":
        assert list(np.asarray(tree.split_feature)[:3]) == [0, 1, 2]
    for name, a, b in zip(tree._fields, tree, ref):
        if a is None:
            assert b is None, name
            continue
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert a.tobytes() == b.tobytes(), name
    assert lor.tobytes() == ref_lor.tobytes()


@pytest.mark.parametrize("invalid", [(0,), (1, 4), ()])
def test_an_invalid_slot_leaves_its_two_places_as_they_were(invalid):
    rng = np.random.default_rng(7)
    L, C, F, B, K = 11, 4, 5, 16, 5
    before = rng.normal(size=(L + 1, C, F, B)).astype(np.float32)
    parents, new_leaves = [3, 0, 5, 1, 2], [6, 7, 8, 9, 10]
    valid = np.ones(K, bool)
    valid[list(invalid)] = False
    h_left, h_right = (rng.normal(size=(K, F, B, C)).astype(np.float32)
                       for _ in range(2))
    out = np.asarray(batch_grower.write_children(
        *map(jnp.asarray, (before, parents, new_leaves, valid, h_left,
                           h_right))))
    want = before.copy()
    for j in range(K):
        if valid[j]:
            want[parents[j]] = np.moveaxis(h_left[j], -1, 0)
            want[new_leaves[j]] = np.moveaxis(h_right[j], -1, 0)
    # (the spare last row takes the invalid slots' slabs: no one reads it)
    assert out[:L].tobytes() == want[:L].tobytes()
    for j in invalid:
        for place in (parents[j], new_leaves[j]):
            assert out[place].tobytes() == before[place].tobytes()


@pytest.mark.parametrize("rows", [[3, 0, 5, 1, 2], [11, 11, 0], [4]])
def test_the_slabs_read_one_by_one_are_the_gathered_rows(rows):
    rng = np.random.default_rng(8)
    state = jnp.asarray(rng.normal(size=(12, 4, 5, 16)).astype(np.float32))
    got = np.asarray(batch_grower.read_slabs(state, jnp.asarray(rows)))
    assert got.tobytes() == np.asarray(state)[rows].tobytes()
