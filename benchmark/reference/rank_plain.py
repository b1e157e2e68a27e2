"""Plain reference for a LambdaMART round (imports nothing of the
program, takes no table or array the program made).

What it is given: the raw features (float32 values, handed over as the
float64 feature-major matrix the data generator made), the graded labels
and the query lengths, and the trees the timed path produced as plain
arrays (``harness.program.plain_trees``).

LambdaMART as published (Burges, "From RankNet to LambdaRank to
LambdaMART", 2010) and as LightGBM's ``rank_objective.hpp:138-292`` states
it.  Per query: sort the docs by score, descending, ties in index order;
for each position ``i`` under the truncation level and each ``j`` below it
whose doc has ANOTHER label, with ``hi`` the doc of the larger label,

    rho      = 1 / (1 + exp(sigma (s_hi - s_lo)))
    |dNDCG|  = |gain_i - gain_j| |1/log2(2 + i) - 1/log2(2 + j)| / maxDCG@T
    lambda   = -sigma rho |dNDCG|         (added to hi, subtracted from lo)
    hessian  = sigma^2 rho (1 - rho) |dNDCG|            (added to both)

with ``gain = 2^label - 1``, ``maxDCG@T`` the query's ideal DCG over its
first ``T`` (the truncation level) positions, nothing for a query without
a relevant doc; then every gradient and hessian of the query times
``log2(1 + S) / S``, ``S`` the sum of ``|lambda|`` over its pairs (the
normalisation, ``lambdarank_norm``).  NDCG@k is DCG@k over the ideal
DCG@k, and 1 for a query without a relevant doc.

Over it, what ``gbdt_plain`` does for a tree (reference/gbdt_plain.py):
every tree followed over all raw rows (``x[feature] <= threshold`` goes
left), the row count of every leaf, the Newton step ``-G / (H +
lambda_l2) * learning_rate`` from ITS gradients at ITS scores, the scores
of the training and the held-out docs after every tree, the held-out
NDCG, and the split search's regret at the largest nodes of the trees
drawn from ``--seed`` (``gbdt_plain``'s tables and gains, which never
touch a gradient's formula, are used as they are).

How it is laid out, and where it departs from the description:

* Queries are padded into blocks of its own: sorted by length, cut into
  blocks of about ``SLOTS`` doc slots, each block as wide as its longest
  query rounded up to ``LANE`` docs.  Docs go into a block and gradients
  come back by numpy indexing on the host.
* The pairs are enumerated position by position: a scan over ``i`` in
  ``[0, T)`` whose step holds the pairs ``(i, j > i)`` of every query of
  the block as ``[queries, width]`` vectors.  A doc's sums are taken in
  float32 (at most ``T`` terms for a doc below the truncation level, a
  row sum for one inside it); a leaf's ``G``, ``H`` and count are added in
  float64 on the host.
* ``exp`` takes ``sigma (s_hi - s_lo)`` cut to ``[-50, 50]``, as
  ``rank_objective.hpp`` builds its sigmoid table over a bounded range.
* The scores it sorts by are the float32 sums the PROGRAM's arithmetic
  gives: tree after tree, in order, ``score += float32(leaf_value) *
  float32(learning_rate)`` with ``leaf_value`` the stated value before
  shrinkage (the stated value over the learning rate, which float32 holds
  exactly).  Docs of one query in one leaf tie exactly, early trees leave
  many such ties, and a near-tie that another rounding flipped would move
  two docs' gradients discontinuously (another position, another
  discount; a doc in or out of the truncation level): with the same
  additions in the same order the reference's order IS the program's
  wherever the program's scores are sound, and where they are not
  (``train_score_gap`` compares them with this sum) the gradients differ
  and the leaf values show it.

``dtype=bfloat16`` is the CONTROL: scores, ``rho``, lambdas, hessians,
their sums, the leaf sums and the leaf values rounded to bfloat16 after
every step (``lax.reduce_precision``, which the compiler may not take
out), the reference's OWN values added to its scores.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness import load_module

# what never touches a gradient: the thresholds' float32 floor, the
# trees' dense tables, the control's rounding, the split search's
# candidates and gains
_plain = load_module("reference", "gbdt_plain")
HIGHEST = _plain.HIGHEST
f32_floor, _rounding = _plain.f32_floor, _plain._rounding
search_tables, split_search = _plain.search_tables, _plain.split_search
node_gains = _plain.node_gains

SLOTS = 1 << 20       # doc slots of one block of queries
LANE = 128            # a block's width is a multiple of this
SEARCH_CHUNK = 4096   # rows per block of candidate comparisons


# ------------------------------------------------------------------ queries
class Queries:
    """The query structure of one data part, and the reference's own
    blocks of it."""

    def __init__(self, sizes: np.ndarray, labels: np.ndarray, trunc: int):
        self.sizes = np.asarray(sizes, np.int64)
        self.bounds = np.concatenate([[0], np.cumsum(self.sizes)])
        self.n = int(self.bounds[-1])
        self.qid = np.repeat(np.arange(len(self.sizes)), self.sizes)
        self.labels = np.asarray(labels, np.float32)
        self.gains = np.exp2(self.labels.astype(np.float64)) - 1.0
        self.blocks, self._max_dcg = [], {}
        order = np.argsort(self.sizes, kind="stable")
        a = 0
        while a < len(order):
            # as many of the next-longer queries as fit the slots at the
            # width of the longest of them
            b = a + 1
            while b < len(order) and \
                    (b + 1 - a) * _up(self.sizes[order[b]], LANE) <= SLOTS:
                b += 1
            qids = order[a:b]
            width = _up(self.sizes[qids].max(), LANE)
            col = np.arange(width)[None, :]
            idx = np.where(col < self.sizes[qids, None],
                           self.bounds[qids, None] + col, -1)
            self.blocks.append((qids, idx))
            a = b
        self._constants = None
        self.inv_max_dcg = 1.0 / np.where(
            (m := self.max_dcg(trunc)) > 0, m, np.inf)

    def by_slot(self, per_doc: np.ndarray, idx: np.ndarray, pad):
        return np.where(idx >= 0, per_doc[np.maximum(idx, 0)], pad)

    def max_dcg(self, k: int) -> np.ndarray:
        """float64 [queries]: the ideal DCG over the first ``k``
        positions."""
        if k in self._max_dcg:
            return self._max_dcg[k]
        out = self._max_dcg[k] = np.zeros(len(self.sizes))
        for qids, idx in self.blocks:
            ideal = -np.sort(-self.by_slot(self.gains, idx, 0.0), axis=1)
            kk = min(k, ideal.shape[1])
            out[qids] = (ideal[:, :kk] / np.log2(np.arange(kk) + 2.0)).sum(1)
        return out

    def ndcg(self, score: np.ndarray, ks) -> list:
        """Mean NDCG at each cut-off, float64 on the host: docs sorted by
        query, then score descending, then index; a query without a
        relevant doc reads 1."""
        qid = self.qid
        # a stable sort a key: docs of equal score stay in index order
        order = np.lexsort((-score.astype(np.float64), qid))
        pos = np.arange(self.n) - self.bounds[qid]       # qid is sorted already
        term = self.gains[order] / np.log2(pos + 2.0)
        out = []
        for k in ks:
            dcg = np.bincount(qid, weights=term * (pos < k),
                              minlength=len(self.sizes))
            ideal = self.max_dcg(int(k))
            out.append(float(np.mean(np.where(
                ideal > 0, dcg / np.where(ideal > 0, ideal, 1.0), 1.0))))
        return out


def _up(n, m: int) -> int:
    return int(-(-int(n) // m) * m)


# ---------------------------------------------------------------- gradients
@partial(jax.jit, static_argnames=("sigma", "trunc", "norm", "dtype"))
def _block_gradients(score, gain, label, inv, sigma, trunc, norm, dtype):
    """Lambda gradients of one block of queries.  ``score`` [B, W] with
    -inf in the pads, ``gain`` 0 and ``label`` -1 there, ``inv`` [B] the
    inverse ideal DCG at the truncation level (0: no relevant doc)."""
    q = _rounding(dtype)
    width = score.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    neg, g_s, l_s, order = jax.lax.sort((-q(score), gain, label, slot),
                                        dimension=1, num_keys=1, is_stable=True)
    s_s = -neg
    real = l_s >= 0
    pos = jnp.arange(width)
    disc = 1.0 / jnp.log2(pos.astype(jnp.float32) + 2.0)

    def one_position(acc, i):
        g_low, h_low, total = acc
        s_i, g_i, l_i = s_s[:, i, None], g_s[:, i, None], l_s[:, i, None]
        pair = (pos[None, :] > i) & real & (l_i >= 0) & (l_s != l_i)
        i_better = l_i > l_s
        gap = jnp.where(i_better, s_i - s_s, s_s - s_i)       # s_hi - s_lo
        rho = q(1.0 / (1.0 + jnp.exp(jnp.clip(sigma * gap, -50.0, 50.0))))
        delta = jnp.abs((g_i - g_s) * (disc[i] - disc)[None, :]) * inv[:, None]
        lam = jnp.where(pair, q(-sigma * rho * delta), 0.0)
        hes = jnp.where(pair, q(sigma * sigma * rho * (1.0 - rho) * delta), 0.0)
        to_i = jnp.where(i_better, lam, -lam)
        # the doc at position i takes the row's sum, the docs below it
        # their own term
        at_i = (pos == i)[None, :]
        g_low = q(g_low - to_i + at_i * q(to_i.sum(1))[:, None])
        h_low = q(h_low + hes + at_i * q(hes.sum(1))[:, None])
        return (g_low, h_low, q(total + q(jnp.abs(lam).sum(1)))), None

    zero = jnp.zeros_like(s_s)
    (g_p, h_p, total), _ = jax.lax.scan(
        one_position, (zero, zero, jnp.zeros(score.shape[0])),
        jnp.arange(min(trunc, width)))
    if norm:
        scale = jnp.where(total > 0, q(jnp.log2(1.0 + total)
                                       / jnp.where(total > 0, total, 1.0)), 1.0)
        g_p, h_p = q(g_p * scale[:, None]), q(h_p * scale[:, None])
    _, g_d, h_d = jax.lax.sort((order, g_p, h_p), dimension=1, num_keys=1)
    return g_d, h_d


def gradients(queries: Queries, score: np.ndarray, sigma: float, trunc: int,
              norm: bool, dtype=jnp.float32):
    """``(g, h)`` float32 [n] of every doc at ``score`` (float32 [n])."""
    g = np.zeros(queries.n, np.float32)
    h = np.zeros(queries.n, np.float32)
    if queries._constants is None:      # what a job never changes, by block
        queries._constants = [
            (jnp.asarray(queries.by_slot(queries.gains, idx, 0.0), jnp.float32),
             jnp.asarray(queries.by_slot(queries.labels, idx, -1.0), jnp.float32),
             jnp.asarray(queries.inv_max_dcg[qids], jnp.float32))
            for qids, idx in queries.blocks]
    for (_, idx), constants in zip(queries.blocks, queries._constants):
        real = idx >= 0
        g_b, h_b = _block_gradients(
            jnp.asarray(queries.by_slot(score, idx, -np.inf), jnp.float32),
            *constants,
            sigma=float(sigma), trunc=int(trunc), norm=bool(norm), dtype=dtype)
        g[idx[real]] = np.asarray(g_b)[real]
        h[idx[real]] = np.asarray(h_b)[real]
    return g, h


# ------------------------------------------------------------------ routing
@jax.jit
def _route_block(xt, sel, thr, left, right, plen):
    """Leaf of every row of one block under every tree: int32 [R, B]."""
    def one_tree(_, tab):
        sel_t, thr_t, left_t, right_t, plen_t = tab
        x_node = jnp.matmul(sel_t, xt, precision=HIGHEST)            # [NI, B]
        go_left = (x_node <= thr_t[:, None]).astype(jnp.float32)
        hits = jnp.matmul(left_t, go_left, precision=HIGHEST) + \
            jnp.matmul(right_t, 1.0 - go_left, precision=HIGHEST)    # [L, B]
        return None, jnp.argmax(hits == plen_t[:, None], axis=0).astype(jnp.int32)
    return jax.lax.scan(one_tree, None, (sel, thr, left, right, plen))[1]


def route(xt64: np.ndarray, trees, leaves: int, block: int) -> np.ndarray:
    """int16 [R, n]: the leaf each raw row reaches under each tree
    (``x[feature] <= threshold`` goes left; numeric features, nothing
    missing), a block of rows at a time."""
    features, n = xt64.shape
    tabs = tuple(jnp.asarray(a) for a in
                 _plain.stack_tables(trees, features, leaves))
    out = np.empty((len(trees), n), np.int16)
    for a in range(0, n, block):
        e = min(a + block, n)
        xb = _rows(xt64, a, e, block)
        out[:, a:e] = np.asarray(_route_block(jnp.asarray(xb), *tabs))[:, :e - a]
    return out


def _rows(xt64: np.ndarray, a: int, e: int, block: int) -> np.ndarray:
    """Rows ``a`` to ``e`` as float32 [F, block], zeros behind them:
    every block has one shape, one compiled program."""
    xb = np.zeros((xt64.shape[0], block), np.float32)
    xb[:, :e - a] = xt64[:, a:e]
    return xb


# ---------------------------------------------------------------- following
def follow(queries: Queries, leaf_of: np.ndarray, params: dict, leaves: int,
           addends=None, dtype=jnp.float32, keep: tuple = ()):
    """Tree after tree over the training docs: the gradients at the
    scores so far, each leaf's count, ``G`` and ``H`` (float64 [R, L, 3]),
    the Newton values they call for, then the tree's step.  ``addends``
    float32 [R, L] is what each leaf adds to the score (the program's
    arithmetic on the stated values: ``program_addends``); ``None`` makes
    the reference add its OWN values, held in ``dtype`` (the control).
    Returns the sums, the reference's values [R, L] (learning rate in),
    the final scores, and the gradients of the trees in ``keep``."""
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    sigma, trunc, norm = (float(params.get("sigmoid", 1.0)),
                          int(params.get("lambdarank_truncation_level", 30)),
                          bool(params.get("lambdarank_norm", True)))
    rounds = leaf_of.shape[0]
    sums = np.zeros((rounds, leaves, 3), np.float64)
    values = np.zeros((rounds, leaves), np.float64)
    score = np.zeros(queries.n, np.float32)
    kept = {}
    for t in range(rounds):
        g, h = gradients(queries, score, sigma, trunc, norm, dtype)
        if t in keep:
            kept[t] = (g, h)
        leaf = leaf_of[t]
        sums[t, :, 0] = np.bincount(leaf, minlength=leaves)
        sums[t, :, 1] = np.bincount(leaf, weights=g.astype(np.float64),
                                    minlength=leaves)
        sums[t, :, 2] = np.bincount(leaf, weights=h.astype(np.float64),
                                    minlength=leaves)
        values[t] = newton_values(sums[t], lr, l2, dtype)
        step = values[t].astype(np.float32) if addends is None else addends[t]
        score = _in(dtype, score + step[leaf])
    return sums, values, score, kept


def _in(dtype, x: np.ndarray) -> np.ndarray:
    """``x`` rounded to ``dtype`` and back to float32."""
    if dtype == jnp.float32:
        return x.astype(np.float32)
    return np.asarray(jnp.asarray(x, dtype), np.float32)


def newton_values(sums: np.ndarray, learning_rate: float, lambda_l2: float,
                  dtype=jnp.float32) -> np.ndarray:
    """Leaf values [L] the sums [L, 3] call for, the learning rate in;
    empty (padded) leaves give 0."""
    g, h = sums[:, 1], sums[:, 2]
    if dtype != jnp.float32:
        g, h = (_in(dtype, v).astype(np.float64) for v in (g, h))
    with np.errstate(divide="ignore", invalid="ignore"):
        step = np.where(sums[:, 0] > 0, -g / (h + lambda_l2), 0.0) * learning_rate
    return step if dtype == jnp.float32 else _in(dtype, step).astype(np.float64)


def program_addends(stated: np.ndarray, learning_rate: float) -> np.ndarray:
    """float32 [R, L]: what the program adds to a doc's score for each
    stated leaf value, in its own arithmetic: the value before shrinkage
    (stated / learning rate: float32 holds it exactly, the host's product
    was taken in float64) times the float32 learning rate, in float32."""
    before = (np.asarray(stated, np.float64) / learning_rate).astype(np.float32)
    return before * np.float32(learning_rate)


def scores_after_each(leaf_of: np.ndarray, addends: np.ndarray,
                      dtype=jnp.float32) -> np.ndarray:
    """float32 [R, n]: the docs' scores after each tree, by the same
    float32 additions in the same order (held in ``dtype``)."""
    out = np.empty(leaf_of.shape, np.float32)
    score = np.zeros(leaf_of.shape[1], np.float32)
    for t in range(leaf_of.shape[0]):
        score = _in(dtype, score + addends[t][leaf_of[t]])
        out[t] = score
    return out


# -------------------------------------------------------------- split search
@jax.jit
def _search_block(xt, leaf, ghc, cand, desc, sel, thr):
    """Sums over the rows of each searched node of ONE tree in one block
    of rows: everything, what the stated split sends left, and what every
    candidate threshold of every feature would send left.  ``leaf`` [B]
    int32, ``ghc`` [3, B] (count, g, h; 0 in padded rows), ``desc`` [K, L]
    the leaves under each node, ``sel`` [K, F] and ``thr`` [K] its stated
    split.  The candidates' product runs at ``highest`` precision: lambda
    gradients are heavy-tailed, and one bfloat16 pass rounds the few large
    ones that carry a node's gain (gbdt_plain_csr.py says what one pass
    read on constant gradients)."""
    b = xt.shape[1]
    k, (f, c) = desc.shape[0], cand.shape
    chunk = min(SEARCH_CHUNK, b)
    in_node = jnp.matmul(desc, jax.nn.one_hot(leaf, desc.shape[1], axis=0))  # [K, B]
    go_left = (jnp.matmul(sel, xt, precision=HIGHEST) <= thr[:, None]) \
        .astype(jnp.float32)

    def chunks(a):      # [M, B] -> [B/chunk, M, chunk]
        return a.reshape(a.shape[0], b // chunk, chunk).transpose(1, 0, 2)

    def one_chunk(acc, xs):
        node_k, left_k, ghc_k, x_k = xs
        w = node_k[:, None, :] * ghc_k[None, :, :]                     # [K, 3, chunk]
        le = (x_k[:, None, :] <= cand[:, :, None]).astype(jnp.float32)
        total, stated, cands = acc
        return (total + w.sum(-1), stated + (w * left_k[:, None, :]).sum(-1),
                cands + jnp.matmul(w.reshape(k * 3, chunk),
                                   le.reshape(f * c, chunk).T,
                                   precision=HIGHEST)), None
    zero = (jnp.zeros((k, 3)), jnp.zeros((k, 3)), jnp.zeros((k * 3, f * c)))
    return jax.lax.scan(one_chunk, zero, (chunks(in_node), chunks(go_left),
                                          chunks(ghc), chunks(xt)))[0]


def search(xt64: np.ndarray, trees, leaf_of: np.ndarray, kept: dict,
           tables: dict, block: int) -> None:
    """Fill ``tables["found"]`` (what ``gbdt_plain.split_search`` reads)
    with the searched nodes' sums, for the trees ``tables["wanted"]``
    flags, from the gradients ``kept`` of those trees."""
    features, n = xt64.shape
    which = np.flatnonzero(tables["wanted"])
    cand = jnp.asarray(tables["cand"])
    per_tree = []
    for t in which:
        node = tables["node"][t]
        sel = np.zeros((len(node), features), np.float32)
        sel[np.arange(len(node)), np.asarray(trees[t]["split_feature"])[node]] = 1.0
        thr = f32_floor(np.asarray(trees[t]["threshold"], np.float64)[node])
        per_tree.append((jnp.asarray(tables["desc"][t]), jnp.asarray(sel),
                         jnp.asarray(thr)))
    found = [np.zeros((len(which),) + s, np.float64) for s in
             ((tables["node"].shape[1], 3), (tables["node"].shape[1], 3),
              (tables["node"].shape[1] * 3, cand.shape[0] * cand.shape[1]))]
    for a in range(0, n, block):
        e = min(a + block, n)
        xb = jnp.asarray(_rows(xt64, a, e, block))
        for w, t in enumerate(which):
            g, h = kept[int(t)]
            ghc = np.zeros((3, block), np.float32)
            ghc[0, :e - a], ghc[1, :e - a], ghc[2, :e - a] = 1.0, g[a:e], h[a:e]
            leaf = np.zeros(block, np.int32)
            leaf[:e - a] = leaf_of[t, a:e]
            out = _search_block(xb, jnp.asarray(leaf), jnp.asarray(ghc), cand,
                                *per_tree[w])
            for acc, part in zip(found, out):
                acc[w] += np.asarray(part, np.float64)
    tables["found"] = found
