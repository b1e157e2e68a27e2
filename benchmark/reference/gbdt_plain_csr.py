"""Plain reference for a binary-logloss GBDT round on SPARSE rows: the
semantics of ``gbdt_plain`` (reference/gbdt_plain.py: raw values,
``x[feature] <= threshold`` goes left, float32 row sums added in float64,
Newton leaf values, exact AUC, the split search of the largest nodes
against the reference's own candidates) on scipy CSR row blocks.  It
imports nothing of the program and takes no table the program made,
feature bundles least of all: a stated split names a raw column and a
real threshold, and an absent entry is the value 0.0.

The rows arrive FEATURE-MAJOR like ``gbdt_plain``'s: the ``[F, n]``
transpose of the row CSR (``csr.T``, a view), so one comparison serves
both references.  What differs from ``gbdt_plain``:

* a block of rows is never made dense over all F columns.  The trees of
  a job name a few hundred to a few thousand of the 4,228 columns; each
  block is made dense over THOSE (``[U, B]`` float32, scattered from the
  block's stored entries on the host) and a node reads its column as one
  row of it.
* the split search's candidates are the reference's own quantiles of the
  raw column, implicit zeros counted: a column that is 0.0 in most rows
  (a one-hot level) has ONE candidate, ``x <= 0``; the others (``dense``
  columns) have up to ``candidates``.  The dense columns' sums come from
  ``gbdt_plain``'s chunked matmul over their dense rows; a sparse
  column's from its stored entries: the rows with ``x > 0`` are the right
  side, summed per (leaf, column) on the host and added up the tree.
* that matmul runs at ``highest`` precision.  ``gbdt_plain`` leaves it
  at the matrix unit's default, one bfloat16 pass ("gradients keep 8
  bits"), which is sound where the rounding differs from row to row and
  sums away.  A FIRST tree's gradients are two constants, ``p`` and
  ``p - 1``: at a tenth positive 0.1 and -0.9 round to 0.10009766 and
  -0.8984375 in every row alike, the candidates' left sums drift with
  the rows they hold while the stated split's, added in float32, do not,
  and the best candidate's gain reads a third too high (tree 0 at the
  published size on the chip: regret 0.2845 in one pass, 0.0066 at
  ``highest``, which is what float32 on a CPU gives; ``gbdt_plain``'s
  cells are half positive, and 0.5 is exact in bfloat16).

``dtype=bfloat16`` is the CONTROL, as in ``gbdt_plain``, whose helpers
that never touch the rows (the thresholds' float32 floor, the rounding,
the exact AUC, the Newton values, the gains added up a tree) are used as
they are.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness import load_module

# what does not touch the rows is gbdt_plain's own: the thresholds'
# float32 floor, the control's rounding, the exact AUC, the Newton values
# and the gains added up a tree
_dense = load_module("reference", "gbdt_plain")
SUB, SEARCH_CHUNK, HIGHEST = _dense.SUB, _dense.SEARCH_CHUNK, _dense.HIGHEST
f32_floor, _rounding = _dense.f32_floor, _dense._rounding
exact_auc, newton_values, node_gains = \
    _dense.exact_auc, _dense.newton_values, _dense.node_gains
_THREADS = 6


def tree_tables(tree: dict, place: np.ndarray, leaves: int):
    """Dense tables of one tree, padded to ``leaves`` leaves: the row of
    the block's dense columns each node reads (``place`` maps a raw
    column to it), its float32 threshold, and for every leaf which nodes
    it passes on the left and on the right."""
    ni = len(tree["split_feature"])
    row = np.zeros((leaves - 1,), np.int32)
    thr = np.full((leaves - 1,), np.float32(np.inf))
    left = np.zeros((leaves, leaves - 1), np.float32)
    right = np.zeros((leaves, leaves - 1), np.float32)
    row[:ni] = place[np.asarray(tree["split_feature"])]
    thr[:ni] = f32_floor(np.asarray(tree["threshold"], np.float64))
    # walk down from the root; a negative child ~c is leaf c
    stack = [(0, [], [])] if ni else []
    while stack:
        node, ls, rs = stack.pop()
        for child, l2, r2 in ((int(tree["left_child"][node]), ls + [node], rs),
                              (int(tree["right_child"][node]), ls, rs + [node])):
            if child < 0:
                left[~child, l2] = 1.0
                right[~child, r2] = 1.0
            else:
                stack.append((child, l2, r2))
    plen = left.sum(1) + right.sum(1)
    # padded leaves can never match: ask them for one more node than exists
    plen[tree["num_leaves"]:] = leaves
    return row, thr, left, right, plen.astype(np.float32)


@partial(jax.jit, static_argnames=("dtype",))
def _block(xu, sign, score0, row, thr, left, right, plen, values, dtype,
           search=None):
    """One block of rows through every tree.  ``xu`` [U, B] float32 the
    block dense over the columns the trees name, ``row`` [R, NI] which of
    them each node reads, ``values`` [R, L] the leaf values added to the
    score after each tree.  Returns per tree the per-sub-block leaf sums
    [R, B/SUB, L, 3] (count, G, H) and the scores after each tree [R, B];
    with ``search`` also the searched nodes' sums over the dense columns,
    and for the searched trees each row's leaf and (1, g, h)."""
    b = xu.shape[1]
    sub = min(SUB, b)
    q = _rounding(dtype)
    tabs = (row, thr, left, right, plen, values)
    if search is not None:
        cand, dense_rows, wanted, which, desc, node = search
        tabs += (wanted, desc, node)
        chunk = min(SEARCH_CHUNK, b)
        k, (f, c) = desc.shape[1], cand.shape

        def chunks(a):      # [M, B] -> [B/chunk, M, chunk]
            return a.reshape(a.shape[0], b // chunk, chunk).transpose(1, 0, 2)
        xd_c = chunks(xu[dense_rows])

        def searched(in_leaf, go_left, ghc, desc_t, node_t):
            """Sums over the rows of each searched node: everything, what
            the stated split sends left, and what every candidate
            threshold of every DENSE column would send left."""
            in_node = jnp.matmul(desc_t, in_leaf)                     # [K, B]

            def one_chunk(acc, xs):
                node_k, left_k, ghc_k, x_k = xs
                w = node_k[:, None, :] * ghc_k[None, :, :]            # [K, 3, chunk]
                le = (x_k[:, None, :] <= cand[:, :, None]).astype(jnp.float32)
                total, stated, cands = acc
                return (total + w.sum(-1),
                        stated + (w * left_k[:, None, :]).sum(-1),
                        cands + jnp.matmul(w.reshape(k * 3, chunk),
                                           le.reshape(f * c, chunk).T,
                                           precision=HIGHEST)), None
            zero = (jnp.zeros((k, 3)), jnp.zeros((k, 3)), jnp.zeros((k * 3, f * c)))
            out, _ = jax.lax.scan(one_chunk, zero, (
                chunks(in_node), chunks(go_left[node_t]), chunks(ghc), xd_c))
            return out

        def not_searched(in_leaf, go_left, ghc, desc_t, node_t):
            return (jnp.zeros((k, 3)), jnp.zeros((k, 3)),
                    jnp.zeros((k * 3, f * c)))

    def one_tree(score, tab):
        row_t, thr_t, left_t, right_t, plen_t, val_t = tab[:6]
        x_node = xu[row_t]                                          # [NI, B]
        go_left = (x_node <= thr_t[:, None]).astype(jnp.float32)
        hits = jnp.matmul(left_t, go_left, precision=HIGHEST) + \
            jnp.matmul(right_t, 1.0 - go_left, precision=HIGHEST)  # [L, B]
        in_leaf = (hits == plen_t[:, None]).astype(jnp.float32)    # one 1 per row
        s = q(score)
        resp = q(-sign / q(1 + q(jnp.exp(q(sign * s)))))
        g = resp
        h = q(jnp.abs(resp) * q(1 - jnp.abs(resp)))
        # padded rows carry sign 0: they count for nothing
        real = jnp.abs(sign)
        ghc = jnp.stack([real, g * real, h * real])                     # [3, B]
        sums = q(jnp.einsum("lcs,kcs->clk", in_leaf.reshape(-1, b // sub, sub),
                            ghc.reshape(3, b // sub, sub), precision=HIGHEST))
        step = jnp.matmul(q(val_t), in_leaf, precision=HIGHEST)        # [B]
        score = q(s + step)
        if search is None:
            return score, (sums, score)
        wanted_t, desc_t, node_t = tab[6:]
        found = jax.lax.cond(wanted_t, searched, not_searched,
                             in_leaf, go_left, ghc, desc_t, node_t)
        return score, (sums, score, found,
                       jnp.argmax(in_leaf, axis=0).astype(jnp.int32), ghc)

    _, out = jax.lax.scan(one_tree, score0, tabs)
    if search is None:
        return out
    return out[:3] + (out[3][which], out[4][which])


def _used_columns(trees, search) -> np.ndarray:
    used = np.unique(np.concatenate(
        [np.asarray(t["split_feature"], np.int64) for t in trees]
        + ([search["dense"]] if search is not None else [])))
    return used if len(used) else np.zeros(1, np.int64)


def follow(xt, y: np.ndarray, trees, values: np.ndarray,
           init_score: float, block: int, dtype=jnp.float32,
           keep_scores: bool = False, search: dict = None):
    """Run the rows ``xt`` ([F, n], the transpose of the row CSR) through
    ``trees`` in blocks; ``values`` [R, L] is what each tree's leaves add
    to the score (the first tree's without the starting score, which
    every row begins at).  Returns ``sums`` float64 [R, L, 3] (count, G,
    H per leaf, taken BEFORE each tree's step), the final score of every
    row, and with ``keep_scores`` the score of every row after every
    tree.  With ``search`` (the tables of ``search_tables``) its key
    ``found`` is filled with the searched nodes' sums (``split_search``
    reads them)."""
    rows_csr = xt.T.tocsr()        # the row CSR itself: no copy
    n, features = rows_csr.shape
    leaves = values.shape[1]
    used = _used_columns(trees, search)
    used = np.pad(used, (0, -len(used) % 8), mode="edge")
    place = np.full(features, -1, np.int64)
    place[used[::-1]] = np.arange(len(used))[::-1]      # first of equal ones
    cols = list(zip(*(tree_tables(t, place, leaves) for t in trees)))
    tabs = tuple(jnp.asarray(np.stack(c)) for c in cols)
    vals = jnp.asarray(values, jnp.float32)
    on_device, found, sparse = None, None, None
    if search is not None:
        which = np.flatnonzero(search["wanted"])
        on_device = (jnp.asarray(search["cand"]),
                     jnp.asarray(place[search["dense"]]),
                     jnp.asarray(search["wanted"]), jnp.asarray(which),
                     jnp.asarray(search["desc"]), jnp.asarray(search["node"]))
        found = [0.0, 0.0, 0.0]
        # the sparse columns' right-side sums per (searched tree, leaf,
        # column): rows with x > 0, from the stored entries
        spot = np.full(features, -1, np.int64)
        spot[search["sparse"]] = np.arange(len(search["sparse"]))
        sparse = np.zeros((len(which), 3, leaves * len(search["sparse"])))
    total = np.zeros((len(trees), leaves, 3), np.float64)
    final = np.empty(n, np.float32)
    per_tree = np.empty((len(trees), n), np.float32) if keep_scores else None
    sign_all = np.where(y > 0, np.float32(1), np.float32(-1))
    indptr = rows_csr.indptr
    pool = ThreadPoolExecutor(_THREADS)
    for a in range(0, n, block):
        e = min(a + block, n)
        # every block has the same shape: one compiled program
        lo, hi = indptr[a], indptr[e]
        col = rows_csr.indices[lo:hi]
        val = rows_csr.data[lo:hi]
        at = np.repeat(np.arange(e - a), np.diff(indptr[a:e + 1]))
        xu = np.zeros((len(used), block), np.float32)
        keep = place[col] >= 0
        xu[place[col[keep]], at[keep]] = val[keep]
        sb = np.zeros(block, np.float32)
        sb[:e - a] = sign_all[a:e]
        score0 = jnp.full((block,), np.float32(init_score))
        out = _block(jnp.asarray(xu), jnp.asarray(sb), score0, *tabs,
                     vals, dtype=dtype, search=on_device)
        sums, scores = out[:2]
        total += np.asarray(sums, np.float64).sum(axis=1)
        if search is not None:
            found = [acc + np.asarray(part[which], np.float64)
                     for acc, part in zip(found, out[2])]
            leaf, ghc = np.asarray(out[3]), np.asarray(out[4], np.float64)
            right = (spot[col] >= 0) & (val > 0)
            r_at, r_col = at[right], spot[col[right]]

            def one(job, leaf=leaf, ghc=ghc, r_at=r_at, r_col=r_col):
                w, c = job
                flat = leaf[w, r_at] * len(search["sparse"]) + r_col
                sparse[w, c] += np.bincount(flat, weights=ghc[w, c, r_at],
                                            minlength=sparse.shape[2])
            list(pool.map(one, [(w, c) for w in range(len(which))
                                for c in range(3)]))
        final[a:e] = np.asarray(scores[-1])[:e - a]
        if keep_scores:
            per_tree[:, a:e] = np.asarray(scores)[:, :e - a]
    pool.shutdown()
    if search is not None:
        search["found"] = found
        search["found_sparse"] = sparse.reshape(
            len(which), 3, leaves, len(search["sparse"]))
    return total, final, per_tree


def search_tables(xt, trees, leaves: int, nodes: int,
                  candidates: int, wanted) -> dict:
    """What ``follow`` needs to search the splits of the trees ``wanted``
    (indices): the reference's own candidate thresholds (quantiles of an
    evenly spaced 200,000 of the raw rows, absent entries counted as 0.0:
    nothing of the program's bins) — ``dense`` the columns with more than
    the one candidate 0.0 and ``cand`` [Fd, C] theirs, ``sparse`` the
    columns whose every quantile is 0.0 — and per tree its ``nodes``
    largest internal nodes by the stated row counts: ``node`` [R, K] their
    indices and ``desc`` [R, K, L] which leaves lie under each (all 0
    where a tree has fewer)."""
    features, n = xt.shape
    sample = xt.T.tocsr()[::max(1, n // 200000)].tocsc()
    levels = np.arange(1, candidates + 1) / (candidates + 1.0)
    dense, cand = [], []
    for j in range(features):
        stored = sample.data[sample.indptr[j]:sample.indptr[j + 1]]
        # quantiles of the column with its implicit zeros, without
        # building it: the zeros sit between the negatives and the positives
        if not len(stored):
            continue
        full = np.sort(np.concatenate(
            [stored, np.zeros(sample.shape[0] - len(stored))])) \
            if len(stored) * 8 > sample.shape[0] else None
        if full is None:
            neg, pos = np.sort(stored[stored < 0]), np.sort(stored[stored > 0])
            rank = levels * (sample.shape[0] - 1)
            if len(neg) <= rank[0] and len(pos) < sample.shape[0] - 1 - rank[-1]:
                continue        # every quantile is 0.0
            full = np.concatenate(
                [neg, np.zeros(sample.shape[0] - len(neg) - len(pos)), pos])
        dense.append(j)
        cand.append(np.quantile(full, levels).astype(np.float32))
    dense = np.asarray(dense, np.int64)
    sparse = np.setdiff1d(np.arange(features), dense)
    if not len(dense):          # the device tables need a row
        dense = np.zeros(1, np.int64)
        cand = [np.full(candidates, np.float32(np.inf))]
    desc = np.zeros((len(trees), nodes, leaves), np.float32)
    node = np.zeros((len(trees), nodes), np.int32)
    flags = np.zeros(len(trees), bool)
    flags[list(wanted)] = True
    for t in np.flatnonzero(flags):
        tree = trees[t]
        ni = len(tree["split_feature"])
        under = np.zeros((ni, leaves), np.float32)
        # children come after their parent: walk backwards
        for k in range(ni - 1, -1, -1):
            for child in (int(tree["left_child"][k]), int(tree["right_child"][k])):
                if child < 0:
                    under[k, ~child] = 1.0
                else:
                    under[k] += under[child]
        count = under[:, :tree["num_leaves"]] @ np.asarray(
            tree["leaf_count"], np.float64)
        top = np.argsort(-count, kind="stable")[:nodes]
        node[t, :len(top)] = top
        desc[t, :len(top)] = under[top]
    return {"cand": np.stack(cand), "dense": dense, "sparse": sparse,
            "wanted": flags, "desc": desc, "node": node}


def split_search(search: dict, lambda_l2: float, min_rows: int,
                 min_hessian: float):
    """From the sums ``follow`` left in ``search``: per searched node
    (``[W, K]``, W the wanted trees in order) its rows, the gain of the
    stated split and the best gain over every column's candidate
    thresholds, each ``GL^2/HL + GR^2/HR - GP^2/HP`` with both sides
    holding at least ``min_rows`` rows and ``min_hessian`` of H."""
    total, stated, cands = search["found"]
    f, c = search["cand"].shape
    w, k = total.shape[:2]
    cands = cands.reshape(w, k, 3, f * c)
    # a sparse column's candidate x <= 0: the node's rows but those with
    # x > 0, the latter added up from the leaves under the node
    desc = search["desc"][np.flatnonzero(search["wanted"])].astype(np.float64)
    right = np.einsum("wkl,wcls->wkcs", desc, search["found_sparse"])
    cands = np.concatenate([cands, total[..., None] - right], axis=-1)

    def gain(left, tot):
        right = tot - left
        ok = ((left[:, :, 0] >= min_rows) & (right[:, :, 0] >= min_rows)
              & (left[:, :, 2] >= min_hessian) & (right[:, :, 2] >= min_hessian))
        with np.errstate(divide="ignore", invalid="ignore"):
            g = (left[:, :, 1] ** 2 / (left[:, :, 2] + lambda_l2)
                 + right[:, :, 1] ** 2 / (right[:, :, 2] + lambda_l2)
                 - tot[:, :, 1] ** 2 / (tot[:, :, 2] + lambda_l2))
        return np.where(ok, g, -np.inf)
    every = gain(cands, total[..., None])                   # [W, K, Fd*C + Fs]
    best_at = every.argmax(-1)
    best = np.take_along_axis(every, best_at[..., None], -1)[..., 0]
    column = np.concatenate([np.repeat(search["dense"], c), search["sparse"]])
    return {"rows": total[:, :, 0], "stated": gain(stated[..., None],
                                                   total[..., None])[..., 0],
            "best": best, "best_feature": column[best_at]}
