"""Plain reference for a binary-logloss GBDT round (imports nothing of the
program, takes no table or array the program made).

What it is given: the raw float32 features and labels the harness made
from the seed, and the trees the timed path produced (its answers) as
plain arrays: for every internal node the feature, the real-valued
threshold and the two children; for every leaf the value and the row
count the program states.

What it computes, from the raw rows alone, tree after tree:

* which leaf each row reaches (``x[feature] <= threshold`` goes left;
  numeric features, nothing missing),
* the gradients ``g, h`` of the binary log-loss at the score the rows
  have before that tree (the average-label start, then every earlier
  tree's leaf value),
* per leaf: the row count, ``G = sum g``, ``H = sum h``, and the Newton
  step ``-G / (H + lambda_l2) * learning_rate``,
* the score of every row after every tree, for the training rows and for
  the held-out rows, and the exact (tie-aware) AUC of the held-out rows.

* for the largest nodes of a tree (``split_search``): the gain of the
  split the tree states, and the best gain any feature gives at the
  reference's OWN candidate thresholds (quantiles of the raw rows), both
  from the same sums of the reference's gradients over the node's raw
  rows.  A program whose histograms or split search are at fault states
  splits that the raw rows do not bear out.

Row sums are taken in float32 over sub-blocks of ``SUB`` rows and added
in float64 on the host.  Matrix products run at ``highest`` precision: a
selection by a 0/1 matrix is then exact in float32.

``dtype=bfloat16`` is the CONTROL: the same arithmetic with scores,
gradients, sums and leaf values rounded to bfloat16, the nearest
precision below the float32 the program computes them in, after every
step.  The rounding is ``lax.reduce_precision``, which the compiler may
not take out (a plain ``astype`` round trip it does take out on the TPU:
``xla_allow_excess_precision``).  Put in the program's place it has to
come out as not correct (tools/control.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SUB = 65536          # rows per float32 partial sum
SEARCH_CHUNK = 16384  # rows per block of candidate comparisons
HIGHEST = jax.lax.Precision.HIGHEST


def f32_floor(thr64: np.ndarray) -> np.ndarray:
    """Largest float32 <= each float64 threshold: for float32 ``x``,
    ``x <= thr64`` holds exactly when ``x <= f32_floor(thr64)``."""
    t = thr64.astype(np.float32)
    over = t.astype(np.float64) > thr64
    return np.where(over, np.nextafter(t, np.float32(-np.inf)), t)


def tree_tables(tree: dict, features: int, leaves: int):
    """Dense tables of one tree, padded to ``leaves`` leaves: the node's
    feature as a one-hot row, its float32 threshold, and for every leaf
    which nodes it passes on the left and on the right."""
    ni = len(tree["split_feature"])
    sel = np.zeros((leaves - 1, features), np.float32)
    thr = np.full((leaves - 1,), np.float32(np.inf))
    left = np.zeros((leaves, leaves - 1), np.float32)
    right = np.zeros((leaves, leaves - 1), np.float32)
    sel[np.arange(ni), tree["split_feature"]] = 1.0
    thr[:ni] = f32_floor(np.asarray(tree["threshold"], np.float64))
    # walk down from the root; a negative child ~c is leaf c
    stack = [(0, [], [])] if ni else []
    if not ni:
        pass    # a stump: leaf 0 has an empty path and matches every row
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
    return sel, thr, left, right, plen.astype(np.float32)


def stack_tables(trees, features: int, leaves: int):
    cols = list(zip(*(tree_tables(t, features, leaves) for t in trees)))
    return tuple(np.stack(c) for c in cols)


def _rounding(dtype):
    """Identity for float32; for a narrower type, a rounding to it that
    stays in the compiled program."""
    if dtype == jnp.float32:
        return lambda x: x
    info = jnp.finfo(dtype)
    return lambda x: jax.lax.reduce_precision(x, info.nexp, info.nmant)


@partial(jax.jit, static_argnames=("dtype",))
def _block(xt, sign, score0, sel, thr, left, right, plen, values, dtype,
           search=None):
    """One block of rows through every tree.  ``xt`` [F, B] float32,
    ``values`` [R, L] the leaf values added to the score after each tree.
    Returns per tree the per-sub-block leaf sums [R, B/SUB, L, 3]
    (count, G, H) and the scores after each tree [R, B]; with ``search``
    (see ``split_search``) also the searched nodes' sums."""
    b = xt.shape[1]
    sub = min(SUB, b)
    q = _rounding(dtype)
    tabs = (sel, thr, left, right, plen, values)
    if search is not None:
        cand, wanted, desc, node = search
        tabs += (wanted, desc, node)
        chunk = min(SEARCH_CHUNK, b)
        k, (f, c) = desc.shape[1], cand.shape

        def chunks(a):      # [M, B] -> [B/chunk, M, chunk]
            return a.reshape(a.shape[0], b // chunk, chunk).transpose(1, 0, 2)
        xt_c = chunks(xt)

        def searched(in_leaf, go_left, ghc, desc_t, node_t):
            """Sums over the rows of each searched node: everything,
            what the stated split sends left, and what every candidate
            threshold of every feature would send left.  One pass of the
            matrix unit (its default precision: 0/1 factors are exact,
            gradients keep 8 bits, sums are float32), a chunk of rows at
            a time."""
            in_node = jnp.matmul(desc_t, in_leaf)                     # [K, B]

            def one_chunk(acc, xs):
                node_k, left_k, ghc_k, x_k = xs
                w = node_k[:, None, :] * ghc_k[None, :, :]            # [K, 3, chunk]
                le = (x_k[:, None, :] <= cand[:, :, None]).astype(jnp.float32)
                total, stated, cands = acc
                return (total + w.sum(-1),
                        stated + (w * left_k[:, None, :]).sum(-1),
                        cands + jnp.matmul(w.reshape(k * 3, chunk),
                                           le.reshape(f * c, chunk).T)), None
            zero = (jnp.zeros((k, 3)), jnp.zeros((k, 3)), jnp.zeros((k * 3, f * c)))
            out, _ = jax.lax.scan(one_chunk, zero, (
                chunks(in_node), chunks(go_left[node_t]), chunks(ghc), xt_c))
            return out

        def not_searched(in_leaf, go_left, ghc, desc_t, node_t):
            return (jnp.zeros((k, 3)), jnp.zeros((k, 3)),
                    jnp.zeros((k * 3, f * c)))

    def one_tree(score, tab):
        sel_t, thr_t, left_t, right_t, plen_t, val_t = tab[:6]
        x_node = jnp.matmul(sel_t, xt, precision=HIGHEST)          # [NI, B]
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
        return score, (sums, score, found)

    _, out = jax.lax.scan(one_tree, score0, tabs)
    return out


def follow(xt: np.ndarray, y: np.ndarray, trees, values: np.ndarray,
           init_score: float, block: int, dtype=jnp.float32,
           keep_scores: bool = False, search: dict = None):
    """Run the rows ``xt`` [F, n] through ``trees`` in blocks; ``values``
    [R, L] is what each tree's leaves add to the score (the first tree's
    without the starting score, which every row begins at).  Returns
    ``sums`` float64 [R, L, 3] (count, G, H per leaf, taken BEFORE each
    tree's step), the final score of every row, and with ``keep_scores``
    the score of every row after every tree.  With ``search`` (the tables
    of ``search_tables``) its key ``found`` is filled with the searched
    nodes' sums (``split_search`` reads them)."""
    features, n = xt.shape
    leaves = values.shape[1]
    tabs = tuple(jnp.asarray(a) for a in stack_tables(trees, features, leaves))
    vals = jnp.asarray(values, jnp.float32)
    on_device, found = None, None
    if search is not None:
        on_device = tuple(jnp.asarray(search[k])
                          for k in ("cand", "wanted", "desc", "node"))
        which = np.flatnonzero(search["wanted"])
        found = [0.0, 0.0, 0.0]
    total = np.zeros((len(trees), leaves, 3), np.float64)
    final = np.empty(n, np.float32)
    per_tree = np.empty((len(trees), n), np.float32) if keep_scores else None
    sign_all = np.where(y > 0, np.float32(1), np.float32(-1))
    for a in range(0, n, block):
        e = min(a + block, n)
        # every block has the same shape: one compiled program
        pad = block - (e - a)
        xb = np.ascontiguousarray(xt[:, a:e])
        sb = sign_all[a:e]
        if pad:
            xb = np.pad(xb, ((0, 0), (0, pad)))
            sb = np.pad(sb, (0, pad))
        score0 = jnp.full((block,), np.float32(init_score))
        out = _block(jnp.asarray(xb), jnp.asarray(sb), score0, *tabs,
                     vals, dtype=dtype, search=on_device)
        sums, scores = out[:2]
        total += np.asarray(sums, np.float64).sum(axis=1)
        if search is not None:
            found = [acc + np.asarray(part[which], np.float64)
                     for acc, part in zip(found, out[2])]
        final[a:e] = np.asarray(scores[-1])[:e - a]
        if keep_scores:
            per_tree[:, a:e] = np.asarray(scores)[:, :e - a]
    if search is not None:
        search["found"] = found
    return total, final, per_tree


def search_tables(xt: np.ndarray, trees, leaves: int, nodes: int,
                  candidates: int, wanted) -> dict:
    """What ``follow`` needs to search the splits of the trees ``wanted``
    (indices): the reference's own candidate thresholds ``cand`` [F, C]
    (quantiles of an evenly spaced 200,000 of the raw rows: nothing of
    the program's bins), and per tree its ``nodes`` largest internal
    nodes by the stated row counts: ``node`` [R, K] their indices and
    ``desc`` [R, K, L] which leaves lie under each (all 0 where a tree
    has fewer)."""
    features, n = xt.shape
    rows = xt[:, ::max(1, n // 200000)]
    levels = np.arange(1, candidates + 1) / (candidates + 1.0)
    cand = np.quantile(rows, levels, axis=1).T.astype(np.float32)    # [F, C]
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
    return {"cand": cand, "wanted": flags, "desc": desc, "node": node}


def split_search(search: dict, lambda_l2: float, min_rows: int,
                 min_hessian: float):
    """From the sums ``follow`` left in ``search["found"]``: per searched
    node (``[W, K]``, W the wanted trees in order) its rows, the gain of
    the stated split and the best gain over every feature's candidate
    thresholds, each ``GL^2/HL + GR^2/HR - GP^2/HP`` with both sides
    holding at least ``min_rows`` rows and ``min_hessian`` of H."""
    total, stated, cands = search["found"]
    f, c = search["cand"].shape
    w, k = total.shape[:2]
    cands = cands.reshape(w, k, 3, f * c)

    def gain(left, tot):
        right = tot - left
        ok = ((left[:, :, 0] >= min_rows) & (right[:, :, 0] >= min_rows)
              & (left[:, :, 2] >= min_hessian) & (right[:, :, 2] >= min_hessian))
        with np.errstate(divide="ignore", invalid="ignore"):
            g = (left[:, :, 1] ** 2 / (left[:, :, 2] + lambda_l2)
                 + right[:, :, 1] ** 2 / (right[:, :, 2] + lambda_l2)
                 - tot[:, :, 1] ** 2 / (tot[:, :, 2] + lambda_l2))
        return np.where(ok, g, -np.inf)
    every = gain(cands, total[..., None])                        # [W, K, F*C]
    best_at = every.argmax(-1)
    best = np.take_along_axis(every, best_at[..., None], -1)[..., 0]
    return {"rows": total[:, :, 0], "stated": gain(stated[..., None],
                                                   total[..., None])[..., 0],
            "best": best, "best_feature": best_at // c}


def exact_auc(y: np.ndarray, score: np.ndarray) -> float:
    """Mann-Whitney AUC in float64 with ties at half weight."""
    score = np.asarray(score, np.float64)
    order = np.argsort(score, kind="stable")
    s = score[order]
    pos = (np.asarray(y)[order] > 0)
    # midrank of each tie group
    edge = np.flatnonzero(np.r_[True, s[1:] != s[:-1], True])
    lo, hi = edge[:-1], edge[1:]
    mid = (lo + hi + 1) / 2.0                      # 1-based average rank
    rank = np.repeat(mid, hi - lo)
    n_pos = float(pos.sum())
    n_neg = float(len(s) - n_pos)
    return float((rank[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def newton_values(sums: np.ndarray, learning_rate: float, lambda_l2: float,
                  init_score: float, dtype=jnp.float32) -> np.ndarray:
    """Leaf values [R, L] the sums call for: the shrunk Newton step, with
    the starting score folded into the first tree as the program's model
    file has it.  Empty (padded) leaves give 0."""
    g, h = sums[..., 1], sums[..., 2]
    if dtype != jnp.float32:
        g = np.asarray(jnp.asarray(g, dtype), np.float64)
        h = np.asarray(jnp.asarray(h, dtype), np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        step = np.where(sums[..., 0] > 0, -g / (h + lambda_l2), 0.0) * learning_rate
    if dtype != jnp.float32:
        step = np.asarray(jnp.asarray(step, dtype), np.float64)
    out = step.copy()
    out[0] += init_score
    return out, step


def node_gains(tree: dict, sums: np.ndarray, lambda_l2: float) -> np.ndarray:
    """Gain of every split of one tree from the leaf sums [L, 3], added up
    the tree: ``GL^2/HL + GR^2/HR - GP^2/HP``; and each node's row count."""
    ni = len(tree["split_feature"])
    node = np.zeros((ni, 3), np.float64)
    kids = lambda k: (int(tree["left_child"][k]), int(tree["right_child"][k]))

    def total(child: int) -> np.ndarray:
        return sums[~child] if child < 0 else node[child]

    # post-order without recursion: a node is summed once both children are
    order, stack = [], ([0] if ni else [])
    while stack:
        k = stack.pop()
        order.append(k)
        stack.extend(c for c in kids(k) if c >= 0)
    for k in reversed(order):
        node[k] = total(kids(k)[0]) + total(kids(k)[1])
    gains = np.zeros(ni)
    for k in range(ni):
        l, r = total(int(tree["left_child"][k])), total(int(tree["right_child"][k]))
        gains[k] = (l[1] ** 2 / (l[2] + lambda_l2) + r[1] ** 2 / (r[2] + lambda_l2)
                    - node[k, 1] ** 2 / (node[k, 2] + lambda_l2))
    return gains, node[:, 0]
