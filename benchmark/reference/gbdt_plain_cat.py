"""Plain reference for a binary-logloss GBDT round on rows some of whose
columns are CATEGORICAL: the semantics of ``gbdt_plain``
(reference/gbdt_plain.py: raw values, float32 row sums added in float64,
Newton leaf values, exact AUC, the split search of the largest nodes
against the reference's own candidates) with a categorical node decided
by SET MEMBERSHIP OF THE RAW CODE.  It imports nothing of the program and
takes no table the program made, bins least of all: a stated categorical
split names a raw column and the set of raw integer codes that go left,
and a code in no set (a level the program kept no bin for, a code never
seen) goes right.

The rows arrive feature-major like ``gbdt_plain``'s (``[F, n]`` float32;
a code below 2**24 is exact).  What differs from ``gbdt_plain``:

* a tree's tables carry, for every node, whether it is categorical and
  its left set as a 0/1 row over ALL categorical columns' codes laid end
  to end (column c's code v at ``first[c] + v``, widths from the raw rows'
  own largest code).  A block's codes are made one-hot over that axis
  once (``[W, B]``, exact 0/1 in bfloat16) and every tree's sets meet it
  in one matrix product, a single 1 a row and node: membership, exactly.
  A numeric node is ``x <= threshold`` as before.
* the split search's candidates for a categorical column are sets, by
  the rules of LightGBM's documentation (docs/Features.rst, Optimal Split
  for Categorical Features; docs/Parameters.rst) under the configuration's
  published parameters, from the reference's OWN float64 sums per level
  over the node's raw rows (the same one-hot, at ``highest`` precision):
  the ``cat_levels_kept`` most frequent levels of the TRAINING rows take
  part (the others go right at any categorical node of this column, as
  they do in the stated model); a column of up to ``max_cat_to_onehot``
  such levels offers each level alone under ``lambda_l2``; any other
  offers the levels of at least ``cat_smooth`` rows in the node, sorted
  by ``G / (H + cat_smooth)``, as prefixes of the ascending and of the
  descending order, at most ``max_cat_threshold`` levels and half the
  levels in play, under ``lambda_l2 + cat_l2``, a prefix being looked at
  once ``min_data_per_group`` rows have joined since the last one looked
  at and while the right side keeps as many.  A stated set that no such
  scan could state (more than ``max_cat_threshold`` levels, or a level
  of fewer than ``cat_smooth`` rows in the node) has no gain: the regret
  is infinite.  WHICH levels have a bin is the program's to say: it
  counts them on a sample of the rows, this reference on all of them, and
  near the 254th most frequent the two lists differ.
* numeric columns keep ``gbdt_plain``'s 64 quantile thresholds; a
  categorical column has none.
* matrix products run at ``highest`` precision wherever a gradient is a
  factor (``gbdt_plain_csr``'s reason: a tenth positive).

Departures from upstream's description, each on purpose: upstream's
``FindBin`` also drops a column's rarest levels once the kept ones cover
99% of the sample, this reference keeps ``cat_levels_kept``; upstream
scans with its histogram's float32/int sums, this reference in float64;
ties in the sort are broken by level code here, by bin there.

``dtype=bfloat16`` is the CONTROL, as in ``gbdt_plain``, whose helpers
that never touch the rows are used as they are.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness import load_module

_dense = load_module("reference", "gbdt_plain")
SUB, SEARCH_CHUNK, HIGHEST = _dense.SUB, _dense.SEARCH_CHUNK, _dense.HIGHEST
f32_floor, _rounding = _dense.f32_floor, _dense._rounding
exact_auc, newton_values, node_gains = \
    _dense.exact_auc, _dense.newton_values, _dense.node_gains
_paths = _dense.tree_tables


class Codes:
    """Where each categorical column's codes lie on the one axis all the
    sets share: ``columns`` (raw column indices), ``first`` [C] offsets,
    ``width`` the padded length."""

    def __init__(self, columns, widths):
        self.columns = np.asarray(columns, np.int64)
        self.widths = np.asarray(widths, np.int64)
        self.first = np.concatenate([[0], np.cumsum(self.widths)[:-1]]) \
            .astype(np.int64)
        self.width = int(-(-max(int(self.widths.sum()), 1) // 128) * 128)
        self.place = {int(c): k for k, c in enumerate(self.columns)}


def codes_of(trees, *parts) -> Codes:
    """The categorical columns (those a tree splits by a set) and, from
    the raw rows of ``parts`` ([F, n] each), each one's largest code."""
    columns = sorted({int(f) for t in trees
                      for f, c in zip(t["split_feature"], t["is_cat"]) if c})
    widths = [int(max(float(p[c].max()) for p in parts)) + 1 for c in columns]
    return Codes(columns, widths)


def tree_tables(tree: dict, features: int, leaves: int, codes: Codes):
    """``gbdt_plain.tree_tables`` and, per node, the 0/1 flag of a
    categorical node and its left set over the shared code axis."""
    sel, thr, left, right, plen = _paths(tree, features, leaves)
    is_cat = np.zeros((leaves - 1,), np.float32)
    member = np.zeros((leaves - 1, codes.width), np.float32)
    for i, cat in enumerate(tree["is_cat"]):
        if not cat:
            continue
        k = codes.place[int(tree["split_feature"][i])]
        inside = tree["left_codes"][i]
        inside = inside[(inside >= 0) & (inside < codes.widths[k])]
        is_cat[i] = 1.0
        member[i, codes.first[k] + inside] = 1.0
        thr[i] = np.float32(np.inf)
    return sel, thr, left, right, plen, is_cat, member


def _one_hot(xt, columns, widths, width):
    """The block's codes one-hot over the shared axis: [W, B] bfloat16,
    each column's segment from one compare of its own codes."""
    parts = [(jax.lax.iota(jnp.int32, w)[:, None]
              == xt[c].astype(jnp.int32)[None, :]).astype(jnp.bfloat16)
             for c, w in zip(columns, widths)]
    parts.append(jnp.zeros((width - sum(widths), xt.shape[1]), jnp.bfloat16))
    return jnp.concatenate(parts, axis=0)


@partial(jax.jit, static_argnames=("dtype", "columns", "widths", "width"))
def _block(xt, sign, score0, sel, thr, left, right, plen, is_cat, member,
           values, dtype, columns, widths, width, search=None):
    """One block of rows through every tree (``gbdt_plain._block`` with
    the sets).  With ``search`` also the searched nodes' sums: everything,
    what the stated split sends left, what every candidate threshold of a
    numeric column would, and the sums per code of every categorical
    column."""
    b = xt.shape[1]
    sub = min(SUB, b)
    q = _rounding(dtype)
    hot = _one_hot(xt, columns, widths, width)                      # [W, B]
    tabs = (sel, thr, left, right, plen, is_cat, member, values)
    if search is not None:
        cand, wanted, desc, node = search
        tabs += (wanted, desc, node)
        chunk = min(SEARCH_CHUNK, b)
        k, (f, c) = desc.shape[1], cand.shape

        def chunks(a):      # [M, B] -> [B/chunk, M, chunk]
            return a.reshape(a.shape[0], b // chunk, chunk).transpose(1, 0, 2)
        xt_c, hot_c = chunks(xt), chunks(hot)

        def searched(in_leaf, go_left, ghc, desc_t, node_t):
            in_node = jnp.matmul(desc_t, in_leaf)                     # [K, B]

            def one_chunk(acc, xs):
                node_k, left_k, ghc_k, x_k, hot_k = xs
                w = node_k[:, None, :] * ghc_k[None, :, :]            # [K, 3, chunk]
                le = (x_k[:, None, :] <= cand[:, :, None]).astype(jnp.float32)
                total, stated, cands, levels = acc
                w2 = w.reshape(k * 3, chunk)
                return (total + w.sum(-1),
                        stated + (w * left_k[:, None, :]).sum(-1),
                        cands + jnp.matmul(w2, le.reshape(f * c, chunk).T,
                                           precision=HIGHEST),
                        levels + jnp.matmul(w2, hot_k.astype(jnp.float32).T,
                                            precision=HIGHEST)), None
            zero = (jnp.zeros((k, 3)), jnp.zeros((k, 3)),
                    jnp.zeros((k * 3, f * c)), jnp.zeros((k * 3, width)))
            out, _ = jax.lax.scan(one_chunk, zero, (
                chunks(in_node), chunks(go_left[node_t]), chunks(ghc), xt_c,
                hot_c))
            return out

        def not_searched(in_leaf, go_left, ghc, desc_t, node_t):
            return (jnp.zeros((k, 3)), jnp.zeros((k, 3)),
                    jnp.zeros((k * 3, f * c)), jnp.zeros((k * 3, width)))

    def one_tree(score, tab):
        sel_t, thr_t, left_t, right_t, plen_t, cat_t, member_t, val_t = tab[:8]
        x_node = jnp.matmul(sel_t, xt, precision=HIGHEST)          # [NI, B]
        inside = jnp.matmul(member_t.astype(jnp.bfloat16), hot,
                            preferred_element_type=jnp.float32)     # [NI, B]
        go_left = jnp.where(cat_t[:, None] > 0, inside,
                            (x_node <= thr_t[:, None]).astype(jnp.float32))
        hits = jnp.matmul(left_t, go_left, precision=HIGHEST) + \
            jnp.matmul(right_t, 1.0 - go_left, precision=HIGHEST)  # [L, B]
        in_leaf = (hits == plen_t[:, None]).astype(jnp.float32)    # one 1 per row
        s = q(score)
        resp = q(-sign / q(1 + q(jnp.exp(q(sign * s)))))
        g = resp
        h = q(jnp.abs(resp) * q(1 - jnp.abs(resp)))
        real = jnp.abs(sign)        # padded rows carry sign 0
        ghc = jnp.stack([real, g * real, h * real])                     # [3, B]
        sums = q(jnp.einsum("lcs,kcs->clk", in_leaf.reshape(-1, b // sub, sub),
                            ghc.reshape(3, b // sub, sub), precision=HIGHEST))
        step = jnp.matmul(q(val_t), in_leaf, precision=HIGHEST)        # [B]
        score = q(s + step)
        if search is None:
            return score, (sums, score)
        wanted_t, desc_t, node_t = tab[8:]
        found = jax.lax.cond(wanted_t, searched, not_searched,
                             in_leaf, go_left, ghc, desc_t, node_t)
        return score, (sums, score, found)

    _, out = jax.lax.scan(one_tree, score0, tabs)
    return out


def follow(xt: np.ndarray, y: np.ndarray, trees, values: np.ndarray,
           init_score: float, block: int, dtype=jnp.float32,
           keep_scores: bool = False, search: dict = None,
           codes: Codes = None):
    """``gbdt_plain.follow`` with categorical nodes: ``codes`` lays the
    categorical columns' codes on one axis (``codes_of``; the same for
    the training and the held-out rows of one comparison)."""
    features, n = xt.shape
    leaves = values.shape[1]
    codes = codes_of(trees, xt) if codes is None else codes
    cols = list(zip(*(tree_tables(t, features, leaves, codes) for t in trees)))
    tabs = tuple(jnp.asarray(np.stack(c)) for c in cols)
    vals = jnp.asarray(values, jnp.float32)
    on_device, found = None, None
    if search is not None:
        on_device = tuple(jnp.asarray(search[k])
                          for k in ("cand", "wanted", "desc", "node"))
        which = np.flatnonzero(search["wanted"])
        found = [0.0, 0.0, 0.0, 0.0]
    total = np.zeros((len(trees), leaves, 3), np.float64)
    final = np.empty(n, np.float32)
    per_tree = np.empty((len(trees), n), np.float32) if keep_scores else None
    sign_all = np.where(y > 0, np.float32(1), np.float32(-1))
    for a in range(0, n, block):
        e = min(a + block, n)
        pad = block - (e - a)       # every block the same shape
        xb = np.ascontiguousarray(xt[:, a:e])
        sb = sign_all[a:e]
        if pad:
            xb = np.pad(xb, ((0, 0), (0, pad)))
            sb = np.pad(sb, (0, pad))
        score0 = jnp.full((block,), np.float32(init_score))
        out = _block(jnp.asarray(xb), jnp.asarray(sb), score0, *tabs, vals,
                     dtype=dtype, columns=tuple(int(c) for c in codes.columns),
                     widths=tuple(int(w) for w in codes.widths),
                     width=codes.width, search=on_device)
        sums, scores = out[:2]
        total += np.asarray(sums, np.float64).sum(axis=1)
        if search is not None:
            found = [acc + np.asarray(part[which], np.float64)
                     for acc, part in zip(found, out[2])]
        final[a:e] = np.asarray(scores[-1])[:e - a]
        if keep_scores:
            per_tree[:, a:e] = np.asarray(scores)[:, :e - a]
    if search is not None:
        search["found"], search["codes"] = found, codes
    return total, final, per_tree


def search_tables(xt: np.ndarray, trees, leaves: int, nodes: int,
                  candidates: int, wanted, categorical=()) -> dict:
    """``gbdt_plain.search_tables``; a categorical column (``categorical``:
    raw column indices) gets no threshold (its candidates are sets,
    ``split_search``), and ``level_rows`` its rows per code over ALL the
    training rows."""
    out = _dense.search_tables(xt, trees, leaves, nodes, candidates, wanted)
    out["categorical"] = [int(c) for c in categorical]
    for c in out["categorical"]:
        out["cand"][c] = np.float32(-np.inf)        # no row lies below
    out["level_rows"] = {c: np.bincount(xt[c].astype(np.int64))
                         for c in out["categorical"]}
    return out


def _subset_best(lvl: np.ndarray, total: np.ndarray, how: dict) -> float:
    """Best gain of the sorted-subset scan of one node and column:
    ``lvl`` [3, kept] (rows, G, H per kept level), ``total`` [3] the
    node's; upstream's scan as the module docstring tells it."""
    use = np.flatnonzero(lvl[0] >= how["cat_smooth"])
    if not len(use):
        return -np.inf
    order = use[np.argsort(lvl[1, use] / (lvl[2, use] + how["cat_smooth"]),
                           kind="stable")]
    most = min(int(how["max_cat_threshold"]), (len(use) + 1) // 2)
    l2, group = how["lambda_l2"] + how["cat_l2"], how["min_data_per_group"]
    best = -np.inf
    for seq in (order, order[::-1]):
        left, joined = np.zeros(3), 0.0
        for i in seq[:most]:
            left = left + lvl[:, i]
            joined += lvl[0, i]
            right = total - left
            if left[0] < how["min_rows"] or left[2] < how["min_hessian"]:
                continue
            if right[0] < max(how["min_rows"], group) \
                    or right[2] < how["min_hessian"]:
                break
            if joined < group:
                continue
            joined = 0.0
            best = max(best, left[1] ** 2 / (left[2] + l2)
                       + right[1] ** 2 / (right[2] + l2)
                       - total[1] ** 2 / (total[2] + l2))
    return best


def split_search(search: dict, trees, how: dict):
    """``gbdt_plain.split_search`` with the categorical columns' sets:
    per searched node its rows, the gain of the stated split (under the
    regulariser its kind of split is searched with, minus infinity where
    no scan could state it) and the best gain over every numeric column's
    thresholds and every categorical column's candidate sets.  ``how``:
    ``lambda_l2 min_rows min_hessian cat_l2 cat_smooth max_cat_threshold
    max_cat_to_onehot min_data_per_group cat_levels_kept``."""
    total, stated, cands, levels = search["found"]
    codes = search["codes"]
    f, c = search["cand"].shape
    w, k = total.shape[:2]
    l2 = how["lambda_l2"]

    def gain(left, tot, l2_):
        right = tot - left
        ok = ((left[..., 0] >= how["min_rows"]) & (right[..., 0] >= how["min_rows"])
              & (left[..., 2] >= how["min_hessian"])
              & (right[..., 2] >= how["min_hessian"]))
        with np.errstate(divide="ignore", invalid="ignore"):
            g = (left[..., 1] ** 2 / (left[..., 2] + l2_)
                 + right[..., 1] ** 2 / (right[..., 2] + l2_)
                 - tot[..., 1] ** 2 / (tot[..., 2] + l2_))
        return np.where(ok, g, -np.inf)

    numeric = np.moveaxis(cands.reshape(w, k, 3, f * c), 2, -1)   # [W, K, F*C, 3]
    every = gain(numeric, total[:, :, None, :], l2)
    best = every.max(-1)
    best_feature = every.argmax(-1) // c
    levels = levels.reshape(w, k, 3, -1)
    kept, onehot = {}, {}
    for col in search["categorical"]:
        rows = search["level_rows"][col]
        top = np.argsort(-rows, kind="stable")[:int(how["cat_levels_kept"])]
        kept[col] = np.sort(top[rows[top] > 0])
        onehot[col] = len(kept[col]) <= int(how["max_cat_to_onehot"])
    which = np.flatnonzero(search["wanted"])
    said = np.full((w, k), -np.inf)
    for wi, t in enumerate(which):
        tree = trees[t]
        for ki in range(k):
            if total[wi, ki, 0] <= 0:
                continue
            tot = total[wi, ki]
            for col in search["categorical"]:
                if col not in codes.place:
                    continue
                at = codes.first[codes.place[col]]
                lvl = levels[wi, ki][:, at + kept[col][kept[col] < codes.widths[
                    codes.place[col]]]]
                if onehot[col]:
                    g = gain(lvl.T, tot[None, :], l2).max(initial=-np.inf)
                else:
                    g = _subset_best(lvl, tot, how)
                if g > best[wi, ki]:
                    best[wi, ki], best_feature[wi, ki] = g, col
            node = int(search["node"][t, ki])
            if node >= len(tree["split_feature"]):
                continue
            col = int(tree["split_feature"][node])
            if not tree["is_cat"][node]:
                said[wi, ki] = gain(stated[wi, ki], tot, l2)
                continue
            # which levels have a bin is the program's to say (it counts
            # them on a sample of the rows, this reference on all of them:
            # near the 254th the two lists differ); what a scan may state
            # of them is not
            inside = np.asarray(tree["left_codes"][node])
            inside = inside[(inside >= 0)
                            & (inside < codes.widths[codes.place[col]])]
            if onehot[col]:
                allowed = len(inside) == 1
                l2_node = l2
            else:
                at = codes.first[codes.place[col]]
                allowed = 0 < len(inside) <= int(how["max_cat_threshold"]) \
                    and bool((levels[wi, ki][0, at + inside]
                              >= how["cat_smooth"]).all())
                l2_node = l2 + how["cat_l2"]
            if allowed:
                said[wi, ki] = gain(stated[wi, ki], tot, l2_node)
    return {"rows": total[:, :, 0], "stated": said, "best": best,
            "best_feature": best_feature}
