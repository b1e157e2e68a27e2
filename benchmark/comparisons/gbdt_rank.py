"""The comparison that decides ``correct`` for a cell that trains a
LambdaMART ranker (a configuration names it: ``"comparison":
"gbdt_rank"``).

The program's answers are the trees of one job, the held-out NDCG at each
cut-off it recorded after each round and the training scores it holds
when the job ends.  The plain reference (reference/rank_plain.py) follows
the same rounds from the raw rows, the labels and the query lengths, and
every number below is a gap between the two.  Each has a limit of its own
in the configuration's file (``limits``); the readings the limits were set
from are in PERF.md.

``leaf_count_mismatch``  leaves whose stated row count differs from the
    count of raw rows the reference routes there.  Exact: limit 0.
    Holds binning, the partition and the histogram's count channel.
``leaf_value_gap_median``, ``leaf_value_gap_p99``  over all leaves of all
    trees: |stated value - Newton step of the reference's own G, H| over
    the larger of that step and the tree's median step; the median leaf
    and the 99th percentile.  They hold the GRADIENTS: the reference's
    are LambdaMART's as published, pair by pair (a dropped ``|dNDCG|``, a
    truncation level ignored, a normalisation left out, a query boundary
    off by one doc, ties in reverse order all move them:
    tools/faults_rank.py), the leaf renewal, and the score update of every
    earlier round (stale scores give other orders, so other gradients).
    The tolerance is float32's: a doc's gradient is a sum of up to a
    thousand pair terms, the program's and the reference's in different
    orders, and a leaf's sums run over thousands of docs.
``train_score_gap``  worst doc: |score the program holds - the float32 sum
    of the stated leaf values' steps along the reference's routing, added
    in the program's order|.  Holds the score update.  The reference sorts
    by THAT sum (rank_plain.py says why), so its order is the program's
    wherever this reads zero.
``valid_ndcg_gap``  largest over the cut-offs and the rounds: |NDCG@k
    recorded - the reference's float64 NDCG@k of the held-out docs scored
    by the program's trees|.  Holds valid scoring in the scan, the device
    NDCG's sort, its ties, its query boundaries and its ideal DCG.  The
    tolerance is a float32 mean of 9,799 float32 ratios against float64.
``split_regret_mean``  the histogram's gradient sums and the split search,
    which nothing above holds: ``gbdt_binary``'s reading (the largest
    nodes of the first tree, the last and others drawn from ``--seed``;
    the gain a stated split gives away against the best of the reference's
    own quantile thresholds, from sums of the reference's own gradients).
    The candidates are held to ``min_data_in_leaf`` and
    ``min_sum_hessian_in_leaf``; the stated split to the same, less the
    slack ``stated_hessian_shortfall``'s limit states (a stated split
    further under the bound than that gives no gain at all: the regret
    reads infinite).
``stated_hessian_shortfall``  ``min_sum_hessian_in_leaf``, the one
    constraint the source's settings state beyond the defaults: over EVERY
    split of every tree, how far the thinner child's hessians lie under
    the bound, as a share of it, by the reference's own float64 sums of its
    own hessians (0 where both children hold it).  The program departs
    from the configuration here and the limit says by how much: it holds a
    child to the bound by the int8 levels its histograms sum (hessians
    rounded at random to ``num_grad_quant_bins`` levels: the sum is
    unbiased and a child the program read at the bound the reference reads
    a few per cent short).  A program that ignores the bound grows leaves
    of a handful of docs and reads near 1 (tools/faults_rank.py
    ``ignore_min_hessian``).
``valid_ndcg_deficit``  ``ndcg_floor.ndcg`` of the configuration minus the
    reference's held-out NDCG@``ndcg_floor.at`` after round
    ``ndcg_floor.round``: limit 0.  A floor under what the trees are
    worth, whatever was searched.

Worked out and printed, but NOT compared (``NOT_COMPARED``): the worst
leaf, the worst node's regret, the stated gains against the reference's,
the reference's NDCG at the floor's round and at the last, the nodes the
regret was taken over, each searched tree's own regret, how many splits
leave a child under ``min_sum_hessian_in_leaf`` at all, and the trees'
leaves (median and least: a tree of this job may stop short of
``num_leaves`` where ``min_sum_hessian_in_leaf`` binds).
"""

from __future__ import annotations

import numpy as np

from harness import load_module

_binary = load_module("comparisons", "gbdt_binary")
pad_values, searched_trees = _binary.pad_values, _binary.searched_trees

NOT_COMPARED = ("leaf_value_gap", "split_regret_max", "split_gain_gap",
                "ref_valid_ndcg_floor_round", "ref_valid_ndcg_last",
                "split_searched", "split_regret_by_tree",
                "stated_splits_short", "leaves_median", "leaves_min")


def _queries(ref, cfg: dict, part):
    _, y, sizes = part
    trunc = int(cfg["params"].get("lambdarank_truncation_level", 30))
    return ref.Queries(sizes, y, trunc)


def thinner_child_hessian(tree: dict, sums: np.ndarray) -> np.ndarray:
    """The smaller of the two children's hessian sums at every split of one
    tree, the leaf sums [L, 3] added up the tree."""
    kids = np.stack([np.asarray(tree[k], np.int64)
                     for k in ("left_child", "right_child")], 1)     # [splits, 2]
    node = np.zeros(len(kids))
    total = lambda c: sums[~c, 2] if c < 0 else node[c]
    # post-order without recursion: a node is summed once both children are
    order, stack = [], ([0] if len(kids) else [])
    while stack:
        k = stack.pop()
        order.append(k)
        stack.extend(int(c) for c in kids[k] if c >= 0)
    thin = np.zeros(len(kids))
    for k in reversed(order):
        both = [total(int(c)) for c in kids[k]]
        node[k], thin[k] = sum(both), min(both)
    return thin


def gaps(ref, cfg: dict, answers: dict, inputs: dict, seed: int,
         split_trees="configured") -> dict:
    """Every compared number of one job.  ``answers``: ``trees`` (plain
    dicts), ``valid_ndcg`` ({k: per round}), ``train_scores`` [n];
    ``inputs``: ``train`` and ``valid`` as ``(xt64 [F, n], y, sizes)``."""
    how, params = cfg["compare"], cfg["params"]
    block = int(how["block_rows"])
    lr, l2 = float(params["learning_rate"]), float(params.get("lambda_l2", 0.0))
    leaves = int(params["num_leaves"])
    ks = [int(k) for k in params["eval_at"]]
    trees = answers["trees"]
    xt, y, _ = inputs["train"]
    queries = _queries(ref, cfg, inputs["train"])
    stated = pad_values(trees, leaves)
    addends = ref.program_addends(stated, lr)

    if split_trees == "configured":
        split_trees = how.get("split_trees")
    wanted = searched_trees(len(trees), split_trees, seed)
    tables = ref.search_tables(xt, trees, leaves, int(how["split_nodes"]),
                               int(how["split_candidates"]), wanted)
    leaf_of = ref.route(xt, trees, leaves, block)
    sums, want, final, kept = ref.follow(queries, leaf_of, params, leaves,
                                         addends=addends, keep=tuple(wanted))
    ref.search(xt, trees, leaf_of, kept, tables, block)
    min_hessian = float(params.get("min_sum_hessian_in_leaf", 1e-3))
    min_rows = int(params.get("min_data_in_leaf", 20))
    found = ref.split_search(tables, l2, min_rows, min_hessian)
    # the stated split under the job's bounds less the stated slack: the
    # program holds a child to min_sum_hessian_in_leaf by its int8 hessian
    # levels, so the reference's float sums read a child it took a little
    # short of it (``stated_hessian_shortfall``, a compared number)
    slack = float(cfg["limits"]["stated_hessian_shortfall"])
    stated_gain = ref.split_search(tables, l2, min_rows,
                                   min_hessian * (1.0 - slack))["stated"]
    held = (found["rows"] >= float(how["split_min_share"]) * len(y)) \
        & (found["best"] > 0)
    with np.errstate(invalid="ignore"):      # inf - inf where nothing is held
        away = np.clip(found["best"] - stated_gain, 0.0, None)
    regret_max = float((away / found["best"])[held].max()) if held.any() else None
    regret_mean = float(away[held].sum() / found["best"][held].sum()) \
        if held.any() else None
    by_tree = {int(t): round(float(away[w][held[w]].sum()
                                   / found["best"][w][held[w]].sum()), 4)
               for w, t in enumerate(wanted) if held[w].any()}
    thin = np.concatenate([thinner_child_hessian(tr, sums[t])
                           for t, tr in enumerate(trees)])
    shortfall = float(np.clip(1.0 - thin / min_hessian, 0.0, None).max(initial=0.0))

    counts = pad_values(trees, leaves, "leaf_count")
    mismatch = int((counts != sums[..., 0]).sum())
    floor = np.array([np.median(np.abs(want[t, :tr["num_leaves"]]))
                      for t, tr in enumerate(trees)])[:, None]
    rel = np.abs(stated - want) / np.maximum(np.abs(want), floor)
    live = sums[..., 0] > 0
    score_gap = float(np.abs(np.asarray(answers["train_scores"], np.float64)
                             - final).max())

    xv = inputs["valid"][0]
    valid_queries = _queries(ref, cfg, inputs["valid"])
    per_tree = ref.scores_after_each(ref.route(xv, trees, leaves, block), addends)
    ref_ndcg = np.array([valid_queries.ndcg(s, ks) for s in per_tree])   # [R, ks]
    got = np.array([answers["valid_ndcg"][k] for k in ks], np.float64).T
    ndcg_gap = float(np.abs(got - ref_ndcg).max())

    at = how["ndcg_floor"]
    floor_round = ref_ndcg[min(int(at["round"]), len(trees)) - 1,
                           ks.index(int(at["at"]))]
    share = float(how["split_min_share"])
    gain_gap = 0.0
    for t, tree in enumerate(trees):
        g_ref, rows = ref.node_gains(tree, sums[t], l2)
        big = rows >= share * len(y)
        if big.any():
            gain_gap = max(gain_gap, float(
                (np.abs(np.asarray(tree["split_gain"], np.float64) - g_ref)[big]
                 / g_ref[big]).max()))
    grown = [int(tr["num_leaves"]) for tr in trees]
    return {"leaf_count_mismatch": mismatch,
            "leaf_value_gap": float(rel[live].max()),
            "leaf_value_gap_median": float(np.median(rel[live])),
            "leaf_value_gap_p99": float(np.quantile(rel[live], 0.99)),
            "train_score_gap": score_gap, "valid_ndcg_gap": ndcg_gap,
            "split_regret_max": regret_max, "split_regret_mean": regret_mean,
            "valid_ndcg_deficit": float(at["ndcg"]) - float(floor_round),
            "split_gain_gap": gain_gap,
            "ref_valid_ndcg_floor_round": float(floor_round),
            "ref_valid_ndcg_last": float(ref_ndcg[-1, ks.index(int(at["at"]))]),
            "split_searched": int(held.sum()),
            "split_regret_by_tree": by_tree,
            "stated_hessian_shortfall": shortfall,
            "stated_splits_short": int((thin < min_hessian).sum()),
            "leaves_median": float(np.median(grown)), "leaves_min": min(grown)}


def control_answers(ref, cfg: dict, answers: dict, inputs: dict, dtype) -> dict:
    """The reference in the program's place, in the precision below: the
    program's splits kept, and every leaf value, training score and
    held-out NDCG worked out again with scores, gradients, sums and values
    held in ``dtype``, the reference's own values added to its scores.
    Judged by ``gaps`` like a run's answers, it has to come out as not
    correct."""
    how, params = cfg["compare"], cfg["params"]
    block, leaves = int(how["block_rows"]), int(params["num_leaves"])
    ks = [int(k) for k in params["eval_at"]]
    trees = answers["trees"]
    queries = _queries(ref, cfg, inputs["train"])
    leaf_of = ref.route(inputs["train"][0], trees, leaves, block)
    _, values, final, _ = ref.follow(queries, leaf_of, params, leaves,
                                     addends=None, dtype=dtype)
    valid_queries = _queries(ref, cfg, inputs["valid"])
    per_tree = ref.scores_after_each(
        ref.route(inputs["valid"][0], trees, leaves, block),
        values.astype(np.float32), dtype)
    ndcg = np.array([valid_queries.ndcg(s, ks) for s in per_tree])
    out = [dict(tr, leaf_value=values[t, :tr["num_leaves"]])
           for t, tr in enumerate(trees)]
    return {"trees": out, "train_scores": final,
            "valid_ndcg": {k: list(ndcg[:, i]) for i, k in enumerate(ks)}}
