"""The comparison that decides ``correct`` for a cell that trains a
binary-logloss GBDT (a configuration names it: ``"comparison":
"gbdt_binary"``; another objective brings ``comparisons/<name>.py`` with
the same ``gaps``).

The program's answers are the trees of one job, the held-out AUC it
recorded after each round and the training scores it holds when the job
ends.  The plain reference (reference/<name>.py) follows the same rounds
from the raw rows and every number below is a gap between the two.  Each
has a limit of its own in the configuration's file (``limits``); the
readings the limits were set from are in PERF.md.

``leaf_count_mismatch``  leaves whose stated row count differs from the
    count of raw rows the reference routes there.  Exact: limit 0.
    Holds binning, the partition and the histogram's count channel.
``leaf_value_gap_median``, ``leaf_value_gap_p99``  over all leaves of all
    trees: |stated value - Newton step of the reference's own G, H| over
    the larger of that step and the tree's median step; the median leaf
    and the 99th percentile.  Hold the gradients, the leaf renewal, and
    the score update of every earlier round (stale scores give other
    gradients).
``train_score_gap``  worst row: |score the program holds - sum of the
    stated leaf values along the reference's routing|.  Holds the score
    update (the leaf-value gather by the program's own row-to-leaf map).
``valid_auc_gap``  worst round: |AUC recorded - exact float64 AUC of the
    reference's held-out scores|.  Holds valid scoring in the scan and
    the device AUC.
``split_regret_mean``  the histogram's gradient sums and the split
    search, which nothing above holds (leaf values are renewed from full
    gradients).  For the ``split_nodes`` largest nodes with at least
    ``split_min_share`` of the rows, in the first tree, the last, and
    ``split_trees`` - 2 more drawn from ``--seed``: the reference sums
    its own gradients over the node's raw rows on either side of the
    stated split and of every feature's ``split_candidates`` quantile
    thresholds (its own, not the program's bins); a node's regret is the
    gain the stated split gives away against the best candidate, 0 where
    the stated split is the better.  Compared: all held nodes together,
    gain given away over gain to be had, so the large nodes weigh most.
    A histogram that loses, mis-scales or overflows a sum states splits
    that the raw rows do not bear out.
``valid_auc_deficit``  ``auc_floor.auc`` of the configuration minus the
    reference's exact held-out AUC after round ``auc_floor.round``:
    limit 0.  A floor under what the trees are worth, whatever was
    searched.

Worked out and printed, but NOT compared (``NOT_COMPARED``; PERF.md
section 6 has the readings): ``leaf_value_gap``, the worst leaf, and
``split_regret_max``, the worst held node's regret over its best gain,
which both swing from one data set to the next; ``split_gain_gap``, the
stated gain of the large nodes against the reference's gain of the same
split, which the int8 gradients move as far as a fault does; the
reference's held-out AUC after the floor's round and after the last; and
``split_searched``, the number of nodes the regret was taken over.
"""

from __future__ import annotations

import numpy as np

NOT_COMPARED = ("leaf_value_gap", "split_regret_max", "split_gain_gap", "ref_valid_auc_floor_round",
                "ref_valid_auc_last", "split_searched")


def init_score_of(y: np.ndarray) -> float:
    p = float(np.clip(np.mean(y, dtype=np.float64), 1e-15, 1 - 1e-15))
    return float(np.log(p / (1.0 - p)))


def pad_values(trees, leaves: int, key: str = "leaf_value") -> np.ndarray:
    out = np.zeros((len(trees), leaves), np.float64)
    for t, tree in enumerate(trees):
        out[t, :tree["num_leaves"]] = tree[key]
    return out


def searched_trees(rounds: int, count, seed: int) -> list:
    """Which trees' splits are searched: the first, the last, and others
    drawn from the seed, ``count`` in all (every tree where ``count`` is
    None or reaches ``rounds``)."""
    if count is None or count >= rounds:
        return list(range(rounds))
    fixed = sorted({0, rounds - 1})
    rest = [t for t in range(rounds) if t not in fixed]
    drawn = np.random.default_rng([int(seed), 7]).choice(
        rest, size=max(0, count - len(fixed)), replace=False)
    return sorted(fixed + [int(t) for t in drawn])


def gaps(ref, cfg: dict, answers: dict, inputs: dict, seed: int,
         split_trees="configured") -> dict:
    """Every compared number of one job.  ``answers``: ``trees`` (plain
    dicts), ``valid_auc`` per round, ``train_scores`` [n]; ``inputs``:
    ``train`` and ``valid`` as ``(xt32, y)``."""
    train, valid = inputs["train"], inputs["valid"]
    how = cfg["compare"]
    block = int(how["block_rows"])
    params = cfg["params"]
    lr, l2 = float(params["learning_rate"]), float(params.get("lambda_l2", 0.0))
    leaves = int(params["num_leaves"])
    trees = answers["trees"]
    xt, y = train
    init = init_score_of(y)
    stated = pad_values(trees, leaves)
    stated_step = stated.copy()
    stated_step[0, :trees[0]["num_leaves"]] -= init
    if split_trees == "configured":
        split_trees = how.get("split_trees")
    search = ref.search_tables(
        xt, trees, leaves, int(how["split_nodes"]), int(how["split_candidates"]),
        searched_trees(len(trees), split_trees, seed))
    sums, final, _ = ref.follow(xt, y, trees, stated_step, init, block,
                                search=search)
    want, want_step = ref.newton_values(sums, lr, l2, init)
    found = ref.split_search(search, l2, int(params.get("min_data_in_leaf", 20)),
                             float(params.get("min_sum_hessian_in_leaf", 1e-3)))
    held = (found["rows"] >= float(how["split_min_share"]) * len(y)) \
        & (found["best"] > 0)
    away = np.clip(found["best"] - found["stated"], 0.0, None)[held]
    regret_max = float((away / found["best"][held]).max()) if held.any() else None
    regret_mean = float(away.sum() / found["best"][held].sum()) if held.any() else None

    counts = pad_values(trees, leaves, "leaf_count")
    mismatch = int((counts != sums[..., 0]).sum())
    floor = np.array([np.median(np.abs(want_step[t, :tr["num_leaves"]]))
                      for t, tr in enumerate(trees)])[:, None]
    rel = np.abs(stated - want) / np.maximum(np.abs(want_step), floor)
    live = sums[..., 0] > 0
    leaf_gap = float(rel[live].max())
    leaf_gap_median = float(np.median(rel[live]))
    score_gap = float(np.abs(np.asarray(answers["train_scores"], np.float64)
                             - final).max())

    xv, yv = valid
    _, _, per_tree = ref.follow(xv, yv, trees, stated_step, init,
                                min(block, _round_up(xv.shape[1], ref.SUB)),
                                keep_scores=True)
    ref_auc = [ref.exact_auc(yv, per_tree[t]) for t in range(len(trees))]
    auc_gap = float(np.abs(np.asarray(answers["valid_auc"]) - ref_auc).max())

    auc_floor = how["auc_floor"]
    auc_floor_round = ref_auc[min(int(auc_floor["round"]), len(trees)) - 1]
    share = float(how["split_min_share"])
    gain_gap = 0.0
    for t, tree in enumerate(trees):
        g_ref, rows = ref.node_gains(tree, sums[t], l2)
        big = rows >= share * len(y)
        if big.any():
            gain_gap = max(gain_gap, float(
                (np.abs(np.asarray(tree["split_gain"], np.float64) - g_ref)[big]
                 / g_ref[big]).max()))
    return {"leaf_count_mismatch": mismatch, "leaf_value_gap": leaf_gap,
            "leaf_value_gap_median": leaf_gap_median,
            "leaf_value_gap_p99": float(np.quantile(rel[live], 0.99)),
            "train_score_gap": score_gap, "valid_auc_gap": auc_gap,
            "split_regret_max": regret_max, "split_regret_mean": regret_mean,
            "valid_auc_deficit": float(auc_floor["auc"]) - auc_floor_round,
            "split_gain_gap": gain_gap, "ref_valid_auc_floor_round": auc_floor_round,
            "ref_valid_auc_last": ref_auc[-1], "split_searched": int(held.sum())}


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def control_answers(ref, cfg: dict, answers: dict, inputs: dict, dtype) -> dict:
    """The reference in the program's place, in the precision below: the
    program's splits kept, and every leaf value, training score and
    held-out score worked out again with scores, gradients, sums and
    values held in ``dtype``.  Judged by ``gaps`` like a run's answers,
    it has to come out as not correct."""
    params = cfg["params"]
    lr, l2 = float(params["learning_rate"]), float(params.get("lambda_l2", 0.0))
    leaves = int(params["num_leaves"])
    trees = answers["trees"]
    (xt, y), valid = inputs["train"], inputs["valid"]
    block = int(cfg["compare"]["block_rows"])
    init = init_score_of(y)
    step = np.zeros((len(trees), leaves), np.float64)
    stated = np.zeros_like(step)
    for t in range(len(trees)):
        # tree t's sums need the scores that the trees before it left
        sums, final, _ = ref.follow(xt, y, trees[:t + 1], step[:t + 1], init,
                                    block, dtype=dtype)
        full, own = ref.newton_values(sums, lr, l2, init, dtype=dtype)
        step[t], stated[t] = own[t], full[t]
    _, final, _ = ref.follow(xt, y, trees, step, init, block, dtype=dtype)
    xv, yv = valid
    _, _, per_tree = ref.follow(xv, yv, trees, step, init,
                                min(block, _round_up(xv.shape[1], ref.SUB)),
                                dtype=dtype, keep_scores=True)
    out = [dict(tr, leaf_value=stated[t, :tr["num_leaves"]])
           for t, tr in enumerate(trees)]
    return {"trees": out, "train_scores": final,
            "valid_auc": [ref.exact_auc(yv, per_tree[t])
                          for t in range(len(trees))]}
