"""The comparison that decides ``correct`` for a cell that trains a
binary-logloss GBDT on rows with CATEGORICAL columns (a configuration
names it: ``"comparison": "gbdt_binary_cat"``).

The seven readings are ``gbdt_binary``'s, computed by its own ``gaps``
(comparisons/gbdt_binary.py says what each holds and how) against the
plain reference the configuration names (reference/gbdt_plain_cat.py),
which decides a categorical node by membership of the raw code in the
node's stated set.  What this module adds is the hand-over: the answers'
trees carry ``is_cat`` and ``left_codes`` per node
(drivers/train_jobs_cat.py ``plain_trees``), the reference is bound to
one layout of the categorical columns' codes for the training and the
held-out rows alike, to the configuration's categorical columns and to
its published categorical parameters.  So here ``leaf_count_mismatch``
also holds the categorical binning (a level without a bin has to go
where the stated model sends its raw code: right), the partition by set
and the stated sets themselves; ``split_regret_mean`` also holds the
sorted-subset scan (both directions, ``cat_l2``, ``max_cat_threshold``:
a stated set that no scan of upstream's could state reads as an infinite
regret).
"""

from __future__ import annotations

from harness import load_module

_dense = load_module("comparisons", "gbdt_binary")
NOT_COMPARED = _dense.NOT_COMPARED
_CAT_KEYS = ("cat_l2", "cat_smooth", "max_cat_threshold", "max_cat_to_onehot",
             "min_data_per_group")


class Bound:
    """The reference with this comparison's trees, code layout and
    parameters filled in: what ``gbdt_binary.gaps`` calls."""

    def __init__(self, ref, cfg: dict, trees, inputs: dict):
        self.ref, self.trees = ref, trees
        self.columns = [int(c) for c in cfg["categorical"]["columns"]]
        self.codes = ref.codes_of(trees, inputs["train"][0], inputs["valid"][0])
        params = cfg["params"]
        self.how = {k: float(params[k]) for k in _CAT_KEYS}
        self.how["cat_levels_kept"] = int(cfg["compare"]["cat_levels_kept"])
        self.SUB = ref.SUB
        self.exact_auc, self.newton_values, self.node_gains = \
            ref.exact_auc, ref.newton_values, ref.node_gains

    def search_tables(self, xt, trees, leaves, nodes, candidates, wanted):
        return self.ref.search_tables(xt, trees, leaves, nodes, candidates,
                                      wanted, categorical=self.columns)

    def follow(self, *args, **kwargs):
        return self.ref.follow(*args, codes=self.codes, **kwargs)

    def split_search(self, search, lambda_l2, min_rows, min_hessian):
        return self.ref.split_search(search, self.trees, {
            **self.how, "lambda_l2": lambda_l2, "min_rows": min_rows,
            "min_hessian": min_hessian})


def gaps(ref, cfg: dict, answers: dict, inputs: dict, seed: int,
         split_trees="configured") -> dict:
    """Every compared number of one job.  ``inputs``: ``train`` and
    ``valid`` as ``(xt32 [F, n], y)``."""
    return _dense.gaps(Bound(ref, cfg, answers["trees"], inputs), cfg,
                       answers, inputs, seed, split_trees)


def control_answers(ref, cfg: dict, answers: dict, inputs: dict, dtype) -> dict:
    """``gbdt_binary.control_answers`` with the sets: the reference in the
    program's place, in the precision below."""
    return _dense.control_answers(Bound(ref, cfg, answers["trees"], inputs),
                                  cfg, answers, inputs, dtype)
