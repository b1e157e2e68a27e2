#!/usr/bin/env python3
"""Writes the four example set-ups under this directory, once, from a seed.

The suite used to read the upstream project's ``examples/`` tree, which is
on no machine that runs these tests.  These are small stand-ins with the
same layout: per task a ``train.conf`` / ``predict.conf`` pair, a train and
a test file (label in column 0, tab-separated; LibSVM for ranking) and the
side files the loader picks up by name (``.weight``, ``.init``, ``.query``).
The data is synthetic; only the shapes of the files follow upstream.

    python tests/data/examples/make_examples.py      # rewrites the files

The files are committed; rerun only to change them, and then rerun the
tests that read them (test_cli, test_consistency, test_native,
test_engine::test_binary_reference_example).
"""

import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20260928
N_TRAIN, N_TEST, N_FEAT = 500, 200, 28

TRAIN_CONF = """\
# {title}
task = train
boosting_type = gbdt
objective = {objective}
metric = {metric}
{extra}metric_freq = 1
is_training_metric = true
max_bin = 255
data = {train}
valid_data = {test}
num_trees = 100
learning_rate = {lr}
num_leaves = {leaves}
tree_learner = serial
feature_fraction = 0.8
bagging_freq = 5
bagging_fraction = 0.8
min_data_in_leaf = {min_data}
min_sum_hessian_in_leaf = {min_hess}
output_model = LightGBM_model.txt
"""

PREDICT_CONF = """\
task = predict
data = {test}
input_model = LightGBM_model.txt
"""


def _write(path, text):
    with open(os.path.join(HERE, path), "w") as fh:
        fh.write(text)


def _tsv(path, label, feat, label_fmt):
    rows = ("\t".join([label_fmt % lab] + ["%.3f" % v for v in row])
            for lab, row in zip(label, feat))
    _write(path, "\n".join(rows) + "\n")


def _column(path, vals, fmt):
    _write(path, "\n".join(fmt % v for v in vals) + "\n")


def _confs(d, **kw):
    os.makedirs(os.path.join(HERE, d), exist_ok=True)
    kw.setdefault("extra", "")
    _write(f"{d}/train.conf", TRAIN_CONF.format(**kw))
    _write(f"{d}/predict.conf", PREDICT_CONF.format(test=kw["test"]))


def binary(rng):
    d = "binary_classification"
    _confs(d, title="binary classification, weighted rows",
           objective="binary", metric="binary_logloss,auc",
           train="binary.train", test="binary.test", lr=0.1, leaves=63,
           min_data=50, min_hess=5.0)
    w = rng.normal(size=N_FEAT)
    for name, n in (("binary.train", N_TRAIN), ("binary.test", N_TEST)):
        feat = rng.normal(size=(n, N_FEAT))
        logit = feat @ w * 0.6 + 0.8 * feat[:, 0] * feat[:, 1]
        label = (logit + rng.normal(scale=1.0, size=n) > 0).astype(int)
        _tsv(f"{d}/{name}", label, feat, "%d")
        _column(f"{d}/{name}.weight", rng.uniform(0.5, 1.5, size=n), "%.3f")


def regression(rng):
    d = "regression"
    _confs(d, title="regression with initial scores",
           objective="regression", metric="l2",
           train="regression.train", test="regression.test", lr=0.05,
           leaves=31, min_data=20, min_hess=5.0)
    w = rng.normal(size=N_FEAT)
    for name, n in (("regression.train", N_TRAIN),
                    ("regression.test", N_TEST)):
        feat = rng.normal(size=(n, N_FEAT))
        linear = feat @ w * 0.3
        label = linear + np.sin(2.0 * feat[:, 0]) + (feat[:, 1] > 0.5) \
            + rng.normal(scale=0.2, size=n)
        _tsv(f"{d}/{name}", label, feat, "%.4f")
        # the initial score is a rough linear guess the trees improve on
        _column(f"{d}/{name}.init",
                0.5 * linear + rng.normal(scale=0.1, size=n), "%.4f")


def multiclass(rng):
    d = "multiclass_classification"
    _confs(d, title="five classes", objective="multiclass",
           metric="multi_logloss", extra="num_class = 5\n",
           train="multiclass.train", test="multiclass.test", lr=0.05,
           leaves=31, min_data=10, min_hess=1.0)
    centres = rng.normal(scale=1.2, size=(5, N_FEAT))
    for name, n in (("multiclass.train", N_TRAIN),
                    ("multiclass.test", N_TEST)):
        label = rng.integers(0, 5, size=n)
        feat = centres[label] * (rng.random((n, N_FEAT)) < 0.3) \
            + rng.normal(size=(n, N_FEAT))
        _tsv(f"{d}/{name}", label, feat, "%d")


def lambdarank(rng):
    d = "lambdarank"
    _confs(d, title="ranking: LibSVM rows, query sizes in .query",
           objective="lambdarank", metric="ndcg",
           extra="ndcg_eval_at = 1,3,5\n",
           train="rank.train", test="rank.test", lr=0.1, leaves=31,
           min_data=10, min_hess=1.0)
    n_feat = 30
    w = rng.normal(size=n_feat)
    for name, n_query in (("rank.train", 40), ("rank.test", 16)):
        sizes = rng.integers(8, 20, size=n_query)
        lines = []
        for size in sizes:
            feat = rng.normal(size=(size, n_feat)) \
                * (rng.random((size, n_feat)) < 0.6)
            rel = feat @ w + rng.normal(scale=0.7, size=size)
            label = np.digitize(rel, np.quantile(rel, [0.5, 0.8, 0.95]))
            for lab, row in zip(label, feat):
                cells = " ".join("%d:%.3f" % (j + 1, v)
                                 for j, v in enumerate(row) if v != 0.0)
                lines.append(f"{lab} {cells}")
        _write(f"{d}/{name}", "\n".join(lines) + "\n")
        _column(f"{d}/{name}.query", sizes, "%d")


def main():
    rng = np.random.default_rng(SEED)
    for make in (binary, regression, multiclass, lambdarank):
        make(rng)


if __name__ == "__main__":
    main()
