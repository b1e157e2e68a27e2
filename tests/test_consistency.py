"""CLI-vs-Python-API consistency on example configs laid out like the
reference's (reference test strategy: tests/python_package_test/
test_consistency.py runs the CLI on examples/*.conf and compares against
the Python API).  The examples are the stand-ins under
tests/data/examples/."""

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.application import main

EXAMPLES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "examples")

CASES = {
    "regression": ("regression", "regression.train", "regression.test",
                   "regression"),
    "multiclass_classification": ("multiclass_classification",
                                  "multiclass.train", "multiclass.test",
                                  "multiclass"),
    "lambdarank": ("lambdarank", "rank.train", "rank.test", "lambdarank"),
}


@pytest.mark.parametrize("example", sorted(CASES))
def test_example_conf_trains_and_matches_python_api(example, tmp_path):
    d, train_f, test_f, objective = CASES[example]
    conf = f"{EXAMPLES}/{d}/train.conf"
    model = tmp_path / "model.txt"
    result = tmp_path / "preds.txt"
    overrides = [f"config={conf}",
                 f"data={EXAMPLES}/{d}/{train_f}",
                 f"valid={EXAMPLES}/{d}/{test_f}",
                 f"output_model={model}",
                 "num_trees=10", "verbose=-1"]
    main(overrides)
    assert model.exists()

    # CLI predictions == Python API predictions from the saved model
    main(["task=predict", f"data={EXAMPLES}/{d}/{test_f}",
          f"input_model={model}", f"output_result={result}",
          f"config={conf}"])
    cli_preds = np.loadtxt(result)
    bst = lgb.Booster(model_file=str(model))

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.parser import load_text_file
    X, y, meta = load_text_file(f"{EXAMPLES}/{d}/{test_f}", Config())
    api_preds = bst.predict(X)
    np.testing.assert_allclose(cli_preds, api_preds, rtol=1e-6, atol=1e-10)

    # sanity: the model actually learned something on its metric
    if objective == "regression":
        # the regression example ships companion .init score files; like the
        # reference, predictions EXCLUDE external init scores — add them
        # back for the quality check (gbdt.cpp:308 skips boost_from_average)
        init_f = f"{EXAMPLES}/{d}/{test_f}.init"
        base = np.loadtxt(init_f) if os.path.exists(init_f) else 0.0
        # 10 trees at the conf's small lr: require improvement over the
        # init-score baseline, not full convergence
        assert np.mean((api_preds + base - y) ** 2) < \
            np.mean((base - y) ** 2) * 0.98
    elif objective == "multiclass":
        acc = float((np.argmax(api_preds, axis=1) == y).mean())
        assert acc > 0.3  # 5 classes, 10 trees: well above the 0.2 chance
    else:  # lambdarank: model NDCG@5 must beat the untrained ranking
        from lightgbm_tpu.config import Config as _C
        from lightgbm_tpu.metrics import NDCGMetric
        from lightgbm_tpu.io.dataset import Metadata
        md = Metadata(len(y))
        md.set_label(y)
        md.set_group(meta["group"])
        m = NDCGMetric(_C({"eval_at": [5], "objective": "lambdarank"}))
        m.init(md, len(y))
        ndcg_model = m.eval(api_preds)[0][1]
        ndcg_zero = m.eval(np.zeros_like(api_preds))[0][1]
        assert ndcg_model > ndcg_zero + 0.02
