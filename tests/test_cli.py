"""CLI application tests (reference test strategy: test_consistency.py runs
the CLI on examples/*.conf and compares with the Python API), on the
stand-in examples under tests/data/examples/."""

import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.application import main, parse_argv, parse_config_file

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "tests", "data", "examples")
BIN_DIR = f"{EXAMPLES}/binary_classification"


def test_parse_config_file():
    conf = parse_config_file(f"{BIN_DIR}/train.conf")
    assert conf["objective"] == "binary"
    assert conf["task"] == "train"
    assert conf["metric"] == "binary_logloss,auc"


def test_cmdline_overrides_config(tmp_path):
    p = tmp_path / "a.conf"
    p.write_text("num_leaves = 63\nlearning_rate = 0.3\n")
    params = parse_argv([f"config={p}", "num_leaves=7"])
    assert params["num_leaves"] == "7"          # cmdline wins
    assert params["learning_rate"] == "0.3"     # file fills the rest


def test_cli_train_predict_roundtrip(tmp_path):
    model = tmp_path / "model.txt"
    result = tmp_path / "preds.txt"
    main([f"config={BIN_DIR}/train.conf",
          f"data={BIN_DIR}/binary.train",
          f"valid={BIN_DIR}/binary.test",
          f"output_model={model}",
          "num_trees=10", "min_data_in_leaf=20", "verbose=-1"])
    assert model.exists()

    main(["task=predict",
          f"data={BIN_DIR}/binary.test",
          f"input_model={model}",
          f"output_result={result}"])
    preds = np.loadtxt(result)
    te = np.loadtxt(f"{BIN_DIR}/binary.test")
    assert preds.shape[0] == te.shape[0]
    assert np.all((preds >= 0) & (preds <= 1))
    # CLI prediction == Python-API prediction on the same model
    bst = lgb.Booster(model_file=str(model))
    np.testing.assert_allclose(preds, bst.predict(te[:, 1:]), rtol=1e-6)
    # better than chance on held-out data
    auc = _auc(te[:, 0], preds)
    assert auc > 0.7


def _auc(y, p):
    order = np.argsort(p)
    y = y[order]
    n_pos = y.sum()
    n_neg = len(y) - n_pos
    ranks = np.arange(1, len(y) + 1)
    return (ranks[y > 0].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


def test_cli_convert_model_and_refit(tmp_path):
    model = tmp_path / "model.txt"
    main([f"data={BIN_DIR}/binary.train", "objective=binary",
          f"output_model={model}", "num_trees=5", "verbose=-1"])

    cpp_out = tmp_path / "pred.cpp"
    main(["task=convert_model", f"input_model={model}",
          f"convert_model={cpp_out}"])
    code = cpp_out.read_text()
    assert "PredictTree0" in code and "void Predict(" in code

    refit_model = tmp_path / "refit.txt"
    main(["task=refit", f"input_model={model}",
          f"data={BIN_DIR}/binary.train", f"output_model={refit_model}",
          "verbose=-1"])
    assert refit_model.exists()
    bst = lgb.Booster(model_file=str(refit_model))
    assert bst.num_trees() == 5


def test_python_dash_m_entry(tmp_path):
    """python -m lightgbm_tpu works as the CLI binary."""
    model = tmp_path / "m.txt"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu",
         f"data={BIN_DIR}/binary.train", "objective=binary",
         "num_trees=2", f"output_model={model}", "verbose=-1"],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300)
    assert r.returncode == 0, r.stderr
    assert model.exists()


def test_parameter_docs_in_sync():
    """docs/Parameters.md is generated from the _PARAMS registry and must
    not drift (reference .ci/test.sh:155-158 regenerates config_auto.cpp and
    fails CI on diff)."""
    import pathlib
    from lightgbm_tpu.config import generate_parameter_docs
    doc = pathlib.Path(__file__).resolve().parent.parent / "docs" / \
        "Parameters.md"
    assert doc.read_text() == generate_parameter_docs(), \
        "docs/Parameters.md is stale; run python -m lightgbm_tpu.config"
