"""CPU <-> TPU training parity check (reference test_dual.py: the same
install trains device=cpu and device=gpu and asserts approx-equal logloss).

Run directly on a machine with a TPU attached:

    python tests/dual_parity.py

It trains the reference binary_classification example on the CPU backend
and on the TPU backend, one child process after the other (this parent
never imports jax, so each child has the chip to itself), then compares
AUC/logloss.  The TPU run uses the default
bfloat16 histogram products; parity gate is therefore metric-level
(|dAUC| < 2e-3), plus a strict-parity run with tpu_hist_dtype=float32
gated at 5e-4 (the reference's rel-1e-4 single-precision gate, loosened
for bf16-free f32 accumulation-order differences).
"""

import json
import os
import subprocess
import sys

import numpy as np

PARAMS = {"objective": "binary", "metric": ["auc", "binary_logloss"],
          "num_leaves": 31, "verbose": -1}
ROUNDS = 30

WORKER = r"""
import json, sys
import numpy as np
import lightgbm_tpu as lgb
params = json.loads(sys.argv[1])
bst = lgb.train(params, lgb.Dataset(
    '/root/reference/examples/binary_classification/binary.train',
    params=params), num_boost_round=int(sys.argv[2]))
te = np.loadtxt('/root/reference/examples/binary_classification/binary.test')
pred = bst.predict(te[:, 1:])
y = te[:, 0]
order = np.argsort(pred)
ranks = np.empty_like(order, dtype=float); ranks[order] = np.arange(len(pred))
pos = y > 0
auc = (ranks[pos].sum() - pos.sum() * (pos.sum() - 1) / 2) / (
    pos.sum() * (~pos).sum())
eps = 1e-15
ll = float(-np.mean(y * np.log(np.clip(pred, eps, 1)) +
                    (1 - y) * np.log(np.clip(1 - pred, eps, 1))))
print("RESULT " + json.dumps({"auc": float(auc), "logloss": ll}))
"""


def run_backend(backend: str, params) -> dict:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS=backend, PYTHONPATH=repo)
    r = subprocess.run([sys.executable, "-c", WORKER, json.dumps(params),
                        str(ROUNDS)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, r.stdout
    return json.loads(line[-1][len("RESULT "):])


def main():
    cpu = run_backend("cpu", PARAMS)
    tpu_bf16 = run_backend("tpu", dict(PARAMS, tpu_hist_dtype="bfloat16"))
    # float32 is the library default; spelled out for clarity
    strict = dict(PARAMS, tpu_hist_dtype="float32")
    tpu_f32 = run_backend("tpu", strict)
    print(f"cpu      auc={cpu['auc']:.6f} logloss={cpu['logloss']:.6f}")
    print(f"tpu bf16 auc={tpu_bf16['auc']:.6f} "
          f"logloss={tpu_bf16['logloss']:.6f}")
    print(f"tpu f32  auc={tpu_f32['auc']:.6f} logloss={tpu_f32['logloss']:.6f}")
    d_bf16 = abs(cpu["auc"] - tpu_bf16["auc"])
    d_f32 = abs(cpu["auc"] - tpu_f32["auc"])
    assert d_bf16 < 2e-3, f"bf16 AUC drift {d_bf16}"
    assert d_f32 < 5e-4, f"f32 AUC drift {d_f32}"
    print("DUAL PARITY OK")


if __name__ == "__main__":
    main()
