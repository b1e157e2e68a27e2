"""C ABI tests: a PURE C consumer program trains and predicts through
liblgbtpu_capi.so (the analogue of the reference's tests/c_api_test)."""

import os
import subprocess
import sysconfig
import textwrap

import numpy as np
import pytest

try:
    from lightgbm_tpu.native import build_capi
    CAPI = build_capi()
except Exception as e:  # no compiler / headers
    CAPI = None
    _err = str(e)

pytestmark = pytest.mark.skipif(CAPI is None,
                                reason="C API library unavailable")

C_PROGRAM = r"""
#include <stdio.h>
#include <stdlib.h>
#include <stdint.h>
#include <math.h>

extern const char* LGBMTPU_GetLastError(void);
extern int LGBMTPU_DatasetCreateFromMat(const double*, int64_t, int64_t,
                                        const double*, const char*, int64_t*);
extern int LGBMTPU_BoosterCreate(int64_t, const char*, int64_t*);
extern int LGBMTPU_BoosterUpdateOneIter(int64_t, int*);
extern int LGBMTPU_BoosterPredictForMat(int64_t, const double*, int64_t,
                                        int64_t, int, double*, int64_t*);
extern int LGBMTPU_BoosterSaveModel(int64_t, const char*);
extern int LGBMTPU_BoosterNumClasses(int64_t, int*);
extern int LGBMTPU_BoosterCreateFromModelfile(const char*, int64_t*);
extern int LGBMTPU_BoosterNumTrees(int64_t, int*);
extern int LGBMTPU_FreeHandle(int64_t);

#define CHECK(call) do { if ((call) != 0) { \
  fprintf(stderr, "FAIL %s: %s\n", #call, LGBMTPU_GetLastError()); \
  return 1; } } while (0)

int main(int argc, char** argv) {
  const int64_t n = 600, f = 4;
  double* X = malloc(sizeof(double) * n * f);
  double* y = malloc(sizeof(double) * n);
  unsigned s = 42;
  for (int64_t i = 0; i < n; ++i) {
    double row_sum = 0.0;
    for (int64_t j = 0; j < f; ++j) {
      s = s * 1103515245u + 12345u;
      double v = ((double)(s >> 8) / (1 << 24)) * 2.0 - 1.0;
      X[i * f + j] = v;
      row_sum += v;
    }
    y[i] = row_sum > 0.0 ? 1.0 : 0.0;
  }

  int64_t ds, bst;
  CHECK(LGBMTPU_DatasetCreateFromMat(
      X, n, f, y,
      "{\"objective\":\"binary\",\"num_leaves\":7,"
      "\"min_data_in_leaf\":5,\"verbose\":-1}", &ds));
  CHECK(LGBMTPU_BoosterCreate(
      ds, "{\"objective\":\"binary\",\"num_leaves\":7,"
          "\"min_data_in_leaf\":5,\"verbose\":-1}", &bst));
  int finished = 0;
  for (int it = 0; it < 10 && !finished; ++it)
    CHECK(LGBMTPU_BoosterUpdateOneIter(bst, &finished));
  int n_trees = 0;
  CHECK(LGBMTPU_BoosterNumTrees(bst, &n_trees));
  if (n_trees < 5) { fprintf(stderr, "too few trees: %d\n", n_trees); return 1; }

  int num_class = 0;
  CHECK(LGBMTPU_BoosterNumClasses(bst, &num_class));
  if (num_class != 1) { fprintf(stderr, "num_class %d\n", num_class); return 1; }
  double* preds = malloc(sizeof(double) * n * num_class);
  int64_t out_len = n * num_class;  /* in: capacity, out: written */
  CHECK(LGBMTPU_BoosterPredictForMat(bst, X, n, f, 0, preds, &out_len));
  int correct = 0;
  for (int64_t i = 0; i < n; ++i)
    if ((preds[i] > 0.5) == (y[i] > 0.5)) ++correct;
  double acc = (double)correct / n;
  printf("accuracy %.4f trees %d\n", acc, n_trees);
  if (acc < 0.85) { fprintf(stderr, "bad accuracy\n"); return 1; }

  CHECK(LGBMTPU_BoosterSaveModel(bst, argv[1]));
  int64_t bst2;
  CHECK(LGBMTPU_BoosterCreateFromModelfile(argv[1], &bst2));
  double* preds2 = malloc(sizeof(double) * n);
  out_len = n;
  CHECK(LGBMTPU_BoosterPredictForMat(bst2, X, n, f, 0, preds2, &out_len));
  /* capacity too small must FAIL, not overflow */
  int64_t tiny = 3;
  if (LGBMTPU_BoosterPredictForMat(bst2, X, n, f, 0, preds2, &tiny) == 0) {
    fprintf(stderr, "undersized buffer not rejected\n");
    return 1;
  }
  for (int64_t i = 0; i < n; ++i)
    if (fabs(preds[i] - preds2[i]) > 1e-5) {
      fprintf(stderr, "reload mismatch at %lld\n", (long long)i);
      return 1;
    }
  CHECK(LGBMTPU_FreeHandle(bst2));
  CHECK(LGBMTPU_FreeHandle(bst));
  CHECK(LGBMTPU_FreeHandle(ds));
  printf("C API OK\n");
  return 0;
}
"""


def test_c_consumer_end_to_end(tmp_path):
    src = tmp_path / "consumer.c"
    src.write_text(C_PROGRAM)
    exe = tmp_path / "consumer"
    libdir = sysconfig.get_config_var("LIBDIR")
    subprocess.run(
        ["gcc", "-O1", str(src), CAPI, f"-Wl,-rpath,{os.path.dirname(CAPI)}",
         f"-Wl,-rpath,{libdir}", "-lm", "-o", str(exe)],
        check=True, capture_output=True)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    import lightgbm_tpu
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(lightgbm_tpu.__file__)))
    env["PYTHONPATH"] = pkg_root
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([str(exe), str(tmp_path / "model.txt")], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "C API OK" in r.stdout
    assert "accuracy" in r.stdout


C_PROGRAM_V2 = r"""
#include <stdio.h>
#include <stdlib.h>
#include <stdint.h>
#include <string.h>
#include <math.h>

extern const char* LGBMTPU_GetLastError(void);
extern int LGBMTPU_DatasetInitStreaming(int64_t, const char*, int64_t*);
extern int LGBMTPU_DatasetPushRows(int64_t, const double*, int64_t, int64_t,
                                   const double*);
extern int LGBMTPU_DatasetMarkFinished(int64_t);
extern int LGBMTPU_DatasetGetNumData(int64_t, int64_t*);
extern int LGBMTPU_DatasetGetNumFeature(int64_t, int64_t*);
extern int LGBMTPU_DatasetCreateFromCSR(const int32_t*, const int32_t*,
                                        const double*, int64_t, int64_t,
                                        int64_t, const double*, const char*,
                                        int64_t*);
extern int LGBMTPU_BoosterCreate(int64_t, const char*, int64_t*);
extern int LGBMTPU_BoosterAddValidData(int64_t, int64_t);
extern int LGBMTPU_BoosterUpdateOneIter(int64_t, int*);
extern int LGBMTPU_BoosterGetEval(int64_t, int, double*, int64_t*);
extern int LGBMTPU_BoosterGetCurrentIteration(int64_t, int*);
extern int LGBMTPU_BoosterRollbackOneIter(int64_t);
extern int LGBMTPU_BoosterSaveModelToString(int64_t, char*, int64_t*);
extern int LGBMTPU_FreeHandle(int64_t);

#define CHECK(call) do { if ((call) != 0) { \
  fprintf(stderr, "FAIL %s: %s\n", #call, LGBMTPU_GetLastError()); \
  return 1; } } while (0)

static double frand(unsigned* s) {
  *s = *s * 1103515245u + 12345u;
  return ((double)(*s >> 8) / (1 << 24)) * 2.0 - 1.0;
}

int main(void) {
  const int64_t n = 500, f = 3, chunk = 120;
  const char* params = "{\"objective\":\"regression\",\"num_leaves\":7,"
                       "\"min_data_in_leaf\":5,\"metric\":[\"l2\"],"
                       "\"verbose\":-1}";
  /* ---- streaming ingestion in chunks */
  int64_t ds;
  CHECK(LGBMTPU_DatasetInitStreaming(f, params, &ds));
  unsigned s = 7;
  double buf[chunk * 3], yb[chunk];
  int64_t pushed = 0;
  while (pushed < n) {
    int64_t m = (n - pushed) < chunk ? (n - pushed) : chunk;
    for (int64_t i = 0; i < m; ++i) {
      double acc = 0;
      for (int64_t j = 0; j < f; ++j) { buf[i*f+j] = frand(&s); acc += buf[i*f+j]; }
      yb[i] = 2.0 * acc + 0.1 * frand(&s);
    }
    CHECK(LGBMTPU_DatasetPushRows(ds, buf, m, f, yb));
    pushed += m;
  }
  CHECK(LGBMTPU_DatasetMarkFinished(ds));
  int64_t nd = 0, nf = 0;
  CHECK(LGBMTPU_DatasetGetNumData(ds, &nd));
  CHECK(LGBMTPU_DatasetGetNumFeature(ds, &nf));
  if (nd != n || nf != f) { fprintf(stderr, "dims %lld %lld\n",
                                    (long long)nd, (long long)nf); return 1; }

  /* ---- CSR valid set (same distribution) */
  int32_t* indptr = malloc(sizeof(int32_t) * (n + 1));
  int32_t* indices = malloc(sizeof(int32_t) * n * f);
  double* vals = malloc(sizeof(double) * n * f);
  double* yv = malloc(sizeof(double) * n);
  int64_t nnz = 0;
  indptr[0] = 0;
  for (int64_t i = 0; i < n; ++i) {
    double acc = 0;
    for (int64_t j = 0; j < f; ++j) {
      double v = frand(&s);
      acc += v;
      if (j != 1 || v > 0) { indices[nnz] = (int32_t)j; vals[nnz++] = v; }
      else acc -= v;  /* dropped value acts as 0 */
    }
    yv[i] = 2.0 * acc + 0.1 * frand(&s);
    indptr[i + 1] = (int32_t)nnz;
  }
  int64_t dsv;
  CHECK(LGBMTPU_DatasetCreateFromCSR(indptr, indices, vals, n, nnz, f, yv,
                                     params, &dsv));

  int64_t bst;
  CHECK(LGBMTPU_BoosterCreate(ds, params, &bst));
  CHECK(LGBMTPU_BoosterAddValidData(bst, dsv));
  int fin = 0;
  for (int it = 0; it < 20 && !fin; ++it)
    CHECK(LGBMTPU_BoosterUpdateOneIter(bst, &fin));

  double evals[8];
  int64_t elen = 8;
  CHECK(LGBMTPU_BoosterGetEval(bst, 1, evals, &elen));
  if (elen < 1) { fprintf(stderr, "no eval values\n"); return 1; }
  printf("valid l2 %.5f\n", evals[0]);
  if (!(evals[0] < 3.0)) { fprintf(stderr, "weak fit\n"); return 1; }

  int cur = 0;
  CHECK(LGBMTPU_BoosterGetCurrentIteration(bst, &cur));
  CHECK(LGBMTPU_BoosterRollbackOneIter(bst));
  int cur2 = 0;
  CHECK(LGBMTPU_BoosterGetCurrentIteration(bst, &cur2));
  if (cur2 != cur - 1) { fprintf(stderr, "rollback %d->%d\n", cur, cur2);
                         return 1; }

  int64_t need = 0;
  CHECK(LGBMTPU_BoosterSaveModelToString(bst, NULL, &need));
  char* text = malloc(need);
  int64_t cap = need;
  CHECK(LGBMTPU_BoosterSaveModelToString(bst, text, &cap));
  if (strstr(text, "tree") == NULL) { fprintf(stderr, "bad model text\n");
                                      return 1; }
  CHECK(LGBMTPU_FreeHandle(bst));
  CHECK(LGBMTPU_FreeHandle(ds));
  CHECK(LGBMTPU_FreeHandle(dsv));
  printf("C API v2 OK\n");
  return 0;
}
"""


def test_c_consumer_streaming_csr_eval(tmp_path):
    """Streaming push + CSR + eval/rollback/save-to-string through the raw
    C ABI (reference c_api.h:177 InitStreaming, :203 PushRows, :340
    CreateFromCSR, :910 GetEval, :817 RollbackOneIter)."""
    src = tmp_path / "consumer2.c"
    src.write_text(C_PROGRAM_V2)
    exe = tmp_path / "consumer2"
    libdir = sysconfig.get_config_var("LIBDIR")
    subprocess.run(
        ["gcc", "-O1", str(src), CAPI, f"-Wl,-rpath,{os.path.dirname(CAPI)}",
         f"-Wl,-rpath,{libdir}", "-lm", "-o", str(exe)],
        check=True, capture_output=True)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    import lightgbm_tpu
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(lightgbm_tpu.__file__)))
    env["PYTHONPATH"] = pkg_root
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([str(exe)], env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "C API v2 OK" in r.stdout


def test_dual_parity_script_gated():
    """CPU<->TPU dual parity (reference test_dual.py) is a script run by
    hand on a machine with a chip: `python tests/dual_parity.py`.  Here
    just assert the script parses."""
    import ast, pathlib
    src = pathlib.Path(__file__).parent / "dual_parity.py"
    ast.parse(src.read_text())


@pytest.mark.tpu
def test_dual_parity_runs_on_tpu():
    """Placeholder for the dual-parity numbers: this suite is CPU-only,
    so it always skips.  The chip is exercised by `python chip_smoke.py`
    (one process per chip), which holds device predict to the host f64
    walk."""
    pytest.skip("the suite is CPU-only; the chip is exercised by "
                "chip_smoke.py")


C_PROGRAM_V3 = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <stdint.h>
#include <math.h>

extern const char* LGBMTPU_GetLastError(void);
extern int LGBMTPU_DatasetCreateFromMat(const double*, int64_t, int64_t,
                                        const double*, const char*, int64_t*);
extern int LGBMTPU_DatasetCreateFromCSC(const int32_t*, const int32_t*,
                                        const double*, int64_t, int64_t,
                                        int64_t, const double*, const char*,
                                        int64_t*);
extern int LGBMTPU_DatasetGetNumData(int64_t, int64_t*);
extern int LGBMTPU_BoosterCreate(int64_t, const char*, int64_t*);
extern int LGBMTPU_BoosterUpdateOneIter(int64_t, int*);
extern int LGBMTPU_BoosterPredictForMat(int64_t, const double*, int64_t,
                                        int64_t, int, double*, int64_t*);
/* last arg: in = capacity, out = doubles written */
extern int LGBMTPU_BoosterSaveModelToString(int64_t, char*, int64_t*);
extern int LGBMTPU_BoosterLoadModelFromString(const char*, int64_t*);
extern int LGBMTPU_BoosterGetNumFeature(int64_t, int*);
extern int LGBMTPU_BoosterGetFeatureNames(int64_t, char*, int64_t, int64_t*);
extern int LGBMTPU_BoosterGetEvalNames(int64_t, char*, int64_t, int64_t*);
extern int LGBMTPU_BoosterPredictForMatSingleRowFastInit(int64_t, int64_t,
                                                         int, int64_t*);
extern int LGBMTPU_BoosterPredictForMatSingleRowFast(int64_t, const double*,
                                                     double*, int64_t,
                                                     int64_t*);
extern int LGBMTPU_FreeHandle(int64_t);

#define CHECK(call) do { if ((call) != 0) { \
  fprintf(stderr, "FAIL %s: %s\n", #call, LGBMTPU_GetLastError()); \
  return 1; } } while (0)

int main(void) {
  const int64_t n = 500, f = 4;
  double* X = malloc(sizeof(double) * n * f);
  double* y = malloc(sizeof(double) * n);
  unsigned s = 7;
  for (int64_t i = 0; i < n; ++i) {
    double acc = 0.0;
    for (int64_t j = 0; j < f; ++j) {
      s = s * 1103515245u + 12345u;
      double v = ((double)(s >> 8) / (1 << 24)) * 2.0 - 1.0;
      X[i * f + j] = v;
      acc += v;
    }
    y[i] = acc > 0.0 ? 1.0 : 0.0;
  }

  int64_t ds = 0, bst = 0;
  CHECK(LGBMTPU_DatasetCreateFromMat(
      X, n, f, y,
      "{\"objective\":\"binary\",\"num_leaves\":15,\"verbose\":-1,"
      "\"min_data_in_leaf\":5,\"metric\":[\"auc\",\"binary_logloss\"]}",
      &ds));
  CHECK(LGBMTPU_BoosterCreate(
      ds,
      "{\"objective\":\"binary\",\"num_leaves\":15,\"verbose\":-1,"
      "\"min_data_in_leaf\":5,\"metric\":[\"auc\",\"binary_logloss\"]}",
      &bst));
  int fin = 0;
  for (int it = 0; it < 8; ++it) CHECK(LGBMTPU_BoosterUpdateOneIter(bst, &fin));

  /* num-feature + name queries */
  int nf = 0;
  CHECK(LGBMTPU_BoosterGetNumFeature(bst, &nf));
  if (nf != (int)f) { fprintf(stderr, "num_feature %d != %d\n", nf, (int)f);
                      return 1; }
  int64_t need = 0;
  CHECK(LGBMTPU_BoosterGetFeatureNames(bst, NULL, 0, &need));
  char* names = malloc(need);
  CHECK(LGBMTPU_BoosterGetFeatureNames(bst, names, need, &need));
  if (strstr(names, "Column_0") == NULL) {
    fprintf(stderr, "feature names missing: %s\n", names); return 1; }
  CHECK(LGBMTPU_BoosterGetEvalNames(bst, NULL, 0, &need));
  char* enames = malloc(need);
  CHECK(LGBMTPU_BoosterGetEvalNames(bst, enames, need, &need));
  if (strstr(enames, "auc") == NULL) {
    fprintf(stderr, "eval names missing: %s\n", enames); return 1; }

  /* model round trip through a string (in: capacity, out: required) */
  need = 0;
  CHECK(LGBMTPU_BoosterSaveModelToString(bst, NULL, &need));
  char* model = malloc(need);
  CHECK(LGBMTPU_BoosterSaveModelToString(bst, model, &need));
  int64_t bst2 = 0;
  CHECK(LGBMTPU_BoosterLoadModelFromString(model, &bst2));

  /* batch vs fast single-row: bit-for-bit */
  double* batch = malloc(sizeof(double) * n);
  int64_t wrote = n;  /* in: capacity */
  CHECK(LGBMTPU_BoosterPredictForMat(bst, X, n, f, 0, batch, &wrote));
  int64_t fastc = 0;
  CHECK(LGBMTPU_BoosterPredictForMatSingleRowFastInit(bst, f, 0, &fastc));
  double rowout[4];
  for (int64_t i = 0; i < n; ++i) {
    CHECK(LGBMTPU_BoosterPredictForMatSingleRowFast(fastc, X + i * f, rowout,
                                                    4, &wrote));
    if (wrote != 1 || rowout[0] != batch[i]) {
      fprintf(stderr, "fast row %lld mismatch %.17g vs %.17g\n",
              (long long)i, rowout[0], batch[i]);
      return 1;
    }
  }

  /* CSC construction matches the dense dataset row count */
  int64_t nnz = n * f;
  int32_t* colptr = malloc(sizeof(int32_t) * (f + 1));
  int32_t* rowind = malloc(sizeof(int32_t) * nnz);
  double* vals = malloc(sizeof(double) * nnz);
  for (int64_t j = 0; j <= f; ++j) colptr[j] = (int32_t)(j * n);
  for (int64_t j = 0; j < f; ++j)
    for (int64_t i = 0; i < n; ++i) {
      rowind[j * n + i] = (int32_t)i;
      vals[j * n + i] = X[i * f + j];
    }
  int64_t dsc = 0;
  CHECK(LGBMTPU_DatasetCreateFromCSC(colptr, rowind, vals, f, nnz, n, y,
                                     "{\"verbose\":-1}", &dsc));
  int64_t ndc = 0;
  CHECK(LGBMTPU_DatasetGetNumData(dsc, &ndc));
  if (ndc != n) { fprintf(stderr, "csc num_data %lld\n", (long long)ndc);
                  return 1; }

  CHECK(LGBMTPU_FreeHandle(fastc));
  CHECK(LGBMTPU_FreeHandle(bst2));
  CHECK(LGBMTPU_FreeHandle(bst));
  CHECK(LGBMTPU_FreeHandle(ds));
  CHECK(LGBMTPU_FreeHandle(dsc));
  printf("C API v3 OK\n");
  return 0;
}
"""


def test_c_consumer_fast_predict_csc_queries(tmp_path):
    """Fast single-row predict (bit-exact vs batch), CSC create,
    model-from-string, num-feature/feature-name/eval-name queries through
    the raw C ABI (VERDICT r1 #6; reference c_api.h:1162, :479, :677,
    :876, :845, :826)."""
    src = tmp_path / "consumer3.c"
    src.write_text(C_PROGRAM_V3)
    exe = tmp_path / "consumer3"
    libdir = sysconfig.get_config_var("LIBDIR")
    subprocess.run(
        ["gcc", "-O1", str(src), CAPI, f"-Wl,-rpath,{os.path.dirname(CAPI)}",
         f"-Wl,-rpath,{libdir}", "-lm", "-o", str(exe)],
        check=True, capture_output=True)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    import lightgbm_tpu
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(lightgbm_tpu.__file__)))
    env["PYTHONPATH"] = pkg_root
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([str(exe)], env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr + r.stdout
    assert "C API v3 OK" in r.stdout
