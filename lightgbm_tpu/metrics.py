"""Evaluation metrics.

TPU-native re-design of the reference metric layer (reference:
include/LightGBM/metric.h:24 ``Metric`` — Init/Eval/factor_to_bigger_better;
factory src/metric/metric.cpp:21-127).

Two evaluation paths:

- **Device** (``eval_device``): the big metrics (pointwise regression
  family, binary logloss/error, auc, ndcg) evaluate as jitted reductions on
  the default jax backend, so per-iteration eval moves only SCALARS across
  the device boundary instead of the full score array (the reference's CUDA
  metrics, src/metric/cuda/cuda_pointwise_metric.cu, reduce on device for
  the same reason).  f32 arithmetic; falls back to host automatically for
  unsupported configurations.
- **Host** (``eval``): float64 NumPy — exact, used for multiclass/xentropy/
  map and whenever ``deterministic=true`` pins bit-reproducible eval.

Families (reference files): regression_metric.hpp, binary_metric.hpp,
multiclass_metric.hpp, rank_metric.hpp (+dcg_calculator.cpp), map_metric.hpp,
xentropy_metric.hpp.  Convention preserved: ``Eval`` returns values where
HIGHER ``factor * value`` is better; factor is -1 for losses, +1 for
auc/ndcg/map (metric.h factor_to_bigger_better).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .config import Config
from .io.dataset import Metadata
from .utils import log


# --------------------------------------------------- jitted device kernels
# one compiled program per (metric, n) — reused every iteration

@functools.lru_cache(maxsize=None)
def _dev_pointwise(kind: str):
    import jax
    import jax.numpy as jnp

    def run(p, y, w, sw):
        if kind == "l2" or kind == "rmse":
            loss = (p - y) ** 2
        elif kind == "l1":
            loss = jnp.abs(p - y)
        elif kind == "binary_logloss":
            # f32-safe clip: 1 - 1e-15 is not representable in float32 (the
            # host path clips at 1e-15 in f64)
            pc = jnp.clip(p, 1e-7, 1 - 1e-7)
            loss = -(y * jnp.log(pc) + (1 - y) * jnp.log(1 - pc))
        elif kind == "binary_error":
            loss = ((p > 0.5) != (y > 0)).astype(jnp.float32)
        else:  # pragma: no cover
            raise ValueError(kind)
        avg = jnp.mean(loss) if w is None else jnp.sum(loss * w) / sw
        return jnp.sqrt(avg) if kind == "rmse" else avg
    return jax.jit(run, static_argnames=())


@functools.lru_cache(maxsize=None)
def _dev_auc():
    import jax
    import jax.numpy as jnp

    def run(score, y, w):
        n = score.shape[0]
        order = jnp.argsort(score, stable=True)
        ys = y[order]
        ws = jnp.ones_like(ys) if w is None else w[order]
        ss = score[order]
        pos_w = jnp.where(ys > 0, ws, 0.0)
        neg_w = jnp.where(ys > 0, 0.0, ws)
        total_pos = jnp.sum(pos_w)
        total_neg = jnp.sum(neg_w)
        # tie groups by score value; half credit inside a group (mirrors the
        # host _weighted_auc / reference binary_metric.hpp AUCMetric)
        boundary = jnp.concatenate([
            jnp.ones((1,), bool), ss[1:] != ss[:-1]])
        gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
        gpos = jax.ops.segment_sum(pos_w, gid, num_segments=n)
        gneg = jax.ops.segment_sum(neg_w, gid, num_segments=n)
        neg_before = jnp.cumsum(gneg) - gneg
        auc = jnp.sum(gpos * (neg_before + 0.5 * gneg))
        denom = total_pos * total_neg
        return jnp.where(denom > 0, auc / jnp.maximum(denom, 1e-30), 1.0)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _dev_ndcg_sums(ks: tuple):
    """Per-k NDCG SUMS over one query-length bucket's queries ([len(ks)]).
    The caller (NDCGMetric.eval_device_traced) runs this per bucket of the
    rank_query_buckets plan and divides the combined sum by the total
    query count — the bucketed twin of the old pad-to-max mean kernel, so
    the fused-eval path pays sum_b nq_b*Q_b sort work instead of
    nq*qmax.  jit's shape-keyed trace cache gives one lowering per bucket
    geometry, warm across iterations.  ``gain_slot`` is the docs' gains
    by padded slot (pads 0), laid out once at set-up: it rides the sort
    as an operand, so only the score is gathered."""
    import jax
    import jax.numpy as jnp

    def run(score, qidx, gain_slot, idcgs, disc):
        with jax.named_scope("ndcg_sort"):
            sc = jnp.where(qidx >= 0, score[jnp.maximum(qidx, 0)], -jnp.inf)
            _, g_srt = jax.lax.sort((-sc, gain_slot), dimension=1,
                                    num_keys=1, is_stable=True)
        out = []
        for i, k in enumerate(ks):
            kk = min(k, sc.shape[1])
            dcg = jnp.sum(g_srt[:, :kk] * disc[None, :kk], axis=1)
            idcg = idcgs[i]
            out.append(jnp.sum(jnp.where(idcg > 0, dcg
                                         / jnp.maximum(idcg, 1e-30), 1.0)))
        return jnp.stack(out)
    return jax.jit(run)


class Metric:
    NAME = "none"
    bigger_is_better = False

    def __init__(self, config: Config):
        self.config = config

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.metadata = metadata
        self.num_data = num_data
        self.label = np.asarray(metadata.label, np.float64)
        self.weight = None if metadata.weight is None else \
            np.asarray(metadata.weight, np.float64)
        self.sum_weight = float(self.weight.sum()) if self.weight is not None \
            else float(num_data)

    def eval(self, score: np.ndarray, objective=None) -> List[Tuple[str, float]]:
        raise NotImplementedError

    #: device-kernel id (_dev_pointwise) — None means no pointwise device
    #: path; AUC/NDCG override eval_device with their own kernels
    _DEV_KIND: Optional[str] = None
    #: True when eval_device_traced accepts the FULL [n, k] score matrix
    #: (multiclass metrics); single-output device kernels take a [n]
    #: column, so the fused scan only hands multiclass score matrices to
    #: metrics that declare this (boosting/gbdt.py fused_valid_ok)
    _DEV_MULTI: bool = False

    def eval_device(self, score_dev, objective=None
                    ) -> Optional[List[Tuple[str, float]]]:
        """Device-path evaluation over the resident score array; returns
        None when this metric/config has no device path (the caller then
        falls back to host ``eval``)."""
        vals = self.eval_device_traced(score_dev, objective)
        if vals is None:
            return None
        import numpy as np
        host = np.asarray(vals)
        return [(name, float(host[i]))
                for i, name in enumerate(self.display_names())]

    def fused_operands(self):
        """Device arrays ``eval_device_traced`` reads besides the score
        that the fused round program should take as ARGUMENTS (a pytree,
        handed back as its ``operands``), or None where the program may
        close over what the metric holds (objectives.py
        ``ObjectiveFunction.fused_operands`` says why)."""
        return None

    def eval_device_traced(self, score_dev, objective=None):
        """Traceable device evaluation: a f32 [len(display_names())] array
        of metric values, or None when no device path exists.  Safe to
        call INSIDE jit (the fused training scan evaluates valid metrics
        per round with this); ``eval_device`` is the host wrapper."""
        if self._DEV_KIND is None:
            return None
        import jax.numpy as jnp
        y, w = self._dev_arrays()
        p = self._dev_convert(score_dev, objective)
        val = _dev_pointwise(self._DEV_KIND)(
            p, y, w, jnp.float32(self.sum_weight))
        return jnp.reshape(val, (1,))

    def display_names(self) -> List[str]:
        """Metric display names in eval() output order, computable WITHOUT
        running an evaluation (LGBM_BoosterGetEvalNames)."""
        return [self.NAME]

    def _dev_arrays(self):
        import jax
        import jax.numpy as jnp
        if not hasattr(self, "_label_dev"):
            label_dev = jnp.asarray(self.label, jnp.float32)
            weight_dev = None if self.weight is None else \
                jnp.asarray(self.weight, jnp.float32)
            if isinstance(label_dev, jax.core.Tracer):
                # called under an ABSTRACT trace (e.g. the fused scan's
                # eval_shape): caching a tracer would leak it into later
                # real evaluations — return uncached, cache on the first
                # concrete call
                return label_dev, weight_dev
            self._label_dev = label_dev
            self._weight_dev = weight_dev
        return self._label_dev, self._weight_dev

    def _dev_convert(self, score, objective):
        if objective is not None and objective.need_convert_output:
            return objective.convert_output(score)
        return score

    def _avg(self, losses: np.ndarray) -> float:
        if self.weight is not None:
            return float(np.sum(losses * self.weight) / self.sum_weight)
        return float(np.mean(losses))

    def _convert(self, score: np.ndarray, objective) -> np.ndarray:
        if objective is not None and objective.need_convert_output:
            import jax.numpy as jnp
            return np.asarray(objective.convert_output(jnp.asarray(score)))
        return score


# ------------------------------------------------------------- regression
class _PointwiseRegression(Metric):
    def eval(self, score, objective=None):
        pred = self._convert(score, objective)
        return [(self.NAME, self._avg(self._loss(pred, self.label)))]


class L2Metric(_PointwiseRegression):
    NAME = "l2"
    _DEV_KIND = "l2"
    def _loss(self, p, y): return (p - y) ** 2


class RMSEMetric(_PointwiseRegression):
    NAME = "rmse"
    _DEV_KIND = "rmse"
    def eval(self, score, objective=None):
        pred = self._convert(score, objective)
        return [(self.NAME, float(np.sqrt(self._avg((pred - self.label) ** 2))))]


class L1Metric(_PointwiseRegression):
    NAME = "l1"
    _DEV_KIND = "l1"
    def _loss(self, p, y): return np.abs(p - y)


class QuantileMetric(_PointwiseRegression):
    NAME = "quantile"
    def _loss(self, p, y):
        a = self.config.alpha
        d = y - p
        return np.where(d >= 0, a * d, (a - 1.0) * d)


class HuberMetric(_PointwiseRegression):
    NAME = "huber"
    def _loss(self, p, y):
        a = self.config.alpha
        d = np.abs(p - y)
        return np.where(d <= a, 0.5 * d * d, a * (d - 0.5 * a))


class FairMetric(_PointwiseRegression):
    NAME = "fair"
    def _loss(self, p, y):
        c = self.config.fair_c
        x = np.abs(p - y)
        return c * x - c * c * np.log1p(x / c)


class PoissonMetric(_PointwiseRegression):
    NAME = "poisson"
    def _loss(self, p, y):
        eps = 1e-10
        return p - y * np.log(np.maximum(p, eps))


class MAPEMetric(_PointwiseRegression):
    NAME = "mape"
    def _loss(self, p, y):
        return np.abs((y - p) / np.maximum(1.0, np.abs(y)))


class GammaMetric(_PointwiseRegression):
    NAME = "gamma"
    def _loss(self, p, y):
        eps = 1e-10
        psafe = np.maximum(p, eps)
        return y / psafe + np.log(psafe) - 1.0 - np.log(np.maximum(y, eps))


class GammaDevianceMetric(_PointwiseRegression):
    NAME = "gamma_deviance"
    def _loss(self, p, y):
        eps = 1e-10
        r = y / np.maximum(p, eps)
        return 2.0 * (np.log(np.maximum(1.0 / np.maximum(r, eps), eps)) + r - 1.0)


class TweedieMetric(_PointwiseRegression):
    NAME = "tweedie"
    def _loss(self, p, y):
        rho = self.config.tweedie_variance_power
        eps = 1e-10
        psafe = np.maximum(p, eps)
        return -y * np.power(psafe, 1 - rho) / (1 - rho) + \
            np.power(psafe, 2 - rho) / (2 - rho)


# ----------------------------------------------------------------- binary
class BinaryLoglossMetric(Metric):
    NAME = "binary_logloss"
    _DEV_KIND = "binary_logloss"

    def eval(self, score, objective=None):
        p = np.clip(self._convert(score, objective), 1e-15, 1 - 1e-15)
        y = self.label
        loss = -(y * np.log(p) + (1 - y) * np.log(1 - p))
        return [(self.NAME, self._avg(loss))]


class BinaryErrorMetric(Metric):
    NAME = "binary_error"
    _DEV_KIND = "binary_error"

    def eval(self, score, objective=None):
        p = self._convert(score, objective)
        err = (p > 0.5) != (self.label > 0)
        return [(self.NAME, self._avg(err.astype(np.float64)))]


def _weighted_auc(label: np.ndarray, score: np.ndarray,
                  weight: Optional[np.ndarray]) -> float:
    """Rank-based weighted AUC (reference binary_metric.hpp AUCMetric)."""
    if weight is None:
        weight = np.ones_like(label, dtype=np.float64)
    order = np.argsort(score, kind="mergesort")
    y, s, w = label[order], score[order], weight[order]
    pos_w = np.where(y > 0, w, 0.0)
    neg_w = np.where(y > 0, 0.0, w)
    # tie-aware: within tied score groups, credit half the pos x neg mass
    cum_neg = np.cumsum(neg_w)
    total_neg = cum_neg[-1] if len(cum_neg) else 0.0
    total_pos = pos_w.sum()
    if total_pos <= 0 or total_neg <= 0:
        return 1.0
    # group by unique score
    boundary = np.r_[True, s[1:] != s[:-1]]
    gid = np.cumsum(boundary) - 1
    ng = gid[-1] + 1
    gpos = np.bincount(gid, weights=pos_w, minlength=ng)
    gneg = np.bincount(gid, weights=neg_w, minlength=ng)
    neg_before = np.cumsum(gneg) - gneg
    auc = np.sum(gpos * (neg_before + 0.5 * gneg))
    return float(auc / (total_pos * total_neg))


class AUCMetric(Metric):
    NAME = "auc"
    bigger_is_better = True

    def eval(self, score, objective=None):
        return [(self.NAME, _weighted_auc(self.label, score, self.weight))]

    def eval_device_traced(self, score_dev, objective=None):
        import jax.numpy as jnp
        y, w = self._dev_arrays()
        return jnp.reshape(_dev_auc()(score_dev, y, w), (1,))


class AveragePrecisionMetric(Metric):
    NAME = "average_precision"
    bigger_is_better = True

    def eval(self, score, objective=None):
        w = self.weight if self.weight is not None else \
            np.ones_like(self.label)
        order = np.argsort(-score, kind="mergesort")
        y, ww = self.label[order] > 0, w[order]
        tp = np.cumsum(np.where(y, ww, 0.0))
        fp = np.cumsum(np.where(y, 0.0, ww))
        prec = tp / np.maximum(tp + fp, 1e-20)
        total_pos = tp[-1] if len(tp) else 0.0
        if total_pos <= 0:
            return [(self.NAME, 1.0)]
        rec_delta = np.diff(np.r_[0.0, tp]) / total_pos
        return [(self.NAME, float(np.sum(prec * rec_delta)))]


# ------------------------------------------------------------- multiclass
class MultiLoglossMetric(Metric):
    NAME = "multi_logloss"

    def eval(self, score, objective=None):
        # score: [n, K] raw; convert via softmax/sigmoid per objective
        p = self._convert(score, objective)
        if objective is None or not objective.need_convert_output:
            ex = np.exp(score - score.max(axis=1, keepdims=True))
            p = ex / ex.sum(axis=1, keepdims=True)
        idx = self.label.astype(int)
        p_true = np.clip(p[np.arange(len(idx)), idx], 1e-15, None)
        if getattr(objective, "NAME", "") == "multiclassova":
            p_true = np.clip(p_true / np.maximum(p.sum(axis=1), 1e-15), 1e-15, None)
        return [(self.NAME, self._avg(-np.log(p_true)))]

    _DEV_MULTI = True

    def eval_device_traced(self, score_dev, objective=None):
        """Traced multiclass logloss over the [n, k] score matrix — the
        fused scan's per-round valid eval (round 6: multiclass rides the
        fused path).  Same formulation as host ``eval`` in device f32
        (the accepted device-eval precision class)."""
        import jax.numpy as jnp
        y, w = self._dev_arrays()
        idx = y.astype(jnp.int32)
        p = self._dev_convert(score_dev, objective)
        if objective is None or not objective.need_convert_output:
            ex = jnp.exp(score_dev - jnp.max(score_dev, axis=1,
                                             keepdims=True))
            p = ex / jnp.sum(ex, axis=1, keepdims=True)
        p_true = jnp.maximum(p[jnp.arange(p.shape[0]), idx], 1e-15)
        if getattr(objective, "NAME", "") == "multiclassova":
            p_true = jnp.maximum(
                p_true / jnp.maximum(jnp.sum(p, axis=1), 1e-15), 1e-15)
        losses = -jnp.log(p_true)
        val = jnp.mean(losses) if w is None else \
            jnp.sum(losses * w) / jnp.float32(self.sum_weight)
        return jnp.reshape(val.astype(jnp.float32), (1,))


class MultiErrorMetric(Metric):
    NAME = "multi_error"

    def eval(self, score, objective=None):
        k = self.config.multi_error_top_k
        idx = self.label.astype(int)
        true_score = score[np.arange(len(idx)), idx]
        # error when the true class is not within top-k (reference
        # multiclass_metric.hpp MultiErrorMetric)
        rank = (score > true_score[:, None]).sum(axis=1)
        err = rank >= k
        return [(self.NAME, self._avg(err.astype(np.float64)))]

    _DEV_MULTI = True

    def eval_device_traced(self, score_dev, objective=None):
        """Traced top-k multiclass error over the [n, k] score matrix
        (fused-scan valid eval; mirrors host ``eval`` — rank counting is
        integer-exact, so only ties at f32-vs-f64 score resolution can
        deviate, the same class as every other device metric)."""
        import jax.numpy as jnp
        topk = self.config.multi_error_top_k
        y, w = self._dev_arrays()
        idx = y.astype(jnp.int32)
        true_score = score_dev[jnp.arange(score_dev.shape[0]), idx]
        rank = jnp.sum(score_dev > true_score[:, None], axis=1)
        err = (rank >= topk).astype(jnp.float32)
        val = jnp.mean(err) if w is None else \
            jnp.sum(err * w) / jnp.float32(self.sum_weight)
        return jnp.reshape(val, (1,))


class AucMuMetric(Metric):
    """Multiclass AUC-mu (reference multiclass_metric.hpp:368 AucMuMetric,
    Kleiman & Page 2019)."""
    NAME = "auc_mu"
    bigger_is_better = True

    def eval(self, score, objective=None):
        y = self.label.astype(int)
        k = self.config.num_class
        wmat = None
        if self.config.auc_mu_weights:
            wmat = np.asarray(self.config.auc_mu_weights, np.float64).reshape(k, k)
        aucs = []
        for a in range(k):
            for b in range(a + 1, k):
                m = (y == a) | (y == b)
                if m.sum() == 0 or (y[m] == a).all() or (y[m] == b).all():
                    continue
                # decision value: difference in class scores, weighted by the
                # partition weights when provided
                if wmat is not None:
                    d = score[m] @ (wmat[a] - wmat[b])
                    d = -d
                else:
                    d = score[m, a] - score[m, b]
                aucs.append(_weighted_auc((y[m] == a).astype(np.float64), d,
                                          None if self.weight is None
                                          else self.weight[m]))
        return [(self.NAME, float(np.mean(aucs)) if aucs else 1.0)]


# ---------------------------------------------------------------- ranking
def _dcg_at_k(labels: np.ndarray, order: np.ndarray, k: int,
              label_gain: np.ndarray) -> float:
    top = order[:k]
    gains = label_gain[labels[top].astype(int)]
    return float(np.sum(gains / np.log2(np.arange(2, len(top) + 2))))


class NDCGMetric(Metric):
    """reference rank_metric.hpp NDCGMetric + dcg_calculator.cpp."""
    NAME = "ndcg"
    bigger_is_better = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("NDCG metric requires query information")
        self.bounds = metadata.query_boundaries
        mx = int(self.label.max()) + 1 if len(self.label) else 1
        gains = self.config.label_gain or [float((1 << i) - 1)
                                           for i in range(max(mx, 31))]
        self.label_gain = np.asarray(gains, np.float64)
        self.ks = list(self.config.eval_at)
        self.__dict__.pop("_rank_dev_buckets", None)

    def eval(self, score, objective=None):
        res = {k: [] for k in self.ks}
        qw = []
        for qi in range(len(self.bounds) - 1):
            s, e = self.bounds[qi], self.bounds[qi + 1]
            lbl = self.label[s:e]
            sc = score[s:e]
            order = np.argsort(-sc, kind="mergesort")
            ideal = np.argsort(-lbl, kind="mergesort")
            qw.append(1.0)
            for k in self.ks:
                idcg = _dcg_at_k(lbl, ideal, k, self.label_gain)
                if idcg <= 0:
                    res[k].append(1.0)
                else:
                    res[k].append(_dcg_at_k(lbl, order, k, self.label_gain) / idcg)
        return [(f"ndcg@{k}", float(np.average(res[k], weights=qw)))
                for k in self.ks]

    def display_names(self):
        return [f"ndcg@{k}" for k in self.ks]

    def fused_operands(self):
        return self._device_state()

    def _device_state(self):
        """The bucket plan's device arrays, built once (span
        ``rank_bucket_plan``): per bucket the padded doc-index matrix,
        the gains by slot, the ideal DCG at each cut-off of its queries
        and the position discount."""
        if not hasattr(self, "_rank_dev_buckets"):
            import jax.numpy as jnp
            from .objectives import (_by_slot, _max_dcg_by_slot,
                                     _rank_buckets)
            from .utils.timer import global_timer, phase
            with phase("rank_bucket_plan", global_timer):
                spec = getattr(self.config, "rank_query_buckets", "auto")
                buckets, _ = _rank_buckets(np.asarray(self.bounds), spec)
                gain_of_doc = self.label_gain[self.label.astype(int)]
                state = []
                for cap, _, idx in buckets:
                    gain_slot = _by_slot(gain_of_doc, idx, 0.0)
                    state.append((
                        jnp.asarray(idx),
                        jnp.asarray(gain_slot, jnp.float32),
                        jnp.asarray(_max_dcg_by_slot(gain_slot, self.ks),
                                    jnp.float32),
                        jnp.asarray(1.0 / np.log2(np.arange(cap) + 2.0),
                                    jnp.float32)))
                self._rank_dev_buckets = tuple(state)
        return self._rank_dev_buckets

    def eval_device_traced(self, score_dev, objective=None, operands=None):
        """NDCG at every cut-off, on the device.  ``operands`` is
        ``fused_operands()`` handed back by the fused round program,
        which takes the plan as arguments and not as literals."""
        state = self._device_state() if operands is None else operands
        total = None
        for bucket in state:
            part = _dev_ndcg_sums(tuple(self.ks))(score_dev, *bucket)
            total = part if total is None else total + part
        return total / (len(self.bounds) - 1)


class MapMetric(Metric):
    """reference map_metric.hpp MapMetric."""
    NAME = "map"
    bigger_is_better = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("MAP metric requires query information")
        self.bounds = metadata.query_boundaries
        self.ks = list(self.config.eval_at)

    def eval(self, score, objective=None):
        res = {k: [] for k in self.ks}
        for qi in range(len(self.bounds) - 1):
            s, e = self.bounds[qi], self.bounds[qi + 1]
            rel = (self.label[s:e] > 0).astype(np.float64)
            order = np.argsort(-score[s:e], kind="mergesort")
            rel_sorted = rel[order]
            hits = np.cumsum(rel_sorted)
            prec = hits / np.arange(1, len(rel_sorted) + 1)
            for k in self.ks:
                topk = slice(0, k)
                denom = min(k, int(rel.sum())) or 1
                ap = np.sum(prec[topk] * rel_sorted[topk]) / denom
                res[k].append(ap if rel.sum() > 0 else 1.0)
        return [(f"map@{k}", float(np.mean(res[k]))) for k in self.ks]

    def display_names(self):
        return [f"map@{k}" for k in self.ks]


# --------------------------------------------------------------- xentropy
class CrossEntropyMetric(Metric):
    NAME = "cross_entropy"

    def eval(self, score, objective=None):
        p = np.clip(self._convert(score, objective), 1e-15, 1 - 1e-15)
        y = self.label
        loss = -(y * np.log(p) + (1 - y) * np.log(1 - p))
        return [(self.NAME, self._avg(loss))]


class CrossEntropyLambdaMetric(Metric):
    NAME = "cross_entropy_lambda"

    def eval(self, score, objective=None):
        # p through the lambda link (see objectives.CrossEntropyLambda)
        w = self.weight if self.weight is not None else 1.0
        sp = np.logaddexp(0.0, score)
        p = np.clip(1.0 - np.exp(-w * sp), 1e-15, 1 - 1e-15)
        y = self.label
        loss = -(y * np.log(p) + (1 - y) * np.log(1 - p))
        return [(self.NAME, float(np.mean(loss)))]


class KLDivergenceMetric(Metric):
    NAME = "kullback_leibler"

    def eval(self, score, objective=None):
        p = np.clip(self._convert(score, objective), 1e-15, 1 - 1e-15)
        y = np.clip(self.label, 1e-15, 1 - 1e-15)
        kl = y * np.log(y / p) + (1 - y) * np.log((1 - y) / (1 - p))
        return [(self.NAME, self._avg(kl))]


_METRICS = {
    "l1": L1Metric, "l2": L2Metric, "rmse": RMSEMetric,
    "quantile": QuantileMetric, "huber": HuberMetric, "fair": FairMetric,
    "poisson": PoissonMetric, "mape": MAPEMetric, "gamma": GammaMetric,
    "gamma_deviance": GammaDevianceMetric, "tweedie": TweedieMetric,
    "binary_logloss": BinaryLoglossMetric, "binary_error": BinaryErrorMetric,
    "auc": AUCMetric, "average_precision": AveragePrecisionMetric,
    "multi_logloss": MultiLoglossMetric, "multi_error": MultiErrorMetric,
    "auc_mu": AucMuMetric,
    "ndcg": NDCGMetric, "map": MapMetric,
    "cross_entropy": CrossEntropyMetric,
    "cross_entropy_lambda": CrossEntropyLambdaMetric,
    "kullback_leibler": KLDivergenceMetric,
}

_DEFAULT_METRIC_FOR_OBJECTIVE = {
    "regression": "l2", "regression_l1": "l1", "huber": "huber", "fair": "fair",
    "poisson": "poisson", "quantile": "quantile", "mape": "mape",
    "gamma": "gamma", "tweedie": "tweedie",
    "binary": "binary_logloss",
    "multiclass": "multi_logloss", "multiclassova": "multi_logloss",
    "cross_entropy": "cross_entropy",
    "cross_entropy_lambda": "cross_entropy_lambda",
    "lambdarank": "ndcg", "rank_xendcg": "ndcg",
}


def create_metrics(config: Config) -> List[Metric]:
    """Factory (reference metric.cpp:21-127): explicit list, or the
    objective's default metric when none requested."""
    names: Sequence[str] = config.metric
    if not names:
        default = _DEFAULT_METRIC_FOR_OBJECTIVE.get(config.objective)
        names = [default] if default else []
    out: List[Metric] = []
    for nm in names:
        if nm in ("none", ""):
            continue
        cls = _METRICS.get(nm)
        if cls is None:
            log.warning(f"Unknown metric: {nm}")
            continue
        out.append(cls(config))
    return out
