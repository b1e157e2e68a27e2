"""Objective functions (gradient/hessian providers).

TPU-native re-design of the reference objective layer (reference:
include/LightGBM/objective_function.h:19 ``ObjectiveFunction`` — Init /
GetGradients / BoostFromScore / ConvertOutput / RenewTreeOutput; factory
src/objective/objective_function.cpp; CUDA twins src/objective/cuda/ keep
gradients on-device, which is the default here: ``get_gradients`` is jitted
XLA over the score array).

Implemented families (reference files cited per class):
  regression_objective.hpp : l2 (+reg_sqrt), l1, huber, fair, poisson,
                             quantile, mape, gamma, tweedie
  binary_objective.hpp     : binary logloss (sigmoid, is_unbalance,
                             scale_pos_weight)
  multiclass_objective.hpp : softmax (num_class trees/iter), ova
  xentropy_objective.hpp   : cross_entropy, cross_entropy_lambda
  rank_objective.hpp       : lambdarank (pairwise, |dNDCG| weights,
                             truncation, norm), rank_xendcg
``objective=none`` lets callers pass custom grad/hess per iteration
(reference c_api.h:793 LGBM_BoosterUpdateOneIterCustom).

Per-leaf output renewal for l1/quantile/mape (reference RenewTreeOutput
weighted-percentile) runs on host NumPy: it is a once-per-tree O(n log n)
pass whose result is L scalars.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .io.dataset import Metadata
from .obs.metrics import count_event, global_metrics
from .ops import compile_cache as cc
from .utils import log
from .utils.timer import global_timer, phase


def _weighted_percentile(values: np.ndarray, weights: Optional[np.ndarray],
                         alpha: float) -> float:
    """Weighted alpha-quantile (reference regression_objective.hpp
    PercentileFun/WeightedPercentileFun)."""
    if len(values) == 0:
        return 0.0
    order = np.argsort(values)
    v = values[order]
    if weights is None:
        pos = alpha * (len(v) - 1)
        lo = int(np.floor(pos))
        hi = min(lo + 1, len(v) - 1)
        frac = pos - lo
        return float(v[lo] * (1 - frac) + v[hi] * frac)
    w = weights[order]
    cw = np.cumsum(w)
    target = alpha * cw[-1]
    idx = int(np.searchsorted(cw, target))
    return float(v[min(idx, len(v) - 1)])


class ObjectiveFunction:
    """Base interface (reference objective_function.h:19)."""

    num_model_per_iteration: int = 1
    need_renew_tree_output: bool = False
    is_constant_hessian: bool = False
    need_convert_output: bool = False
    #: get_gradients is a PURE function of the score (no per-call mutable
    #: Python state), so the trainer may wrap it in one jax.jit.  Set
    #: False where a call mutates state (rank_xendcg's RNG split;
    #: lambdarank under position debiasing, whose bias factors update
    #: each iteration).
    jit_safe: bool = True

    def __init__(self, config: Config):
        self.config = config
        self.metadata: Optional[Metadata] = None
        self.num_data = 0
        #: booster-scoped MetricsRegistry, attached by the trainer AFTER
        #: init (GBDT builds its registry after objective.init runs);
        #: compile-cache bumps dual-scope through it when present
        self._metrics = None

    def attach_booster_metrics(self, registry) -> None:
        """Point telemetry at a booster's own registry and mirror any
        gauges the objective computed at init time (the ranking
        objectives publish ``rank_pad_rows`` / ``rank_bucket_count``)."""
        self._metrics = registry
        for gname in ("rank_pad_rows", "rank_bucket_count"):
            val = getattr(self, "_" + gname, None)
            if val is not None:
                registry.set_gauge(gname, val)

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.metadata = metadata
        self.num_data = num_data
        self._label = jnp.asarray(metadata.label, jnp.float32)
        self._weight = None if metadata.weight is None else \
            jnp.asarray(metadata.weight, jnp.float32)
        # a cached gradient jit traced against the PREVIOUS dataset's
        # labels/weights must not survive re-init (reset_training_data
        # re-runs init on the same objective instance)
        if hasattr(self, "_grad_jit"):
            del self._grad_jit

    def get_gradients(self, score: jax.Array) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def jitted_gradients(self, score: jax.Array
                         ) -> Tuple[jax.Array, jax.Array]:
        """``get_gradients`` under ONE ``jax.jit`` (cached per instance)
        when the objective declares itself pure — one device dispatch per
        iteration instead of one per op (lambdarank's pairwise graph is
        ~40 ops).  Falls back to the eager call for objectives with
        per-call mutable state (jit_safe)."""
        if not self.jit_safe:
            return self.get_gradients(score)
        if not hasattr(self, "_grad_jit"):
            self._grad_jit = jax.jit(self.get_gradients)
        return self._grad_jit(score)

    def fused_operands(self):
        """Device arrays ``get_gradients`` reads besides the score that
        the fused round program should take as ARGUMENTS (a pytree, handed
        back as ``get_gradients``'s second argument), or None where the
        program may close over whatever the objective holds.  A ranking
        job's slot matrices are tens of millions of words: as literals
        they would be serialized into the round program's HLO, hashed for
        the cache key and stored in the cache entry."""
        return None

    def place_rows(self, place) -> None:
        """Put the per-row device arrays the gradient program takes as
        ARGUMENTS where ``place`` puts the booster's rows (a row-sharded
        tree learner's mesh): left on the first device they cross to the
        other devices at every call.  Nothing to do where the program
        closes over them (this class's per-instance jit)."""

    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    def convert_output(self, raw: jax.Array) -> jax.Array:
        return raw

    def renew_tree_output(self, score: np.ndarray, residual_fn, leaf_of_row:
                          np.ndarray, num_leaves: int) -> Optional[np.ndarray]:
        return None

    def _apply_weight(self, g, h):
        if self._weight is not None:
            return g * self._weight, h * self._weight
        return g, h

    @property
    def name(self) -> str:
        return type(self).NAME  # type: ignore[attr-defined]


# --------------------------------------------------------------- regression
class RegressionL2Loss(ObjectiveFunction):
    """reference regression_objective.hpp RegressionL2loss."""
    NAME = "regression"
    is_constant_hessian = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.config.reg_sqrt:
            lbl = np.asarray(metadata.label, np.float64)
            self._label = jnp.asarray(np.sign(lbl) * np.sqrt(np.abs(lbl)),
                                      jnp.float32)
        self.need_convert_output = bool(self.config.reg_sqrt)

    def get_gradients(self, score):
        g = score - self._label
        h = jnp.ones_like(score)
        return self._apply_weight(g, h)

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self._label, np.float64)
        w = None if self._weight is None else np.asarray(self._weight, np.float64)
        return float(np.average(lbl, weights=w))

    def convert_output(self, raw):
        if self.config.reg_sqrt:
            return jnp.sign(raw) * raw * raw
        return raw


class RegressionL1Loss(ObjectiveFunction):
    """reference regression_objective.hpp RegressionL1loss — leaf values are
    renewed to the weighted median of residuals."""
    NAME = "regression_l1"
    is_constant_hessian = True
    need_renew_tree_output = True
    _alpha = 0.5

    def get_gradients(self, score):
        g = jnp.sign(score - self._label)
        h = jnp.ones_like(score)
        return self._apply_weight(g, h)

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self._label, np.float64)
        w = None if self._weight is None else np.asarray(self._weight, np.float64)
        return _weighted_percentile(lbl, w, 0.5)

    def renew_tree_output(self, score, residual_fn, leaf_of_row, num_leaves):
        label = np.asarray(self._label, np.float64)
        resid = label - score
        w = None if self._weight is None else np.asarray(self._weight, np.float64)
        out = np.zeros(num_leaves)
        for leaf in range(num_leaves):
            m = leaf_of_row == leaf
            out[leaf] = _weighted_percentile(resid[m],
                                             None if w is None else w[m],
                                             self._alpha)
        return out


class RegressionHuberLoss(ObjectiveFunction):
    """reference regression_objective.hpp RegressionHuberLoss."""
    NAME = "huber"

    def get_gradients(self, score):
        a = self.config.alpha
        r = score - self._label
        g = jnp.where(jnp.abs(r) <= a, r, a * jnp.sign(r))
        h = jnp.ones_like(score)
        return self._apply_weight(g, h)

    boost_from_score = RegressionL2Loss.boost_from_score


class RegressionFairLoss(ObjectiveFunction):
    """reference regression_objective.hpp RegressionFairLoss."""
    NAME = "fair"

    def get_gradients(self, score):
        c = self.config.fair_c
        r = score - self._label
        g = c * r / (jnp.abs(r) + c)
        h = c * c / ((jnp.abs(r) + c) ** 2)
        return self._apply_weight(g, h)


class RegressionPoissonLoss(ObjectiveFunction):
    """reference regression_objective.hpp RegressionPoissonLoss — log link."""
    NAME = "poisson"
    need_convert_output = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if (np.asarray(metadata.label) < 0).any():
            log.fatal("[poisson]: at least one target label is negative")

    def get_gradients(self, score):
        ef = jnp.exp(score)
        g = ef - self._label
        h = jnp.exp(score + self.config.poisson_max_delta_step)
        return self._apply_weight(g, h)

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self._label, np.float64)
        w = None if self._weight is None else np.asarray(self._weight, np.float64)
        return float(np.log(max(np.average(lbl, weights=w), 1e-20)))

    def convert_output(self, raw):
        return jnp.exp(raw)


class RegressionQuantileLoss(ObjectiveFunction):
    """reference regression_objective.hpp RegressionQuantileloss."""
    NAME = "quantile"
    is_constant_hessian = True
    need_renew_tree_output = True

    def get_gradients(self, score):
        a = self.config.alpha
        g = jnp.where(score >= self._label, 1.0 - a, -a)
        h = jnp.ones_like(score)
        return self._apply_weight(g, h)

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self._label, np.float64)
        w = None if self._weight is None else np.asarray(self._weight, np.float64)
        return _weighted_percentile(lbl, w, self.config.alpha)

    def renew_tree_output(self, score, residual_fn, leaf_of_row, num_leaves):
        r = RegressionL1Loss.renew_tree_output
        self._alpha = self.config.alpha
        return r(self, score, residual_fn, leaf_of_row, num_leaves)


class RegressionMAPELoss(ObjectiveFunction):
    """reference regression_objective.hpp RegressionMAPELOSS — L1 with
    1/|label| weights and weighted-median renewal."""
    NAME = "mape"
    is_constant_hessian = True
    need_renew_tree_output = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = np.abs(np.asarray(metadata.label, np.float64))
        self._label_weight = jnp.asarray(1.0 / np.maximum(1.0, lbl), jnp.float32)

    def get_gradients(self, score):
        g = jnp.sign(score - self._label) * self._label_weight
        h = self._label_weight
        return self._apply_weight(g, h)

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self._label, np.float64)
        lw = np.asarray(self._label_weight, np.float64)
        w = lw if self._weight is None else lw * np.asarray(self._weight, np.float64)
        return _weighted_percentile(lbl, w, 0.5)

    def renew_tree_output(self, score, residual_fn, leaf_of_row, num_leaves):
        label = np.asarray(self._label, np.float64)
        resid = label - score
        lw = np.asarray(self._label_weight, np.float64)
        if self._weight is not None:
            lw = lw * np.asarray(self._weight, np.float64)
        out = np.zeros(num_leaves)
        for leaf in range(num_leaves):
            m = leaf_of_row == leaf
            out[leaf] = _weighted_percentile(resid[m], lw[m], 0.5)
        return out


class RegressionGammaLoss(ObjectiveFunction):
    """reference regression_objective.hpp RegressionGammaLoss — log link."""
    NAME = "gamma"
    need_convert_output = True

    def get_gradients(self, score):
        g = 1.0 - self._label * jnp.exp(-score)
        h = self._label * jnp.exp(-score)
        return self._apply_weight(g, h)

    boost_from_score = RegressionPoissonLoss.boost_from_score
    convert_output = RegressionPoissonLoss.convert_output


class RegressionTweedieLoss(ObjectiveFunction):
    """reference regression_objective.hpp RegressionTweedieLoss — log link."""
    NAME = "tweedie"
    need_convert_output = True

    def get_gradients(self, score):
        rho = self.config.tweedie_variance_power
        g = -self._label * jnp.exp((1.0 - rho) * score) + \
            jnp.exp((2.0 - rho) * score)
        h = -self._label * (1.0 - rho) * jnp.exp((1.0 - rho) * score) + \
            (2.0 - rho) * jnp.exp((2.0 - rho) * score)
        return self._apply_weight(g, h)

    boost_from_score = RegressionPoissonLoss.boost_from_score
    convert_output = RegressionPoissonLoss.convert_output


# ------------------------------------------------------------------- binary
def _binary_gradients(score, sign, weight, sigmoid, lw_pos, lw_neg):
    """Gradients and hessians of the weighted binary log-loss at
    ``score`` for rows of ``sign`` +1/-1 (``weight`` [n] or None)."""
    z = sign * sigmoid * score
    resp = -sign * sigmoid / (1.0 + jnp.exp(z))
    lw = jnp.where(sign > 0, lw_pos, lw_neg)
    g = resp * lw
    h = jnp.abs(resp) * (sigmoid - jnp.abs(resp)) * lw
    if weight is not None:
        return g * weight, h * weight
    return g, h


_binary_gradients_jit = jax.jit(
    _binary_gradients, static_argnames=("sigmoid", "lw_pos", "lw_neg"))


class BinaryLogloss(ObjectiveFunction):
    """reference binary_objective.hpp BinaryLogloss."""
    NAME = "binary"
    need_convert_output = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = np.asarray(metadata.label)
        if not np.isin(np.unique(lbl), (0, 1)).all():
            log.fatal("Binary objective requires 0/1 labels")
        # label weights (is_unbalance / scale_pos_weight,
        # binary_objective.hpp ctor)
        w = None if metadata.weight is None else np.asarray(metadata.weight)
        cnt_pos = float((lbl == 1).sum() if w is None else w[lbl == 1].sum())
        cnt_neg = float((lbl == 0).sum() if w is None else w[lbl == 0].sum())
        lw_pos, lw_neg = 1.0, 1.0
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                lw_neg = cnt_pos / cnt_neg
            else:
                lw_pos = cnt_neg / cnt_pos
        lw_pos *= self.config.scale_pos_weight
        self._lw_pos, self._lw_neg = lw_pos, lw_neg
        self._cnt_pos, self._cnt_neg = cnt_pos, cnt_neg
        self._sign = jnp.asarray(np.where(lbl == 1, 1.0, -1.0), jnp.float32)

    def get_gradients(self, score):
        return _binary_gradients(score, self._sign, self._weight,
                                 self.config.sigmoid, self._lw_pos,
                                 self._lw_neg)

    def jitted_gradients(self, score):
        """One program a shape and hyperparameter set, the rows' signs
        and weights its arguments.  The base class's per-instance jit
        closes over them: every new booster on the same rows compiled
        its gradients again, with n floats as a constant of the
        program."""
        return _binary_gradients_jit(
            score, self._sign, self._weight,
            sigmoid=float(self.config.sigmoid), lw_pos=float(self._lw_pos),
            lw_neg=float(self._lw_neg))

    def place_rows(self, place) -> None:
        self._sign = place(self._sign)
        self._weight = place(self._weight)

    def boost_from_score(self, class_id=0):
        s = self.config.sigmoid
        tot = self._cnt_pos * self._lw_pos + self._cnt_neg * self._lw_neg
        if tot <= 0:
            return 0.0
        p = np.clip(self._cnt_pos * self._lw_pos / tot, 1e-15, 1 - 1e-15)
        init = np.log(p / (1.0 - p)) / s
        log.info(f"[binary:BoostFromScore]: pavg={p:.6f} -> initscore={init:.6f}")
        return float(init)

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * raw))


# --------------------------------------------------------------- multiclass
class MulticlassSoftmax(ObjectiveFunction):
    """reference multiclass_objective.hpp MulticlassSoftmax — one tree per
    class per iteration; grad = p - y, hess = 2 p (1-p) (factor from ref)."""
    NAME = "multiclass"
    need_convert_output = True

    def __init__(self, config):
        super().__init__(config)
        self.num_model_per_iteration = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = np.asarray(metadata.label).astype(np.int32)
        k = self.config.num_class
        if lbl.min() < 0 or lbl.max() >= k:
            log.fatal(f"Label must be in [0, {k}) for multiclass")
        self._onehot = jnp.asarray(np.eye(k, dtype=np.float32)[lbl])  # [n, K]

    def get_gradients(self, score):
        # score: [n, K]
        p = jax.nn.softmax(score, axis=1)
        g = p - self._onehot
        h = 2.0 * p * (1.0 - p)
        if self._weight is not None:
            g = g * self._weight[:, None]
            h = h * self._weight[:, None]
        return g, h

    def convert_output(self, raw):
        return jax.nn.softmax(raw, axis=-1)


class MulticlassOVA(ObjectiveFunction):
    """reference multiclass_objective.hpp MulticlassOVA — K independent
    binary-logloss problems."""
    NAME = "multiclassova"
    need_convert_output = True

    def __init__(self, config):
        super().__init__(config)
        self.num_model_per_iteration = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = np.asarray(metadata.label).astype(np.int32)
        k = self.config.num_class
        self._sign = jnp.asarray(
            np.where(np.eye(k)[lbl] > 0, 1.0, -1.0), jnp.float32)  # [n, K]

    def get_gradients(self, score):
        s = self.config.sigmoid
        z = self._sign * s * score
        resp = -self._sign * s / (1.0 + jnp.exp(z))
        g = resp
        h = jnp.abs(resp) * (s - jnp.abs(resp))
        if self._weight is not None:
            g = g * self._weight[:, None]
            h = h * self._weight[:, None]
        return g, h

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.config.sigmoid * raw))


# ------------------------------------------------------------ cross-entropy
class CrossEntropy(ObjectiveFunction):
    """reference xentropy_objective.hpp CrossEntropy — probabilistic labels
    in [0, 1], logistic link."""
    NAME = "cross_entropy"
    need_convert_output = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lbl = np.asarray(metadata.label)
        if lbl.min() < 0 or lbl.max() > 1:
            log.fatal("[cross_entropy]: labels must be in [0, 1]")

    def get_gradients(self, score):
        p = jax.nn.sigmoid(score)
        g = p - self._label
        h = p * (1.0 - p)
        return self._apply_weight(g, h)

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self._label, np.float64)
        w = None if self._weight is None else np.asarray(self._weight, np.float64)
        p = np.clip(np.average(lbl, weights=w), 1e-15, 1 - 1e-15)
        return float(np.log(p / (1.0 - p)))

    def convert_output(self, raw):
        return jax.nn.sigmoid(raw)


class CrossEntropyLambda(ObjectiveFunction):
    """reference xentropy_objective.hpp CrossEntropyLambda — alternative
    parameterization with weights entering the link:
    z = log1p(w * exp(f)), p = 1 - exp(-z)."""
    NAME = "cross_entropy_lambda"
    need_convert_output = True

    def get_gradients(self, score):
        # link: p = 1 - exp(-w * softplus(f));
        # L = -y log p + (1-y) w softplus(f)
        # dL/df = w sig(f) (1 - y/p)
        # d2L/df2 = w sig(f)(1-sig(f))(1 - y/p) + w^2 sig(f)^2 y (1-p)/p^2
        y = self._label
        w = jnp.ones_like(score) if self._weight is None else self._weight
        sig = jax.nn.sigmoid(score)
        sp = jax.nn.softplus(score)
        one_m_p = jnp.exp(-w * sp)
        p = jnp.clip(1.0 - one_m_p, 1e-15, 1.0)
        g = w * sig * (1.0 - y / p)
        h = w * sig * (1.0 - sig) * (1.0 - y / p) + \
            (w * sig) ** 2 * y * one_m_p / (p * p)
        h = jnp.maximum(h, 1e-15)
        return g, h

    def boost_from_score(self, class_id=0):
        lbl = np.asarray(self._label, np.float64)
        p = max(np.average(lbl), 1e-15)
        return float(np.log(np.expm1(-np.log1p(-min(p, 1 - 1e-15))) + 1e-300))

    def convert_output(self, raw):
        return jnp.log1p(jnp.exp(raw))


# ------------------------------------------------------------------ ranking
def _rank_bucket_ladder(sizes: np.ndarray, spec) -> List[int]:
    """Query-length bucket caps, smallest to largest, covering every
    query.  ``spec`` is ``config.rank_query_buckets``: ``"auto"`` derives
    the next-power-of-two set of the observed lengths; an explicit list
    is used as-is (extended with the max length when it falls short), so
    ``[qmax]`` states the one pad-to-max bucket the parity tests compare
    the ladder with."""
    qmax = int(sizes.max()) if len(sizes) else 1
    if isinstance(spec, str):           # "auto"
        return sorted({1 << max(int(s) - 1, 0).bit_length()
                       for s in np.unique(sizes)}) or [qmax]
    caps = sorted({int(b) for b in spec})
    if caps[-1] < qmax:
        caps.append(qmax)
    return caps


def _rank_buckets(boundaries: np.ndarray, spec
                  ) -> Tuple[List[Tuple[int, np.ndarray, np.ndarray]], int]:
    """Group queries into length buckets.  Returns
    ``([(cap, query_ids[nq_b], qidx[nq_b, cap])...], pad_rows)`` where
    ``qidx`` is the padded doc-index matrix (-1 pads) of the queries
    assigned to that cap (the smallest cap >= the query's length) and
    ``pad_rows`` counts the padding slots across all buckets — the
    quantity the pad-to-max layout inflates to ``nq*qmax - ndocs``."""
    bounds = np.asarray(boundaries).astype(np.int64)
    sizes = np.diff(bounds)
    caps = _rank_bucket_ladder(sizes, spec)
    assign = np.searchsorted(np.asarray(caps), sizes, side="left")
    out: List[Tuple[int, np.ndarray, np.ndarray]] = []
    pad_rows = 0
    for bi, cap in enumerate(caps):
        qids = np.flatnonzero(assign == bi)
        if not len(qids):
            continue
        col = np.arange(cap, dtype=np.int64)[None, :]
        idx = np.where(col < sizes[qids, None], bounds[qids, None] + col,
                       -1).astype(np.int32)
        pad_rows += int(len(qids) * cap - sizes[qids].sum())
        out.append((int(cap), qids.astype(np.int32), idx))
    return out, pad_rows


def _by_slot(per_doc: np.ndarray, idx: np.ndarray, pad) -> np.ndarray:
    """A per-doc vector laid out by padded slot (``idx`` [nq_b, cap], -1
    pads): what never changes in a job is laid out once, on the host, and
    not gathered through ``qidx`` every round."""
    return np.where(idx >= 0, per_doc[np.maximum(idx, 0)], pad)


def _max_dcg_by_slot(gain_slot: np.ndarray, ks) -> np.ndarray:
    """[len(ks), nq_b] float64 ideal DCG at each cut-off of the queries
    of one bucket (``gain_slot`` [nq_b, cap], pads 0): gains sorted
    descending against the position discount (dcg_calculator.cpp
    CalMaxDCGAtK)."""
    ideal = -np.sort(-np.asarray(gain_slot, np.float64), axis=1)
    cap = ideal.shape[1]
    dcg = np.cumsum(ideal / np.log2(np.arange(cap) + 2.0), axis=1)
    return np.stack([dcg[:, min(int(k), cap) - 1] for k in ks])


def _slot_of_doc(buckets_np, num_data: int) -> np.ndarray:
    """i32 [n]: where each doc sits in the concatenation of the buckets'
    flattened ``[nq_b, cap]`` slot matrices.  Every doc has exactly one
    slot, so per-slot results come back onto the docs as ONE gather
    through this map (a permutation, not a reduction)."""
    out = np.zeros(num_data, np.int32)
    base = 0
    for _, _, idx in buckets_np:
        real = idx >= 0
        out[idx[real]] = base + np.flatnonzero(real.reshape(-1))
        base += idx.size
    return out


def _lambdarank_bucket(score, qidx, inv_dcg, gain_slot, label_slot, *,
                       sigmoid: float, trunc: int, norm: bool):
    """Pairwise |dNDCG| lambda gradients for ONE query-length bucket, by
    padded slot: ``(g, h)`` [nq_b, Q], zeros in the pads.  Pure and
    shape-static in ``qidx`` ([nq_b, Q] padded with -1): the whole pair
    tensor is [nq_b, T, Q] with T = min(trunc, Q), so a bucket of short
    queries never pays the longest query's Q.  ``gain_slot`` (pads 0)
    and ``label_slot`` (pads -1) are the job's constants by slot; only
    the score is gathered."""
    s = sigmoid
    with jax.named_scope("rank_gather"):
        sc = jnp.where(qidx >= 0, score[jnp.maximum(qidx, 0)], -jnp.inf)

    # docs by descending score, ties by index like the reference's stable
    # sort; the constants ride the sort as operands, so nothing is
    # gathered through the order afterwards.  Pads score -inf and sort
    # last, their label -1 marks them in sorted space.
    with jax.named_scope("rank_sort"):
        slot = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        neg, g_srt, l_srt, order = jax.lax.sort(
            (-sc, gain_slot, label_slot, slot), dimension=1, num_keys=1,
            is_stable=True)
        s_srt = -neg                                   # [nq_b, Q] desc
        v_srt = l_srt >= 0

    # -- truncation-aware pair enumeration in SORTED space.  The
    # reference (rank_objective.hpp:138-292) iterates i over sorted
    # positions [0, trunc) and j over (i, cnt): every pair has its
    # higher-scored member inside the truncation level, so the pair set
    # is O(Q * trunc), not O(Q^2).  Materializing [nq, T, Q] instead of
    # [nq, Q, Q] is what makes MS-LTR-scale query lengths (thousands of
    # docs) fit in memory (VERDICT r1 #7).
    with jax.named_scope("rank_pairs"):
        Q = sc.shape[1]
        T = int(min(trunc, Q))
        disc = 1.0 / jnp.log2(jnp.arange(Q, dtype=jnp.float32) + 2.0)  # [Q]
        inv = inv_dcg[:, None, None]                         # [nq_b, 1, 1]

        sa = s_srt[:, :T, None]                              # [nq_b, T, 1]
        sb = s_srt[:, None, :]                               # [nq_b, 1, Q]
        ga_ = g_srt[:, :T, None]
        gb_ = g_srt[:, None, :]
        la_ = l_srt[:, :T, None]
        lb_ = l_srt[:, None, :]
        delta = jnp.abs((ga_ - gb_)
                        * (disc[None, :T, None] - disc[None, None, :])) \
            * inv                                            # [nq_b, T, Q]
        # each unordered pair once: position b strictly below position a
        tri = (jnp.arange(Q)[None, None, :]
               > jnp.arange(T)[None, :, None])
        pair_ok = (la_ != lb_) & tri & v_srt[:, :T, None] & v_srt[:, None, :]

        a_better = la_ > lb_
        diff_hl = jnp.where(a_better, sa - sb, sb - sa)      # s_high - s_low
        diff_hl = jnp.clip(diff_hl, -50.0 / s, 50.0 / s)
        rho = 1.0 / (1.0 + jnp.exp(s * diff_hl))
        lam = -s * rho * delta                    # dL/ds for the better doc
        hes = s * s * rho * (1.0 - rho) * delta
        lam = jnp.where(pair_ok, lam, 0.0)
        hes = jnp.where(pair_ok, hes, 0.0)

        # accumulate onto sorted positions: a gets +/-lam per label order,
        # b the negation; hessians add on both ends
        g_a = jnp.where(a_better, lam, -lam)
        below = ((0, 0), (0, Q - T))        # positions past the truncation
        g_pos = jnp.pad(jnp.sum(g_a, axis=2), below) - jnp.sum(g_a, axis=1)
        h_pos = jnp.pad(jnp.sum(hes, axis=2), below) + jnp.sum(hes, axis=1)

        if norm:
            # reference norm_: scale by log2(1 + |sum lambda|) / |sum lambda|
            sum_lam = jnp.sum(jnp.abs(lam), axis=(1, 2))
            nf = jnp.where(sum_lam > 0,
                           jnp.log2(1.0 + sum_lam)
                           / jnp.maximum(sum_lam, 1e-20), 1.0)
            g_pos = g_pos * nf[:, None]
            h_pos = h_pos * nf[:, None]

    # sorted positions back to padded slots: the order is a permutation
    # of the slots, so sorting by it undoes it
    with jax.named_scope("rank_sort"):
        _, g_slot, h_slot = jax.lax.sort((order, g_pos, h_pos), dimension=1,
                                         num_keys=1)
    return g_slot, h_slot


def _lambdarank_gradients(score, state, *, sigmoid: float, trunc: int,
                          norm: bool):
    """Every bucket's pairwise gradients, then ONE gather through the
    static ``slot_of_doc`` back onto the docs.  ``state`` is
    ``LambdarankNDCG._rank_state``: the per-bucket
    ``(qidx, inv_dcg, gain_slot, label_slot)`` and ``slot_of_doc``."""
    buckets, slot_of_doc = state
    parts = [_lambdarank_bucket(score, *bucket, sigmoid=sigmoid,
                                trunc=trunc, norm=norm)
             for bucket in buckets]
    with jax.named_scope("rank_accumulate"):
        g_flat, h_flat = (jnp.concatenate([p[i].reshape(-1) for p in parts])
                          for i in (0, 1))
        return g_flat[slot_of_doc], h_flat[slot_of_doc]


def _xendcg_accum(score, label, gumbel, qidx, g_acc, h_acc):
    """XE-NDCG listwise gradients for ONE query-length bucket, scattered
    onto the per-doc accumulators.  ``gumbel`` is the PER-DOC noise
    vector ([n], drawn once per iteration) gathered through ``qidx`` —
    drawing per doc instead of per padded slot makes the perturbed
    targets identical across bucket geometries (bucketed == pad-to-max
    up to reduction order)."""
    valid = qidx >= 0
    safe = jnp.maximum(qidx, 0)
    sc = jnp.where(valid, score[safe], -1e30)
    lbl = jnp.where(valid, label[safe], 0.0)
    # Gumbel-perturbed relevance targets (XE-NDCG-MART, Bruch et al.):
    # phi = max(2^y - 1 + Gumbel(0,1), 0), renormalized per query
    gum = jnp.where(valid, gumbel[safe], 0.0)
    phi = jnp.maximum(jnp.power(2.0, lbl) - 1.0 + gum, 0.0)
    phi = jnp.where(valid, phi, 0.0)
    phi_sum = jnp.sum(phi, axis=1, keepdims=True)
    target = phi / jnp.maximum(phi_sum, 1e-20)
    p = jax.nn.softmax(sc, axis=1)
    p = jnp.where(valid, p, 0.0)
    g_doc = p - target
    h_doc = p * (1.0 - p)
    g_acc = g_acc.at[safe.reshape(-1)].add(
        jnp.where(valid, g_doc, 0.0).reshape(-1))
    h_acc = h_acc.at[safe.reshape(-1)].add(
        jnp.where(valid, jnp.maximum(h_doc, 1e-15), 0.0).reshape(-1))
    return g_acc, h_acc


def _pos_bias_newton(g, h, biases, positions, counts, *, lr: float,
                     reg: float):
    """Functional Newton step on per-position bias factors
    (rank_objective.hpp:295 UpdatePositionBiasFactors): utility
    derivative w.r.t. a position's bias is -sum(lambda) there,
    L2-regularized per instance.  Pure — returns the NEW bias vector so
    the update can live inside the same compiled program as the
    gradients (the carried-array formulation that makes position-debiased
    lambdarank fully device-resident)."""
    first = jnp.zeros_like(biases).at[positions].add(-g)
    second = jnp.zeros_like(biases).at[positions].add(-h)
    first = first - biases * reg * counts
    second = second - reg * counts
    return biases + lr * first / (jnp.abs(second) + 0.001)


class LambdarankNDCG(ObjectiveFunction):
    """reference rank_objective.hpp:138 LambdarankNDCG — pairwise lambda
    gradients weighted by |dNDCG|, truncation at
    ``lambdarank_truncation_level``, optional per-query normalization.

    Queries are grouped into power-of-two LENGTH BUCKETS
    (``rank_query_buckets``, the serving BucketLadder idiom applied to
    training): each bucket runs one batched pairwise kernel at its own
    [nq_b, T, Q_b] geometry, so padded-pair compute is
    sum_b nq_b*T*Q_b instead of the pad-to-max nq*T*qmax — a ~Q_max/Q̄
    win on skewed (MS-LTR-like) query-length distributions.  Bucket
    programs are keyed through ops/compile_cache.py with NO anchors and
    every data array a traced argument (``rank_compile_hits/misses``):
    identical geometry across boosters and iterations re-enters the same
    XLA executable, zero new lowerings.  Position debiasing threads its
    bias factors as explicit carried DEVICE arrays (functional Newton
    update inside the same program); the host ``_pos_biases`` copy is
    kept in sync only for checkpointing and inspection."""
    NAME = "lambdarank"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Lambdarank tasks require query information")
        with phase("rank_bucket_plan", global_timer):
            self._init_rank_buckets(metadata.query_boundaries)
            lbl = np.asarray(metadata.label)
            gains = self.config.label_gain or [
                float((1 << i) - 1)
                for i in range(max(int(lbl.max()) + 1, 31))]
            self._label_gain = np.asarray(gains, np.float64)
            if int(lbl.max()) >= len(self._label_gain):
                log.fatal("label_gain shorter than max label")
            gain_of_doc = self._label_gain[lbl.astype(int)]
            trunc = int(self.config.lambdarank_truncation_level)
            # per-bucket device arrays.  ``_buckets``: (cap, qidx
            # [nq_b, cap], inv_dcg [nq_b]), the inverse max DCG at the
            # truncation level (rank_objective.hpp:165-177), 0 for a
            # query without a relevant doc.  ``_rank_state``: what the
            # gradient program reads, gains and labels laid out by slot
            # once, here, and the docs' slots
            self._buckets, state = [], []
            for cap, _, idx in self._buckets_np:
                gain_slot = _by_slot(gain_of_doc, idx, 0.0)
                max_dcg = _max_dcg_by_slot(gain_slot, [trunc])[0]
                inv = np.where(max_dcg > 0, 1.0 / np.maximum(max_dcg, 1e-300),
                               0.0)
                qidx, inv = jnp.asarray(idx), jnp.asarray(inv, jnp.float32)
                self._buckets.append((cap, qidx, inv))
                state.append((qidx, inv, jnp.asarray(gain_slot, jnp.float32),
                              jnp.asarray(_by_slot(lbl, idx, -1.0),
                                          jnp.float32)))
            self._rank_state = (tuple(state), jnp.asarray(
                _slot_of_doc(self._buckets_np, num_data)))
        # position-debiased LTR (rank_objective.hpp:43-56,295: per-position
        # additive bias factors on the score, Newton-updated each iteration
        # with L2 regularization lambdarank_position_bias_regularization)
        self.jit_safe = True       # re-init may change the position state
        self._positions = None
        if metadata.position is not None:
            pos = np.asarray(metadata.position)
            ids, inv_idx = np.unique(pos, return_inverse=True)
            self._positions = inv_idx.astype(np.int32)
            self._positions_dev = jnp.asarray(self._positions)
            # the device f32 carry is the source of truth; the host f64
            # mirror below exists for checkpointing/inspection only
            self._pos_biases_dev = jnp.zeros(len(ids), jnp.float32)
            self._pos_biases = np.zeros(len(ids), np.float64)
            self._pos_counts_dev = jnp.asarray(
                np.bincount(inv_idx, minlength=len(ids)).astype(np.float32))
            self._pos_reg = float(
                self.config.lambdarank_position_bias_regularization)
            # the per-iteration bias carry keeps this objective off the
            # FUSED round scan (a scan-traced get_gradients would freeze
            # the carry as a constant); jitted_gradients below still runs
            # the whole update as one cached device program
            self.jit_safe = False

    def attach_booster_metrics(self, registry) -> None:
        """The base class's, and the ranking plan's sizes counted once a
        job, in the booster's registry and the process's."""
        super().attach_booster_metrics(registry)
        counts = self._rank_counts
        count_event("rank_queries", counts["rank_queries"], registry)
        count_event("rank_docs", counts["rank_docs"], registry)
        count_event("rank_slot_rows", counts["rank_slot_rows"], registry)
        count_event("rank_pair_slots", counts["rank_pair_slots"], registry)

    def _init_rank_buckets(self, boundaries) -> None:
        """Build the query-length bucket plan, its telemetry gauges and
        the job's ranking counters (shared with RankXENDCG)."""
        bounds = np.asarray(boundaries)
        sizes = np.diff(bounds)
        self._qmax = int(sizes.max()) if len(sizes) else 1
        spec = getattr(self.config, "rank_query_buckets", "auto")
        self._buckets_np, self._rank_pad_rows = _rank_buckets(bounds, spec)
        self._rank_bucket_count = len(self._buckets_np)
        if self._qmax > 2048 and self._rank_bucket_count == 1 \
                and len(sizes) > 1:
            log.warning(
                f"Longest query has {self._qmax} docs and "
                f"rank_query_buckets={spec!r} puts every query into one "
                f"bucket: the pad-to-max pairwise lambda computation is "
                f"O(max_query_len * truncation) per query whatever its "
                f"length — leave rank_query_buckets at \"auto\" for the "
                f"bucketed kernels, or lower lambdarank_truncation_level "
                f"/ split queries")
        trunc = int(getattr(self.config, "lambdarank_truncation_level", 30))
        slot_rows = sum(idx.size for _, _, idx in self._buckets_np)
        self._rank_counts = {
            "rank_queries": len(sizes), "rank_docs": int(sizes.sum()),
            "rank_slot_rows": slot_rows,
            "rank_pair_slots": sum(idx.size * min(trunc, cap)
                                   for cap, _, idx in self._buckets_np)}
        global_metrics.set_gauge("rank_pad_rows", self._rank_pad_rows)
        global_metrics.set_gauge("rank_bucket_count",
                                 self._rank_bucket_count)
        if self._metrics is not None:
            self._metrics.set_gauge("rank_pad_rows", self._rank_pad_rows)
            self._metrics.set_gauge("rank_bucket_count",
                                    self._rank_bucket_count)

    def _bucket_geoms(self) -> tuple:
        return tuple((int(qidx.shape[0]), cap)
                     for cap, qidx, _ in self._buckets)

    def fused_operands(self):
        return self._rank_state

    def get_gradients(self, score, rank_state=None):
        """Pure traceable composition over the bucket plan — the function
        the fused round scan traces inline (plain lambdarank; the scan
        hands ``rank_state`` in as an operand of the round program, so
        the slot matrices are arguments and not literals of its HLO) and
        tests call eagerly.  Training dispatch goes through
        jitted_gradients, which runs this same arithmetic as one cached
        program."""
        if self._positions is not None:
            score = score + self._pos_biases_dev[self._positions_dev]
        g, h = _lambdarank_gradients(
            score, self._rank_state if rank_state is None else rank_state,
            sigmoid=float(self.config.sigmoid),
            trunc=int(self.config.lambdarank_truncation_level),
            norm=bool(self.config.lambdarank_norm))
        g, h = self._apply_weight(g, h)
        if self._positions is not None and \
                not isinstance(score, jax.core.Tracer):
            self._pos_biases_dev = _pos_bias_newton(
                g, h, self._pos_biases_dev, self._positions_dev,
                self._pos_counts_dev,
                lr=float(self.config.learning_rate), reg=self._pos_reg)
            self._pos_biases = np.asarray(self._pos_biases_dev, np.float64)
        return g, h

    def jitted_gradients(self, score):
        """One compile-cached program per bucket-geometry signature:
        score adjust (position bias), every bucket's pairwise kernel,
        weighting and the functional Newton bias update all lower as a
        SINGLE XLA executable, keyed only by geometry + hyperparameters
        (no anchors; the slot state, weights and biases are traced
        arguments), so a second booster over identical geometry is a pure
        ``rank_compile_hits`` path — zero new lowerings."""
        pos = self._positions is not None
        has_w = self._weight is not None
        statics = (int(self.num_data), self._bucket_geoms(),
                   float(self.config.sigmoid),
                   int(self.config.lambdarank_truncation_level),
                   bool(self.config.lambdarank_norm), has_w,
                   int(self._pos_biases_dev.shape[0]) if pos else 0,
                   float(self.config.learning_rate) if pos else 0.0,
                   float(self._pos_reg) if pos else 0.0)
        sigmoid, trunc, norm = statics[2], statics[3], statics[4]
        lr, reg = statics[7], statics[8]

        def builder():
            def run(score, weight, bias, positions, counts, state):
                sc = score + bias[positions] if pos else score
                g, h = _lambdarank_gradients(sc, state, sigmoid=sigmoid,
                                             trunc=trunc, norm=norm)
                if has_w:
                    g, h = g * weight, h * weight
                if pos:
                    nb = _pos_bias_newton(g, h, bias, positions, counts,
                                          lr=lr, reg=reg)
                    return g, h, nb
                return g, h
            return jax.jit(run)

        fn = cc.get_or_build(("rank_grad", statics), builder, anchors=(),
                             metrics=self._metrics, counter_ns="rank")
        empty_f = jnp.zeros((0,), jnp.float32)
        empty_i = jnp.zeros((0,), jnp.int32)
        out = fn(score, self._weight if has_w else empty_f,
                 self._pos_biases_dev if pos else empty_f,
                 self._positions_dev if pos else empty_i,
                 self._pos_counts_dev if pos else empty_f,
                 self._rank_state)
        if pos:
            g, h, nb = out
            self._pos_biases_dev = nb
            self._pos_biases = np.asarray(nb, np.float64)
            return g, h
        return out


class RankXENDCG(LambdarankNDCG):
    """reference rank_objective.hpp:378 RankXENDCG (XE-NDCG-MART, Bruch et
    al.) — listwise cross-entropy with Gumbel-perturbed relevance targets,
    over the same query-length bucket plan as lambdarank (one listwise
    program per bucket geometry; the Gumbel noise is drawn PER DOC so the
    targets do not depend on the bucket ladder)."""
    NAME = "rank_xendcg"
    # each call splits self._rng — per-call mutable HOST state; the split
    # stays on host (and off the fused scan) while the drawn key rides
    # into the cached device program as a traced argument
    jit_safe = False
    fused_operands = ObjectiveFunction.fused_operands

    def init(self, metadata, num_data):
        ObjectiveFunction.init(self, metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Ranking tasks require query information")
        with phase("rank_bucket_plan", global_timer):
            self._init_rank_buckets(metadata.query_boundaries)
        self._buckets = [(cap, jnp.asarray(idx), None)
                         for cap, qids, idx in self._buckets_np]
        self._positions = None
        self._rng = jax.random.PRNGKey(self.config.objective_seed)
        self._iter = 0

    def get_gradients(self, score):
        self._rng, key = jax.random.split(self._rng)
        gumbel = jax.random.gumbel(key, score.shape)
        g = jnp.zeros_like(score)
        h = jnp.zeros_like(score)
        for cap, qidx, _ in self._buckets:
            g, h = _xendcg_accum(score, self._label, gumbel, qidx, g, h)
        return self._apply_weight(g, h)

    def jitted_gradients(self, score):
        has_w = self._weight is not None
        statics = (int(self.num_data), self._bucket_geoms(), has_w)
        self._rng, key = jax.random.split(self._rng)

        def builder():
            def run(score, label, weight, rkey, buckets):
                gumbel = jax.random.gumbel(rkey, score.shape)
                g = jnp.zeros_like(score)
                h = jnp.zeros_like(score)
                for qidx in buckets:
                    g, h = _xendcg_accum(score, label, gumbel, qidx, g, h)
                if has_w:
                    g, h = g * weight, h * weight
                return g, h
            return jax.jit(run)

        fn = cc.get_or_build(("rank_xendcg", statics), builder, anchors=(),
                             metrics=self._metrics, counter_ns="rank")
        empty_f = jnp.zeros((0,), jnp.float32)
        return fn(score, self._label,
                  self._weight if has_w else empty_f, key,
                  tuple(qidx for _, qidx, _ in self._buckets))


# ------------------------------------------------------------------ factory
_OBJECTIVES = {
    "regression": RegressionL2Loss,
    "regression_l1": RegressionL1Loss,
    "huber": RegressionHuberLoss,
    "fair": RegressionFairLoss,
    "poisson": RegressionPoissonLoss,
    "quantile": RegressionQuantileLoss,
    "mape": RegressionMAPELoss,
    "gamma": RegressionGammaLoss,
    "tweedie": RegressionTweedieLoss,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
    "rank_xendcg": RankXENDCG,
}


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """Factory (reference objective_function.cpp
    ObjectiveFunction::CreateObjectiveFunction)."""
    name = config.objective
    if name == "none":
        return None
    cls = _OBJECTIVES.get(name)
    if cls is None:
        log.fatal(f"Unknown objective type name: {name}")
    return cls(config)
