"""Training entry points: ``train()`` and ``cv()``.

TPU-native re-design of the reference training engine (reference:
python-package/lightgbm/engine.py — ``train`` :109, ``cv``/``CVBooster``
:611,354).  The control flow mirrors the reference: construct datasets, build
the booster, run callbacks before/after each iteration, aggregate eval
results, honor EarlyStopException, set ``best_iteration``/``best_score``.
"""

from __future__ import annotations

import copy
import time
import types
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from .basic import Booster, Dataset
from .callback import (CallbackEnv, EarlyStopException, log_telemetry,
                       record_evaluation)
from .config import normalize_params
from .obs import events as obs_events, observe_training
from .robustness.guards import NumericHalt
from .utils import log
from .utils.paths import check_output_path
from .utils.timer import global_timer, phase


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[Sequence[Dataset]] = None,
          valid_names: Optional[Sequence[str]] = None,
          feval: Optional[Callable] = None,
          init_model: Optional[Union[str, Booster]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None,
          fobj: Optional[Callable] = None,
          resume: Optional[str] = None,
          final_checkpoint: bool = False) -> Booster:
    """Train a booster (reference engine.py:109).

    ``resume="auto"`` (requires ``checkpoint_dir`` in ``params``) loads
    the newest VALID checkpoint, rebuilds the booster through the
    ``init_model`` continuation path with the checkpointed score caches,
    RNG states and eval history restored exactly, and trains the
    REMAINING rounds — ``num_boost_round`` is the TOTAL target, so an
    interrupted-and-resumed run finishes with the same round count (and,
    for deterministic configs, the same trees) as an uninterrupted one.
    With no valid checkpoint, training starts from scratch.

    ``final_checkpoint=True`` (requires ``checkpoint_dir``) guarantees a
    checkpoint at the LAST trained round even when
    ``checkpoint_interval`` does not land on it — the contract the
    continuous-learning pipeline (pipeline/) needs so every
    train→publish cycle ends on a durable, resumable boundary.
    """
    params = normalize_params(params)
    if "num_iterations" in params:
        num_boost_round = params["num_iterations"]
    params["num_iterations"] = num_boost_round
    if fobj is not None:
        params["objective"] = "none"

    # observability session (obs/): trace_output starts the span recorder
    # (exported on exit), profile_dir brackets the run with
    # jax.profiler.trace; both no-ops when unset.  The session and the
    # "train" span, the root every other span nests under, open before
    # the booster exists, so that its construction (``booster_init``) is
    # part of the job on the recorder's and the profiler's timeline.
    session = types.SimpleNamespace(
        **{key: params.get(key, "")
           for key in ("trace_output", "profile_dir", "event_output")})
    with observe_training(session), phase("train", global_timer) as job:
        return _train_job(job, params, train_set, num_boost_round,
                          valid_sets, valid_names, feval, init_model,
                          callbacks, fobj, resume, final_checkpoint)


def _train_job(job, params, train_set, num_boost_round, valid_sets,
               valid_names, feval, init_model, callbacks, fobj, resume,
               final_checkpoint) -> Booster:
    """``train()`` inside its session and root span ``job``: build the
    booster and everything around it (span ``booster_init``), then run
    the rounds."""
    with phase("booster_init"):
        setup = _prepare_job(params, train_set, num_boost_round,
                             valid_sets, valid_names, init_model,
                             callbacks, resume)
    (booster, valid_pairs, train_in_valid, callbacks, cbs_before, cbs_after,
     mgr, tower, resume_state, rounds_to_run, start_round) = setup
    if resume_state is not None and rounds_to_run <= 0:
        return booster              # the checkpoint is already there
    # the booster's own table times the job from here (its timer did
    # not exist when the root span opened)
    job.also(booster._gbdt.timer)
    if resume_state is not None:
        # the journal activates with the session, so the restore (which
        # ran in ``booster_init``) is journaled here; an elastic
        # session's outer journal receives it either way
        obs_events.emit_event(
            "checkpoint_resume", round_idx=start_round,
            total_rounds=int(num_boost_round))
    try:
        out = _run_training(booster, params, train_set, rounds_to_run,
                            valid_pairs, train_in_valid, feval, fobj,
                            callbacks, cbs_before, cbs_after,
                            start_round=start_round)
        if final_checkpoint and mgr is not None:
            mgr.save_final(out)
        return out
    finally:
        if tower is not None:
            # flush the final partial rollup window and run the SLO
            # evaluator over it while the journal is still active
            tower.close()


def _prepare_job(params, train_set, num_boost_round, valid_sets,
                 valid_names, init_model, callbacks, resume):
    """Everything of ``train()`` before the first round: checkpoint
    lookup, the booster, its valid sets, the callbacks and their order,
    the exact-state restore of a resumed run."""
    ckpt_dir = str(params.get("checkpoint_dir", "") or "")
    resume_state = None
    if resume is not None:
        if str(resume) != "auto":
            log.fatal(f"resume={resume!r} is not supported (only 'auto')")
        if not ckpt_dir:
            log.fatal("resume='auto' requires checkpoint_dir= in params")
        from .robustness.checkpoint import load_latest_checkpoint
        resume_state = load_latest_checkpoint(ckpt_dir)
        if resume_state is None:
            log.info(f"resume='auto': no valid checkpoint under "
                     f"{ckpt_dir!r}; training from scratch")
        else:
            if init_model is not None:
                log.warning("resume='auto' found a checkpoint; the given "
                            "init_model is ignored in favor of it")
            init_model = Booster(model_str=resume_state.model_text)

    if init_model is not None:
        # continuation (reference engine.py:233-244): the init model's raw
        # predictions become the train/valid datasets' init_score, and its
        # trees are merged into the new booster (basic.py Booster.__init__)
        predictor = init_model if isinstance(init_model, Booster) \
            else Booster(model_file=str(init_model))
        if resume_state is not None:
            # checkpoint resume restores the exact f32 score caches below,
            # so the init-score predict pass is skipped — this also works
            # on a constructed Dataset whose raw data was freed (CLI)
            train_set._set_resume_predictor(predictor)
        else:
            train_set._apply_predictor(predictor)
    booster = Booster(params=params, train_set=train_set)

    valid_sets = list(valid_sets or [])
    names = list(valid_names or [])
    train_in_valid = False
    valid_pairs = []  # (name, Dataset) for non-train valid sets, in order
    for i, vs in enumerate(valid_sets):
        name = names[i] if i < len(names) else f"valid_{i}"
        if vs is train_set:
            train_in_valid = True
            continue
        booster.add_valid(vs, name)
        valid_pairs.append((name, vs))

    callbacks = list(callbacks or [])
    cfg = booster._gbdt.config
    if str(cfg.telemetry_output or ""):
        # telemetry_output=<path>: one JSONL record per iteration
        # (counters, phase deltas, host/device memory) — the config-key
        # spelling of the log_telemetry callback.  Writability is probed
        # up front (shared utils/paths contract) so a path typo surfaces
        # before round 1, not as a mid-training crash.
        if check_output_path(str(cfg.telemetry_output),
                             key="telemetry_output"):
            # resume="auto" threads the ABSOLUTE restart round into the
            # callback so stale records from the interrupted
            # predecessor (rounds past the checkpoint) are pruned
            # instead of left to overlap the re-trained indices; a
            # from-scratch resume (no valid checkpoint) prunes from 0
            resume_from = None
            if resume is not None:
                resume_from = resume_state.iteration \
                    if resume_state is not None else 0
            callbacks.append(log_telemetry(str(cfg.telemetry_output),
                                           resume_from=resume_from))
    mgr = None
    if ckpt_dir:
        # periodic atomic checkpoints (robustness/checkpoint.py).  Same
        # failure contract as the other output keys: an unwritable dir
        # degrades to a warning before round 1.  The callback is not
        # fused-safe, so checkpointed runs keep the classic loop (a
        # mid-chunk snapshot would pair end-of-chunk scores with
        # mid-chunk trees).
        if check_output_path(ckpt_dir, key="checkpoint_dir", kind="dir"):
            from .robustness.checkpoint import CheckpointManager
            mgr = CheckpointManager(
                ckpt_dir, interval=int(cfg.checkpoint_interval),
                keep=int(cfg.checkpoint_keep),
                history=resume_state.history if resume_state else None,
                # a from-scratch run owns the directory: stale checkpoints
                # from a previous run are cleared (with a warning) so
                # retention and a later resume='auto' see only THIS run
                fresh=resume_state is None)
            callbacks.append(mgr.callback())
    tower = _build_watchtower(cfg, booster)
    if tower is not None:
        callbacks.append(_watchtower_callback(tower, booster))
    callbacks = sorted(callbacks, key=lambda cb: getattr(cb, "order", 0))
    if mgr is not None:
        # the manager snapshots peer-callback state (early-stopping
        # patience) into each checkpoint
        mgr.peer_callbacks = callbacks
    cbs_before = [cb for cb in callbacks if getattr(cb, "before_iteration",
                                                    False)]
    cbs_after = [cb for cb in callbacks if not getattr(cb, "before_iteration",
                                                       False)]

    rounds_to_run = num_boost_round
    start_round = 0
    if resume_state is not None:
        # exact-state restore (score caches / RNG / eval history) on top
        # of the init_model continuation; num_boost_round is the TOTAL
        # target, so only the remaining rounds run.  Callbacks see
        # ABSOLUTE iteration indices (begin_iteration = the resume
        # point), so early stopping / NumericHalt record a
        # best_iteration that counts every tree in the model, not just
        # the resumed segment's.
        resume_state.restore_into(booster, callbacks)
        rounds_to_run = num_boost_round - resume_state.iteration
        start_round = resume_state.iteration
        if rounds_to_run <= 0:
            log.info(f"checkpoint is already at iteration "
                     f"{resume_state.iteration} >= num_boost_round="
                     f"{num_boost_round}; nothing to train")

    return (booster, valid_pairs, train_in_valid, callbacks, cbs_before,
            cbs_after, mgr, tower, resume_state, rounds_to_run, start_round)


def _build_watchtower(cfg, booster):
    """Build the training-side watchtower (obs/timeseries.py rollup ring
    + obs/slo.py burn-rate evaluator + obs/anomaly.py detector) when
    ``slo_config``/``anomaly_detection`` enables it; ``None`` — and zero
    per-round work — otherwise.  Attached to the booster as
    ``gb.watchtower`` so ``Booster.prometheus_text()`` can export rollup
    gauges and SLO state."""
    from .obs.slo import parse_slo_config
    try:
        enabled = parse_slo_config(cfg.slo_config)
    except ValueError:
        enabled = {}   # check_param_conflict already rejected bad specs
    anomaly_on = str(cfg.anomaly_detection or "off").strip().lower() == "on"
    if not enabled and not anomaly_on:
        return None
    from .obs.metrics import count_event
    from .obs.slo import SloEvaluator, Watchtower
    from .obs.timeseries import Rollup, default_rollup_path
    gb = booster._gbdt
    hook = lambda n, v=1: count_event(n, v, gb.metrics)
    tele = str(cfg.telemetry_output or "")
    rollup = Rollup(window_s=float(cfg.rollup_window_s),
                    out_path=default_rollup_path(tele) if tele else None,
                    count=hook)
    evaluator = None
    if enabled:
        evaluator = SloEvaluator(enabled, emit=obs_events.emit_event,
                                 count=hook)
        # training-domain SLOs only; the serving pair is fed (and
        # watched) by PredictionServer
        evaluator.watch_slo("nan_guard_trip_rate")
        evaluator.watch_slo("compile_miss_storm")
        evaluator.watch_slo("heartbeat_staleness_s")
    anomaly = None
    if anomaly_on:
        from .obs.anomaly import AnomalyDetector
        anomaly = AnomalyDetector(emit=obs_events.emit_event, count=hook)
    tower = Watchtower(rollup, slo=evaluator, anomaly=anomaly)
    gb.watchtower = tower
    return tower


def _watchtower_callback(tower, booster):
    """Per-round watchtower feed: round wall-time sample, cumulative
    telemetry counters/gauges, eval metrics — then the anomaly checks
    and the SLO evaluator over any windows that just closed.  Runs after
    the eval callbacks (order 55) and is fused-safe: it only READS the
    device-computed eval list, so watched runs keep the fused fast
    path."""
    from .obs import memory as obs_memory
    gb = booster._gbdt
    state = {"t_prev": time.perf_counter()}

    def _callback(env: CallbackEnv) -> None:
        now = time.perf_counter()
        round_s = now - state["t_prev"]
        state["t_prev"] = now
        rollup = tower.rollup
        rollup.observe_sample("round_s", round_s)
        rollup.observe_gauge("iteration", float(env.iteration))
        snap = gb.metrics.snapshot()
        for name, val in snap["counters"].items():
            rollup.observe_counter(name, val)
        for name, val in snap["gauges"].items():
            rollup.observe_gauge(name, val)
        evals = {}
        for item in env.evaluation_result_list or []:
            key = f"{item[0]}.{item[1]}"
            evals[key] = (float(item[2]), bool(item[3]))
            rollup.observe_gauge("eval." + key, float(item[2]))
        if tower.anomaly is not None:
            counters = snap["counters"]
            misses = counters.get("round_compile_misses", 0) \
                + counters.get("fused_runner_cache_misses", 0)
            tower.anomaly.observe_round(
                env.iteration, round_s=round_s, evals=evals or None,
                compile_misses=float(misses),
                host_rss_mb=obs_memory.host_rss_mb())
        tower.evaluate()

    _callback.order = 55
    _callback.fused_safe = True
    return _callback


def _run_training(booster, params, train_set, num_boost_round, valid_pairs,
                  train_in_valid, feval, fobj, callbacks, cbs_before,
                  cbs_after, start_round: int = 0) -> Booster:
    """The boosting loop of ``train()`` (split out so the observability
    session brackets every exit path).  ``start_round`` > 0 (checkpoint
    resume) makes callback iteration indices ABSOLUTE: the loop runs
    ``[start_round, start_round + num_boost_round)`` with
    ``begin_iteration = start_round``, so best_iteration bookkeeping and
    checkpoint cadence line up with the uninterrupted run's."""
    # fused-rounds fast path: when every per-iteration observer can be
    # driven from device-evaluated metrics — no callbacks at all, or only
    # fused-safe ones (early_stopping / log_evaluation /
    # record_evaluation / log_telemetry, which READ the eval list) with
    # device-evaluable valid metrics — the whole boosting run executes as
    # chunked on-device scans (GBDT.train_fused): one dispatch per ~32
    # rounds instead of one host/device round trip per round.
    # Valid-set scoring, metric eval and the
    # early-stop flag ride the scan; the REAL callbacks run on the host
    # once per round with the device-computed values, so their semantics
    # are exactly the classic loop's.
    cbs_fused_safe = all(getattr(cb, "fused_safe", False)
                         for cb in callbacks) and not cbs_before
    if (cbs_fused_safe and not train_in_valid and start_round == 0
            and feval is None and fobj is None and num_boost_round > 0
            and not booster._gbdt.config.is_provide_training_metric
            and (not valid_pairs or callbacks)
            and booster._gbdt.supports_fused()):
        es_params = next((cb.es_params for cb in callbacks
                          if getattr(cb, "es_params", None)), None)

        def cb_driver(it, evals):
            for cb in cbs_after:
                cb(CallbackEnv(booster, params, it, 0, num_boost_round,
                               evals))
        try:
            with phase("train_fused", booster._gbdt.timer, global_timer):
                finished = booster._gbdt.train_fused(
                    num_boost_round,
                    cb_driver=cb_driver if callbacks else None,
                    es_params=es_params)
        except EarlyStopException as e:
            booster.best_iteration = e.best_iteration + 1
            _set_best_score(booster, e.best_score)
            return booster
        if finished:
            log.warning("Stopped training because there are no more "
                        "leaves that meet the split requirements")
        if booster.best_iteration <= 0:
            _set_best_score(booster,
                            booster._gbdt._last_fused_evals or [])
        return booster

    evals: List = []
    end_round = start_round + num_boost_round
    for it in range(start_round, end_round):
        with phase("iteration", iter=it):
            for cb in cbs_before:
                cb(CallbackEnv(booster, params, it, start_round, end_round,
                               None))
            try:
                finished = booster.update(fobj=fobj)
            except NumericHalt:
                # nan_policy=halt_and_keep_best: keep every completed
                # round; guards.py already warned with the round number
                booster.best_iteration = it
                _set_best_score(booster, evals)
                break
            evals = []
            with phase("metric_eval", booster._gbdt.timer, global_timer):
                if train_in_valid or \
                        booster._gbdt.config.is_provide_training_metric:
                    evals.extend(booster.eval_train())
                evals.extend(booster.eval_valid())
            if feval is not None:
                evals.extend(_eval_custom(feval, booster, train_set,
                                          valid_pairs, train_in_valid))
            try:
                for cb in cbs_after:
                    cb(CallbackEnv(booster, params, it, start_round,
                                   end_round, evals))
            except EarlyStopException as e:
                booster.best_iteration = e.best_iteration + 1
                _set_best_score(booster, e.best_score)
                break
            if finished:
                log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                break
    if booster.best_iteration <= 0:
        # best_iteration stays UNSET without early stopping (reference
        # basic.py contract: predict()/save_model() then use ALL trees).
        # Setting it to the final round here looks harmless but silently
        # truncates predictions after CONTINUED training on the returned
        # booster — new trees beyond the recorded round were ignored
        # (caught in round 4: a 525-tree flagship predicting with 25).
        _set_best_score(booster, evals)
    return booster


def _eval_custom(feval, booster, train_set, valid_pairs, train_in_valid):
    out = []
    fevals = feval if isinstance(feval, (list, tuple)) else [feval]
    gb = booster._gbdt
    for f in fevals:
        if train_in_valid:
            res = f(gb._host_scores(gb.scores), train_set)
            out.append(("training",) + tuple(res))
        for vi, (name, vs) in enumerate(valid_pairs):
            res = f(gb._host_scores(gb.valid_scores[vi]), vs)
            out.append((name,) + tuple(res))
    return out


def _set_best_score(booster: Booster, evals) -> None:
    booster.best_score = {}
    for item in evals or []:
        name, metric, val = item[0], item[1], item[2]
        booster.best_score.setdefault(name, {})[metric] = val


class CVBooster:
    """Ensemble of per-fold boosters (reference engine.py:354)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name: str):
        def handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, feval=None, seed: int = 0,
       callbacks: Optional[List[Callable]] = None,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """K-fold cross-validation (reference engine.py:611)."""
    params = normalize_params(params)
    if params.get("checkpoint_dir"):
        # per-fold trains would interleave checkpoints in one directory
        # (and each fold's fresh start clears the previous fold's) —
        # checkpointing is a single-run feature
        log.warning("checkpoint_dir is not supported inside cv(); "
                    "checkpointing disabled for the fold trainings")
        params = {k: v for k, v in params.items() if k != "checkpoint_dir"}
    if metrics is not None:
        params["metric"] = metrics
    # construction-affecting params (max_bin, linear_tree, enable_bundle...)
    # must reach the shared binning pass (the reference merges params into
    # the train set before building folds, engine.py _make_n_folds)
    if train_set._inner is None:
        train_set.params = {**train_set.params, **params}
    else:
        # binning is already fixed; warn like the reference's
        # _update_params on a constructed Dataset
        stale = [k for k in ("max_bin", "linear_tree", "enable_bundle",
                             "max_bin_by_feature", "min_data_in_bin")
                 if k in params
                 and params[k] != train_set.params.get(k, params[k])]
        if stale:
            log.warning(f"cv params {stale} ignored: the Dataset is "
                        "already constructed with its own binning")
    train_set.construct()
    inner = train_set.inner
    n = inner.num_data
    label = np.asarray(inner.metadata.label)

    rng = np.random.default_rng(seed)
    qb = inner.metadata.query_boundaries
    fold_groups = None  # per-fold (train_sizes, test_sizes) for ranking
    if folds is None:
        idx = np.arange(n)
        if qb is not None:
            # fold over whole queries so boundaries survive
            nq = len(qb) - 1
            qidx = np.arange(nq)
            if shuffle:
                rng.shuffle(qidx)
            qparts = np.array_split(qidx, nfold)
            folds = []
            fold_groups = []
            for part in qparts:
                test_q = np.sort(part)
                train_q = np.setdiff1d(qidx, part)
                test_rows = np.concatenate(
                    [np.arange(qb[q], qb[q + 1]) for q in test_q]) \
                    if len(test_q) else np.array([], int)
                train_rows = np.concatenate(
                    [np.arange(qb[q], qb[q + 1]) for q in train_q]) \
                    if len(train_q) else np.array([], int)
                folds.append((train_rows, test_rows))
                fold_groups.append(
                    (np.diff(qb)[train_q], np.diff(qb)[test_q]))
        elif stratified and params.get("objective") in (
                "binary", "multiclass", "multiclassova"):
            folds_idx = [[] for _ in range(nfold)]
            for cls in np.unique(label):
                cls_idx = idx[label == cls]
                if shuffle:
                    rng.shuffle(cls_idx)
                for i, part in enumerate(np.array_split(cls_idx, nfold)):
                    folds_idx[i].extend(part)
            folds = [(np.setdiff1d(idx, np.asarray(f)), np.asarray(sorted(f)))
                     for f in folds_idx]
        else:
            if shuffle:
                rng.shuffle(idx)
            parts = np.array_split(idx, nfold)
            folds = [(np.setdiff1d(np.arange(n), p), np.sort(p))
                     for p in parts]

    cvb = CVBooster()
    histories = []
    # ONE observability session for the whole cv run: fold train() calls
    # join it (obs.trace.start no-ops while a recorder is active), so
    # trace_output gets a single trace covering every fold instead of
    # each fold overwriting the file
    import types
    obs_cfg = types.SimpleNamespace(
        trace_output=params.get("trace_output", ""),
        event_output=params.get("event_output", ""),
        profile_dir=params.get("profile_dir", ""))
    with observe_training(obs_cfg):
        for fi, (train_idx, test_idx) in enumerate(folds):
            # fold datasets are SUBSETS of the binned data — bin mappers
            # (and the EFB plan) are shared, nothing is re-binned
            # (reference cv builds folds with Dataset.subset, engine.py
            # _make_n_folds)
            dtrain = Dataset.from_inner(inner.subset(train_idx),
                                        dict(train_set.params))
            dtest = Dataset.from_inner(inner.subset(test_idx),
                                       dict(train_set.params))
            if fold_groups is not None:
                gtr, gte = fold_groups[fi]
                dtrain.inner.metadata.set_group(gtr)
                dtest.inner.metadata.set_group(gte)
            rec: Dict[str, Dict[str, List[float]]] = {}
            vs, vn = [dtest], ["valid"]
            if eval_train_metric:
                vs.append(dtrain)
                vn.append("train")
            bst = train(params, dtrain, num_boost_round,
                        valid_sets=vs, valid_names=vn,
                        feval=feval, callbacks=list(callbacks or [])
                        + [record_evaluation(rec)])
            cvb.append(bst)
            histories.append(rec)

    # per-iteration mean/stdv across folds, the reference cv's return
    # shape (engine.py:611 _agg_cv_result); folds stopped early by a
    # callback truncate to the shortest history
    out: Dict[str, List[float]] = {}
    first_valid_key = None
    for set_name in histories[0]:
        # train() labels the training set "training"; cv's public keys use
        # "train" (reference cv key naming)
        public = "train" if set_name == "training" else set_name
        for metric in histories[0][set_name]:
            rows = [h[set_name][metric] for h in histories]
            it = min(len(r) for r in rows)
            arr = np.asarray([r[:it] for r in rows])
            out[f"{public} {metric}-mean"] = [float(v)
                                             for v in arr.mean(axis=0)]
            out[f"{public} {metric}-stdv"] = [float(v)
                                             for v in arr.std(axis=0)]
            if public == "valid" and first_valid_key is None:
                first_valid_key = f"valid {metric}-mean"
    # early stopping in any fold: truncate to the aggregate best
    # iteration over the mean curve and record it, like the reference's
    # cv (its folds run in lockstep and stop once)
    # params may override the round count (train() honors
    # params['num_iterations']); compare against the EFFECTIVE count or a
    # params-supplied limit would read as early stopping
    nbr_eff = int(params.get("num_iterations", num_boost_round))
    stopped = any(
        min((len(r) for r in h.get("valid", {}).values()),
            default=nbr_eff) < nbr_eff
        for h in histories)
    if first_valid_key and stopped:
        ev0 = cvb.boosters[0].eval_valid()
        higher_better = bool(ev0[0][3]) if ev0 else False
        curve = np.asarray(out[first_valid_key])
        best_idx = int(np.argmax(curve) if higher_better
                       else np.argmin(curve))
        for k in list(out):
            out[k] = out[k][:best_idx + 1]
        cvb.best_iteration = best_idx + 1
    if return_cvbooster:
        out["cvbooster"] = cvb
    return out
