"""Training callbacks.

TPU-native re-design of the reference callback system (reference:
python-package/lightgbm/callback.py — ``early_stopping`` :278 with min_delta,
``log_evaluation``, ``record_evaluation``, ``reset_parameter``;
``CallbackEnv`` namedtuple).
"""

from __future__ import annotations

import collections
import os
from typing import Any, Callable, Dict, List, Optional

from .utils import log

CallbackEnv = collections.namedtuple(
    "CallbackEnv",
    ["model", "params", "iteration", "begin_iteration", "end_iteration",
     "evaluation_result_list"])

#: distinguishes train() runs appending telemetry JSONL to one shared
#: path (cv folds) — each log_telemetry instance draws one id
import itertools as _itertools
_TELEMETRY_RUN_SEQ = _itertools.count()


class EarlyStopException(Exception):
    def __init__(self, best_iteration: int, best_score):
        self.best_iteration = best_iteration
        self.best_score = best_score


def log_evaluation(period: int = 1, show_stdv: bool = True) -> Callable:
    def _callback(env: CallbackEnv) -> None:
        if period > 0 and env.evaluation_result_list and \
                (env.iteration + 1) % period == 0:
            parts = []
            for item in env.evaluation_result_list:
                if len(item) == 4:
                    name, metric, val, _ = item
                    parts.append(f"{name}'s {metric}: {val:g}")
                else:
                    name, metric, val, _, stdv = item
                    parts.append(f"{name}'s {metric}: {val:g} + {stdv:g}"
                                 if show_stdv else f"{name}'s {metric}: {val:g}")
            log.info(f"[{env.iteration + 1}]\t" + "\t".join(parts))
    _callback.order = 10
    # fused-training contract (engine.py / GBDT.train_fused): this callback
    # only READS the per-iteration eval list, so it can be driven from the
    # host replay of a fused chunk's device-evaluated metrics
    _callback.fused_safe = True
    return _callback


def record_evaluation(eval_result: Dict[str, Dict[str, List[float]]]) -> Callable:
    if not isinstance(eval_result, dict):
        raise TypeError("eval_result should be a dictionary")

    def _init(env: CallbackEnv) -> None:
        eval_result.clear()
        for item in env.evaluation_result_list:
            name, metric = item[0], item[1]
            eval_result.setdefault(name, collections.OrderedDict())
            eval_result[name].setdefault(metric, [])

    def _callback(env: CallbackEnv) -> None:
        if not eval_result:
            _init(env)
        for item in env.evaluation_result_list:
            name, metric, val = item[0], item[1], item[2]
            eval_result.setdefault(name, collections.OrderedDict())
            eval_result[name].setdefault(metric, []).append(val)
    _callback.order = 20
    _callback.fused_safe = True   # reads the eval list only (see above)
    # resume hook (robustness/checkpoint.py): a checkpointed eval history
    # is re-injected into this dict so a resumed run's recorded history
    # is the uninterrupted run's
    _callback.eval_result = eval_result
    return _callback


def reset_parameter(**kwargs: Any) -> Callable:
    def _callback(env: CallbackEnv) -> None:
        new_params = {}
        for key, value in kwargs.items():
            if isinstance(value, list):
                if len(value) != env.end_iteration - env.begin_iteration:
                    raise ValueError(f"Length of list {key!r} has to be equal "
                                     "to number of boosting rounds")
                new_params[key] = value[env.iteration - env.begin_iteration]
            elif callable(value):
                new_params[key] = value(env.iteration - env.begin_iteration)
        if new_params:
            if "learning_rate" in new_params:
                env.model._gbdt.shrinkage_rate = float(
                    new_params["learning_rate"])
            env.params.update(new_params)
    _callback.before_iteration = True
    _callback.order = 10
    return _callback


def _prune_stale_telemetry(path: str, cut: int) -> int:
    """Drop telemetry records with ``iteration >= cut`` from ``path``
    (atomic rewrite).  A killed run emits records for rounds PAST the
    checkpoint its successor resumes from; without pruning, the resumed
    run re-emits those indices and the file carries duplicate/overlapping
    iterations (or, when every checkpoint was lost, a full restart's
    indices interleaved with the stale tail).  Unparseable lines are kept
    verbatim — pruning must never eat a record it does not understand.
    Returns the number of dropped records."""
    import json
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return 0
    kept, dropped = [], 0
    for ln in lines:
        try:
            it = int(json.loads(ln).get("iteration", -1))
        except (ValueError, TypeError):
            kept.append(ln)
            continue
        if it >= cut:
            dropped += 1
        else:
            kept.append(ln)
    if dropped:
        from .utils.paths import write_atomic
        # telemetry is an append-only log, not crash-critical state; the
        # rewrite only needs atomicity, not a directory fsync
        write_atomic(path, "".join(kept), fsync_dir=False)
    return dropped


#: rows of the compile table a telemetry record carries
_COMPILE_TABLE_ROWS = 12


def log_telemetry(path: str, period: int = 1,
                  resume_from: Optional[int] = None) -> Callable:
    """Append one JSONL telemetry record per boosting iteration to
    ``path`` (the callback behind the ``telemetry_output=<path>`` config
    key; also usable directly in a ``callbacks=[...]`` list).

    Each record carries the iteration index, wall-clock seconds since the
    previous record, the iteration's eval results, the booster's telemetry
    counters (obs/metrics.py) and a host/device memory sample
    (obs/memory.py) — so a BENCH_*.json-style memory regression or a
    silent slow-path fallback is visible per iteration, not just at exit.
    When a trace recorder is active the memory sample is also emitted as a
    Chrome trace counter track.  Fused-safe: it only READS booster state
    and the eval list, so it can be driven from the host replay of a fused
    chunk's device-evaluated metrics — records from that replay carry
    ``"fused_replay": true`` because there ``iter_time_s`` is the replay
    cadence (~0 within a chunk, the whole chunk's wall time at its
    boundary), NOT per-iteration device cost.

    Each record carries a ``"run"`` id unique to this callback instance:
    several train() runs appending to ONE file (``cv()`` folds share the
    ``telemetry_output`` path) stay distinguishable even though their
    iteration indices and per-booster counters each restart at 0.

    ``resume_from`` (set by the engine on ``resume="auto"``) is the
    ABSOLUTE iteration this run restarts at: before its first record is
    written, existing records at or past that index — emitted by the
    killed predecessor for rounds the checkpoint rolled back — are
    pruned, so the file reads as one continuous per-iteration history
    with no duplicate or overlapping indices."""
    import json
    import time as _time

    state: Dict[str, Any] = {"t_last": None, "fused_seen": 0,
                             "run": next(_TELEMETRY_RUN_SEQ),
                             "pruned": resume_from is None}

    def _callback(env: CallbackEnv) -> None:
        if period > 0 and (env.iteration + 1) % period != 0:
            return
        if not state["pruned"]:
            state["pruned"] = True
            n = _prune_stale_telemetry(path, int(resume_from))
            if n:
                log.info(f"telemetry_output: pruned {n} stale record(s) "
                         f"at iteration >= {resume_from} left by the "
                         "interrupted predecessor run")
        from .obs import memory as obs_memory, trace as obs_trace
        # iter_time_s is an ELAPSED measurement — monotonic, so an NTP
        # step mid-run cannot produce a negative or inflated duration;
        # unix_time stays wall (it is a journal stamp, not arithmetic)
        now_mono = _time.monotonic()
        dt = (None if state["t_last"] is None
              else now_mono - state["t_last"])
        state["t_last"] = now_mono
        mem = obs_memory.memory_snapshot()
        rec: Dict[str, Any] = {
            "run": state["run"],
            "iteration": env.iteration,
            "unix_time": round(_time.time(), 3),
            "iter_time_s": None if dt is None else round(dt, 6),
            "evals": {f"{item[0]}.{item[1]}": float(item[2])
                      for item in (env.evaluation_result_list or [])},
        }
        gb = getattr(env.model, "_gbdt", None)
        if gb is not None:
            snap = gb.metrics.snapshot()
            counters = snap["counters"]
            rec["counters"] = counters
            if snap["gauges"]:
                # point-in-time samples
                rec["gauges"] = snap["gauges"]
            fused_now = counters.get("fused_rounds", 0)
            if fused_now > state["fused_seen"]:
                rec["fused_replay"] = True
            state["fused_seen"] = fused_now
        # XLA compile activity is counted process-globally (the
        # jax.monitoring listener has no booster handle, obs/
        # compile_events.py), so the compile-count gate signal rides
        # every record as a separate scope — cumulative process totals,
        # not per-booster deltas
        from .obs.metrics import global_metrics
        rec["process_counters"] = {
            "xla_compile_events":
                global_metrics.counter("xla_compile_events"),
            "xla_program_lowerings":
                global_metrics.counter("xla_program_lowerings"),
            "round_compile_hits":
                global_metrics.counter("round_compile_hits"),
            "round_compile_misses":
                global_metrics.counter("round_compile_misses"),
        }
        # which programs those were, by span and stage: the compile
        # table's top rows, in the records after which it had grown
        # (none once nothing compiles any more)
        from .obs import compile_events
        stages = compile_events.stages_seen()
        if stages != state.get("compile_stages"):
            state["compile_stages"] = stages
            rec["compile_table"] = [
                {**row, "seconds": round(row["seconds"], 6)}
                for row in compile_events.table()[:_COMPILE_TABLE_ROWS]]
        rec.update(mem)
        try:
            with open(path, "a") as f:
                f.write(json.dumps(rec) + "\n")
        except OSError as e:
            # telemetry must never take training down: degrade to a
            # one-time warning (e.g. disk filled mid-run)
            if not state.get("write_failed"):
                state["write_failed"] = True
                log.warning(f"telemetry write to {path!r} failed "
                            f"({type(e).__name__}: {e}); further "
                            "records dropped")
            return
        tr = obs_trace.active()
        if tr is not None:
            track = {k: mem[k] for k in ("host_rss_mb",
                                         "device_bytes_in_use")
                     if mem.get(k) is not None}
            if track:
                tr.add_counter("memory", track)
    _callback.order = 25
    _callback.fused_safe = True   # reads booster state + eval list only
    return _callback


def early_stopping(stopping_rounds: int, first_metric_only: bool = False,
                   verbose: bool = True, min_delta: float = 0.0) -> Callable:
    """reference callback.py:278 — stop when no eval metric improves by more
    than ``min_delta`` in ``stopping_rounds`` rounds."""
    state: Dict[str, Any] = {}

    def _is_better(curr, best, bigger, delta):
        if bigger:
            return curr > best + delta
        return curr < best - delta

    def _init(env: CallbackEnv) -> None:
        if not env.evaluation_result_list:
            raise ValueError(
                "For early stopping, at least one dataset and eval metric is "
                "required for evaluation")
        state["best_score"] = [None] * len(env.evaluation_result_list)
        state["best_iter"] = [0] * len(env.evaluation_result_list)
        state["best_list"] = [None] * len(env.evaluation_result_list)
        state["first_metric"] = env.evaluation_result_list[0][1]
        if verbose:
            log.info(f"Training until validation scores don't improve for "
                     f"{stopping_rounds} rounds")

    def _callback(env: CallbackEnv) -> None:
        # reset at the first iteration so one callback object can be reused
        # across train() runs (cv() folds reuse the same instance) —
        # UNLESS a checkpoint resume just re-seeded the state
        # (robustness/checkpoint.py restore_into sets "resume_ready")
        if env.iteration == env.begin_iteration and \
                not state.pop("resume_ready", False):
            state.clear()
        if not state:
            _init(env)
        best_score = state["best_score"]
        best_iter = state["best_iter"]
        for i, item in enumerate(env.evaluation_result_list):
            name, metric, val, bigger = item[0], item[1], item[2], item[3]
            if name == "training":
                continue
            if first_metric_only and metric.split("@")[0] != \
                    state["first_metric"].split("@")[0]:
                continue
            if best_score[i] is None or _is_better(val, best_score[i], bigger,
                                                   min_delta):
                best_score[i] = val
                best_iter[i] = env.iteration
                state["best_list"][i] = list(env.evaluation_result_list)
            elif env.iteration - best_iter[i] >= stopping_rounds:
                if verbose:
                    log.info(f"Early stopping, best iteration is: "
                             f"[{best_iter[i] + 1}]")
                raise EarlyStopException(best_iter[i], state["best_list"][i])
            if env.iteration == env.end_iteration - 1:
                if verbose:
                    log.info(f"Did not meet early stopping. Best iteration is:"
                             f" [{best_iter[i] + 1}]")
                raise EarlyStopException(best_iter[i], state["best_list"][i])
    _callback.order = 30
    _callback.fused_safe = True   # reads the eval list only (see above)
    # introspection for the fused path's optional IN-JIT compute gating
    # (GBDT.train_fused skips growth in rounds past the would-be stop)
    _callback.es_params = (stopping_rounds, first_metric_only, min_delta)
    # checkpoint hook (robustness/checkpoint.py): the patience state is
    # saved and re-seeded on resume, so a resumed early-stopping run
    # stops at the same round as the uninterrupted one
    _callback.stopping_state = state
    return _callback
