"""Process- and booster-scoped telemetry counters/gauges.

The reference exposes no runtime counters at all — silent slow-path
decisions (a batched-grower fallback, a congested capture window) leave no
artifact.  This registry is the single place such events are tallied:
counters are monotone within a registry's lifetime, gauges carry the last
sampled value.  Two scopes exist:

  * ``global_metrics`` — process-wide, survives across boosters (the
    reference ``global_timer`` analogue for counts),
  * per-booster registries (``GBDT.metrics``) queryable via
    ``Booster.telemetry()``.

Counter bumps are one dict ``get`` + add on coarse (per-iteration /
per-decision) host paths only — never inside per-row or per-leaf loops, and
never inside jitted code (a traced bump would count compilations, not
executions).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

#: Every telemetry counter name used anywhere in the package, declared
#: once with a one-line meaning.  This is a lint contract (tpulint
#: OBS301): bumping an undeclared name — or declaring one nothing bumps —
#: fails `python tools/tpulint.py`.  Keys are parsed from this literal by
#: AST, so keep it a plain ``str: str`` dict.  Gauges are not listed:
#: their names are structural (memory sampling keys), not an API surface.
COUNTERS: Dict[str, str] = {
    "iterations": "boosting rounds executed (strict + fused paths)",
    "strict_rounds": "rounds run on the strict per-tree update path",
    "fused_rounds": "rounds run on the fused round-kernel fast path",
    "trees_grown": "trees grown (k per round for multiclass)",
    "hist_rows_selected":
        "rows the histogram passes had to read, counted from the trees "
        "(root pass + each split's smaller child; the batched serial and "
        "data learners without the bounded pool)",
    "hist_pool_fallbacks": "histogram-pool exhaustion -> rebuild fallbacks",
    "batched_path_fallbacks": "batched-grower bailouts to the strict path",
    "fused_runner_cache_hits": "fused round-runner compile-cache hits",
    "fused_runner_cache_misses": "fused round-runner compile-cache misses",
    "round_compile_hits":
        "process-level compile-cache hits (ops/compile_cache.py)",
    "round_compile_misses":
        "process-level compile-cache misses (ops/compile_cache.py)",
    "xla_compile_events":
        "XLA backend compiles observed by the obs/ compile-event listener",
    "xla_program_lowerings":
        "jaxpr->MLIR lowerings observed by the obs/ compile-event listener",
    "jaxpr_trace_s":
        "seconds tracing Python into jaxprs, outermost traces only "
        "(obs/ compile-event listener)",
    "xla_lowering_s":
        "seconds lowering jaxprs to MLIR modules (compile-event listener)",
    "xla_backend_compile_s":
        "seconds in the XLA backend compiler, persistent-cache "
        "retrieval taken out (compile-event listener)",
    "xla_cache_load_s":
        "seconds retrieving executables from the persistent compile "
        "cache (compile-event listener)",
    "collective_bytes":
        "logical all-reduce payload of the trees the per-iteration loop "
        "brought back, by formula from each tree's own split count",
    "sharded_rounds":
        "rounds of the per-iteration loop whose trees grew over a device "
        "mesh (tree_learner data, voting, feature, data_gspmd)",
    "efb_bundles":
        "physical columns of the EFB plans constructed (io/dataset.py); in "
        "a booster's own registry, of the plan it trains on",
    "efb_features":
        "used (virtual) features those plans cover",
    "efb_conflict_rows":
        "rows in which a bundle member's non-default value lost to an "
        "earlier member of its column (first writer wins); 0 for a plan "
        "whose conflicts were counted over every row",
    "bundle_space_search_rounds":
        "rounds whose split search stayed on the physical bundle columns "
        "(ops/split.py find_best_split_ranges)",
    "bundle_expand_calls":
        "expansions of a bundle histogram to virtual-feature space "
        "(_expand_hist, _expand_hist_col) put into a program, counted when "
        "TRACED: 0 for a job searched wholly in bundle space",
    "cat_features":
        "categorical columns of the training sets constructed "
        "(io/dataset.py); in a booster's own registry, of the set it "
        "trains on",
    "cat_subset_features":
        "those of them whose levels outnumber max_cat_to_onehot: the split "
        "search scans them by sorted subsets, the others one level at a time",
    "cat_levels_kept":
        "bins that hold a level, summed over the categorical columns",
    "cat_other_rows":
        "training rows in a categorical column's other bin (a level beyond "
        "the max_bin - 1 kept, a negative code, NaN), summed over columns",
    "cat_bin_mappers_s":
        "seconds of the dense construct spent on categorical columns "
        "(span cat_bin_mappers: their level counts and their bins)",
    "cat_splits":
        "splits on a categorical column in the trees a fused dispatch "
        "brought back",
    "cat_subset_splits":
        "those of them whose left set came from the sorted-subset scan "
        "(the column has more levels than max_cat_to_onehot)",
    "cat_left_levels":
        "levels in the left sets of those splits, summed",
    "fused_partition_declined":
        "rounds of a fused dispatch whose row partition took the XLA path "
        "(no Pallas backend, or a bundle plan without ranges) and not "
        "ops/round_fuse.py's kernel",
    "nan_guard_trips": "rounds where the numeric guard saw non-finite values",
    "nan_guard_raises": "numeric-guard trips escalated to an exception",
    "nan_rounds_skipped": "rounds dropped by nan_policy=skip_round",
    "nan_guard_halts": "trainings halted by nan_policy=halt_and_keep_best",
    "checkpoints_written": "checkpoints committed to checkpoint_dir",
    "checkpoint_write_failures": "checkpoint writes that failed (warned)",
    "checkpoint_resumes": "trainings resumed from a checkpoint",
    "checkpoints_skipped_invalid":
        "corrupt checkpoints skipped during resume scan",
    "elastic_slow_worker_rounds":
        "rounds a lagging-but-alive worker kept the monitor in bounded wait",
    "elastic_evictions":
        "workers declared dead and evicted by the heartbeat monitor",
    "elastic_reshapes":
        "mesh rebuilds over a survivor set after an eviction",
    "elastic_resumes":
        "post-reshape trainings resumed from the newest checkpoint",
    "serve_requests": "serving-tier predict() requests served",
    "serve_rows": "real (unpadded) rows served by the serving tier",
    "serve_bucket_hits":
        "serving request chunks that re-entered an already-warm bucket",
    "serve_pad_waste_rows":
        "padding rows added to reach bucket shapes (wasted device work)",
    "serve_hot_swaps":
        "registry publishes that atomically replaced a live model version",
    "serve_host_fallback_requests":
        "serving requests answered by the host booster fallback path",
    "serve_compile_hits":
        "serving-scope compile-cache hits (ops/compile_cache.py)",
    "serve_compile_misses":
        "serving-scope compile-cache misses (ops/compile_cache.py)",
    "serve_rejected_requests":
        "serving requests rejected by the in-flight admission bound",
    "serve_deadline_exceeded":
        "serving requests rejected because their deadline_ms had passed",
    "fleet_request_failovers":
        "fleet request dispatch attempts re-dispatched to a surviving "
        "replica (serving/fleet.py)",
    "fleet_replica_respawns":
        "dead serving replicas respawned by the fleet monitor",
    "fleet_replica_respawn_failures":
        "fleet monitor per-slot poll failures (e.g. a respawn failing "
        "at the OS level); the slot is abandoned after the limit",
    "fleet_rolling_swaps":
        "rolling hot-swaps completed across every fleet replica",
    "fleet_rolling_swap_aborts":
        "rolling hot-swaps aborted mid-rollout and rolled back",
    "predict_bucketed_calls":
        "predict_raw device blocks padded to the geometric bucket ladder",
    "predict_bucket_pad_rows":
        "padding rows added by predict_raw bucketing (predict_bucketing=on)",
    "event_journal_records":
        "structured events appended to the event journal (obs/events.py)",
    "trace_merges":
        "cross-rank trace merges performed (obs/merge.py)",
    "rollup_windows_closed":
        "time-series rollup windows finalized into the ring "
        "(obs/timeseries.py)",
    "slo_breaches":
        "SLO burn-rate breach transitions emitted (obs/slo.py)",
    "slo_recoveries":
        "SLO recovery transitions after a breach (obs/slo.py)",
    "anomalies_detected":
        "baseline-relative training anomalies flagged (obs/anomaly.py)",
    "request_traces_kept":
        "request span trees retained by tail-based sampling "
        "(obs/reqtrace.py)",
    "request_traces_sampled_out":
        "healthy request traces dropped by the sampling fraction "
        "(obs/reqtrace.py)",
    "flight_recorder_dumps":
        "crash flight-recorder rings dumped to disk (obs/reqtrace.py)",
    "ingest_shards_done":
        "streaming-ingest shards committed across both passes "
        "(io/streaming.py)",
    "ingest_rows_streamed":
        "rows absorbed by streaming-ingest pass 1 (io/streaming.py)",
    "ingest_resumes":
        "streaming ingests resumed from a workdir manifest instead of "
        "restarting (io/streaming.py)",
    "ingest_sketch_overflows":
        "per-feature exact distinct tallies that overflowed into the "
        "approximate quantile sketch (io/streaming.py)",
    "ingest_stripes_reassigned":
        "sharded-ingest stripes stolen from a dead worker's claim by "
        "a survivor (io/sharded.py)",
    "ingest_worker_deaths":
        "sharded-ingest workers declared dead after heartbeat_timeout_s "
        "of silence (io/sharded.py)",
    "pipeline_cycles_completed":
        "continuous-learning cycles acked end-to-end "
        "(pipeline/trainer.py)",
    "pipeline_publish_retries":
        "pipeline publishes retried after a mid-rollout abort rolled "
        "the fleet back (same cycle, same version)",
    "pipeline_stale_publishes_refused":
        "pipeline publishes refused because the live serving tier was "
        "already at or past the cycle's assigned version",
    "aot_store_hits":
        "serve programs deserialized from the disk AOT executable "
        "store instead of lowered live (ops/aot_store.py)",
    "aot_store_misses":
        "AOT store lookups that found no loadable artifact (absent, "
        "torn, stale or corrupt) and fell back to a live lowering",
    "aot_store_stale_evictions":
        "AOT artifacts evicted because their fingerprint, format or "
        "sha256 failed verification — never loaded, rebuilt live",
    "aot_store_writes":
        "compiled executables serialized into the AOT store "
        "(temp+rename-atomic artifact + sidecar meta)",
    "fleet_autoscale_ups":
        "replica slots spawned by the SLO-driven fleet autoscaler "
        "(serving/fleet.py serving_autoscale=on)",
    "fleet_autoscale_downs":
        "replica slots drained and retired by the fleet autoscaler "
        "after SLO recovery",
    "rank_compile_hits":
        "ranking-scope compile-cache hits — a query-length bucket "
        "re-entered an already-lowered pairwise program "
        "(ops/compile_cache.py)",
    "rank_compile_misses":
        "ranking-scope compile-cache misses — a fresh bucket geometry "
        "lowered a new pairwise program (ops/compile_cache.py)",
    "serve_contrib_requests":
        "serving-tier predict_contrib (tree-SHAP) requests served",
    "rank_queries":
        "queries of a ranking job's training set, counted once a job "
        "when the booster takes its objective (objectives.py)",
    "rank_docs":
        "docs (rows) of a ranking job's training set, once a job",
    "rank_slot_rows":
        "padded doc slots of the query-length bucket plan, the sum over "
        "buckets of queries x cap, once a job: what the per-query sorts "
        "read each round (rank_docs / rank_slot_rows is the plan's fill)",
    "rank_pair_slots":
        "pair slots of the lambdarank pair tensors, the sum over buckets "
        "of queries x min(truncation level, cap) x cap, once a job",
    "valid_mirror_device_bytes":
        "bytes of the valid sets' transposed bins ([F, n], what the "
        "matmul valid scorer and the fused round program read) that "
        "GBDT.add_valid made on the device from the placed [n, F] bins",
    "valid_mirror_host_bytes":
        "bytes of those mirrors transposed on the host and copied to "
        "the device: 0 since PR 39, what a return to the host path shows",
    "hist_col_blocks":
        "column blocks of one compacted histogram pass over the split "
        "batch's leaves (ops/hist_pallas.py col_blocks: 1 where the "
        "[3K, F x bins] accumulator fits the VMEM budget whole), once a "
        "booster",
    "hist_state_bytes":
        "bytes of the per-leaf histogram state the grower carries "
        "through a tree (f32 [leaves or pool slots, 4, F, bins]; it also "
        "carries one spare row for the writes of invalid slots, which "
        "this does not count), once a booster",
    "hist_vmem_budget_bytes":
        "the one VMEM budget every histogram and partition kernel's "
        "blocks follow from (ops/hist_pallas.py VMEM_BUDGET_BYTES), once "
        "a booster",
    "construct_bin_mappers_s":
        "seconds of the dense Dataset.construct in the span "
        "`dense_bin_mappers` (row sample and the bin finder of every "
        "feature), always timed",
    "construct_bin_matrix_s":
        "seconds in the span `dense_bin_matrix` (every value to its "
        "bin: the uint8 matrix), always timed",
}


class MetricsRegistry:
    __slots__ = ("_counters", "_gauges", "_lock")

    def __init__(self) -> None:
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        # the GLOBAL registry is shared by concurrently training
        # boosters (the same scenario per-booster timers exist for), and
        # an unlocked read-modify-write drops increments under threads
        self._lock = threading.Lock()

    def inc(self, name: str, value: float = 1) -> None:
        """Bump a monotone counter."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def set_gauge(self, name: str, value: float) -> None:
        """Record a point-in-time sample (last write wins)."""
        with self._lock:
            self._gauges[name] = float(value)

    def counter(self, name: str) -> float:
        return self._counters.get(name, 0)

    def gauge(self, name: str) -> Optional[float]:
        return self._gauges.get(name)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Copy of the current state (safe to serialize / mutate)."""
        with self._lock:
            return {"counters": dict(self._counters),
                    "gauges": dict(self._gauges)}

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()


#: process-wide registry (the counting analogue of utils.timer.global_timer)
global_metrics = MetricsRegistry()


def count_event(name: str, value: float = 1,
                booster_metrics: Optional[MetricsRegistry] = None) -> None:
    """Bump ``name`` in the global registry and, when given, a booster's
    own registry — the standard dual-scope tally used by instrumentation
    points."""
    global_metrics.inc(name, value)
    if booster_metrics is not None:
        booster_metrics.inc(name, value)
