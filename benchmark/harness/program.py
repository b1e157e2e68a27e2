"""Everything that touches the system under test: the device, the
``Dataset``, one ``lgb.train`` job, and the checks that the job took the
path the cell is about.  Nothing here decides ``correct``."""

from __future__ import annotations

import gc
import os
import time

import numpy as np


class Refused(SystemExit):
    """The run cannot be made here: no result line, exit code 2."""

    def __init__(self, why: str):
        super().__init__(2)
        self.why = why


def require(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


CACHE_MAX_BYTES = 4 << 30


def place_compile_cache(root: str) -> str:
    """JAX's persistent compile cache at a fixed place: where the machine
    says (``JAX_COMPILATION_CACHE_DIR``), else inside the checkout."""
    import jax
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    where = given or os.path.join(root, ".bench_cache", "jax")
    if not given:
        jax.config.update("jax_compilation_cache_dir", where)
        # a round program carries its data set's labels and valid bins
        # (120-180 MB an entry, one per seed): keep the newest two dozen
        jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    # small programs too: a second run has to find every program there
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileLog:
    """Counts of JAX's own compile events: persistent-cache hits and
    misses, backend compiles and the seconds they took."""

    def __init__(self):
        from jax import monitoring
        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **kw) -> None:
        if name.endswith("/cache_hits"):
            self.hits += 1
        elif name.endswith("/cache_misses"):
            self.misses += 1

    def _duration(self, name: str, secs: float, **kw) -> None:
        if "backend_compile" in name:
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "backend_compiles": self.compiles,
                "backend_compile_s": round(self.compile_s, 3)}


def host_usage() -> dict:
    """This process's CPU seconds and page faults so far: what the host
    did in a window is the difference of two readings."""
    import resource
    r = resource.getrusage(resource.RUSAGE_SELF)
    return {"user_s": round(r.ru_utime, 3), "sys_s": round(r.ru_stime, 3),
            "minor_faults": r.ru_minflt}


def open_device(chips: int, rehearse_cpu: bool) -> dict:
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if rehearse_cpu:
        if platform != "cpu":
            raise Refused("--rehearse-cpu is for the CPU backend")
    elif platform != "tpu":
        raise Refused(f"no TPU: jax platform is {platform!r}")
    elif len(devs) != chips:
        raise Refused(f"the cell needs {chips} chip(s), jax sees {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peaks() -> dict:
    """Peak of the fullest chip, and the allocator's two pools apart.
    ``memory_in_use_peak_bytes`` (``peak_bytes_in_use``) is what arrays
    held: bins, scores, the packed mirror, histograms.
    ``memory_reserved_peak_bytes`` (``peak_bytes_reserved``) is what the
    runtime set aside for the loaded programs' temporaries, outside the
    arrays' pool (``bytes_limit`` = in use + reserved + free; PERF.md
    section 2).  ``memory_peak_bytes``, the number the driver holds its
    floor against, is their sum: what of the chip's memory was taken
    while the round program ran."""
    import jax
    stats = [(d.memory_stats() or {}) for d in jax.devices()]
    full = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0)
               + s.get("peak_bytes_reserved", 0))
    in_use = int(full.get("peak_bytes_in_use", 0))
    reserved = int(full.get("peak_bytes_reserved", 0))
    return {"memory_peak_bytes": in_use + reserved,
            "memory_in_use_peak_bytes": in_use,
            "memory_reserved_peak_bytes": reserved}


def memory_stats() -> dict:
    import jax
    return dict(jax.devices()[0].memory_stats() or {})


def bytes_in_use() -> int:
    import jax
    return int(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.devices()))


def construct(lgb, params: dict, train, valid):
    """``lgb.Dataset(...).construct()`` on float64 feature-major data (a
    no-copy view for the program), then the valid set on its mappers."""
    (xt64, y), (xv64, yv) = train, valid
    ds = lgb.Dataset(xt64.T, label=y, params=params).construct()
    dv = None
    if xv64 is not None:
        dv = ds.create_valid(xv64.T, label=yv).construct()
    return ds, dv


def run_job(lgb, params: dict, ds, dv, rounds: int, dispatch: int,
            deadline: float, on_trees=None, at_least: int = 1):
    """One ``lgb.train`` job of up to ``rounds`` rounds, stopped at the
    first dispatch boundary at or after ``deadline`` (host clock) once
    ``at_least`` dispatches are done.  ``on_trees`` is called whenever a
    dispatch has its trees on the host.  Returns the booster, the valid
    metric per round, the rounds done."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.callback import EarlyStopException
    chunks = GBDT.fused_chunks(rounds)
    require(set(chunks) == {min(dispatch, rounds)},
            f"num_boost_round={rounds} gives dispatches {sorted(set(chunks))}, "
            f"the traffic file says {dispatch}")
    evals: dict = {}
    done = [0]

    def boundary(env):
        done[0] = env.iteration + 1
        if env.iteration % dispatch == 0 and on_trees is not None:
            on_trees()
        if done[0] % dispatch == 0 and done[0] < rounds \
                and done[0] >= at_least * dispatch and time.time() >= deadline:
            raise EarlyStopException(env.iteration, env.evaluation_result_list)
    boundary.order = 90
    boundary.fused_safe = True       # reads the clock, changes nothing

    callbacks = [boundary]
    if dv is not None:
        callbacks.insert(0, lgb.record_evaluation(evals))
    bst = lgb.train(params, ds, num_boost_round=rounds,
                    valid_sets=[dv] if dv is not None else None,
                    callbacks=callbacks)
    metric = params.get("metric", "auc")
    aucs = list(evals["valid_0"][metric]) if dv is not None else []
    require(len(bst._gbdt.models) == done[0],
            f"{len(bst._gbdt.models)} trees after {done[0]} rounds")
    return bst, aucs, done[0]


def global_counter(name: str) -> float:
    from lightgbm_tpu.obs.metrics import global_metrics
    return global_metrics.counter(name)


def check_path(bst, cfg: dict, rounds: int, dispatch: int, on_tpu: bool) -> dict:
    """The job went the way the cell says: every round inside the fused
    scan, the split batch and histogram type the configuration expects,
    the booster's state on the TPU."""
    import jax
    gb = bst._gbdt
    expects = cfg.get("expects", {})
    got = {"tpu_split_batch": int(gb.config.tpu_split_batch),
           "hist_dtype": gb.hp.hist_dtype,
           "packed_mirror": gb.bins_words is not None,
           "device_n_bins": int(gb.hp.n_bins)}
    for key, want in expects.items():
        require(got.get(key) == want, f"{key}: expected {want}, got {got.get(key)}")
    require(gb.supports_fused(), "the booster does not support the fused scan")
    fused = gb.metrics.counter("fused_rounds")
    require(fused == rounds, f"{fused} of {rounds} rounds ran in train_fused")
    keys = {k[0] for k in gb._fused_cache}
    require(keys == {dispatch},
            f"fused dispatch lengths {sorted(keys)}, expected {dispatch}")
    if on_tpu:
        off = [name for name, v in vars(gb).items()
               for a in _arrays(v)
               if {d.platform for d in a.devices()} != {"tpu"}]
        require(not off, f"booster state not on the TPU: {sorted(set(off))}")
    return got


def _arrays(v):
    import jax
    if isinstance(v, jax.Array):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _arrays(x)
    elif isinstance(v, dict):
        for x in v.values():
            yield from _arrays(x)


def plain_trees(models) -> list:
    """A job's trees (``Booster._gbdt.models``) as plain arrays: what the
    reference is given."""
    out = []
    for t in models:
        ni = t.num_leaves - 1
        out.append({
            "num_leaves": int(t.num_leaves),
            "split_feature": np.asarray(t.split_feature[:ni], np.int64),
            "threshold": np.asarray(t.threshold[:ni], np.float64),
            "left_child": np.asarray(t.left_child[:ni], np.int64),
            "right_child": np.asarray(t.right_child[:ni], np.int64),
            "split_gain": np.asarray(t.split_gain[:ni], np.float64),
            "leaf_value": np.asarray(t.leaf_value[:t.num_leaves], np.float64),
            "leaf_count": np.asarray(t.leaf_count[:t.num_leaves], np.int64),
        })
    return out


def train_scores(bst) -> np.ndarray:
    return np.asarray(bst._gbdt.scores)[:, 0]


def free_everything() -> int:
    """Drop what the program keeps on the device (its process-wide cache
    of compiled runners holds the boosters' arrays) and say what is left."""
    import jax
    from lightgbm_tpu.ops.compile_cache import GLOBAL_COMPILE_CACHE
    GLOBAL_COMPILE_CACHE.clear()
    gc.collect()
    jax.clear_caches()
    gc.collect()
    return bytes_in_use()
