"""Measure the collective layer: per-round psum wall time and overlap.

The ``collective_overlap`` optimization (ops/histogram.py
``reduce_hist``) splits the histogram all-reduce into two half psums so
the compiler can overlap the first half's network time with the second
half's issue — but since PR 6 it has only been *counted*
(``collective_overlap_rounds``), never *measured*.  Host timers inside a
jitted region are meaningless (device work is async), so this module
times standalone compiled probes OF the real ``reduce_hist`` body on the
real mesh:

  * ``t_blocked`` — the probe compiled with overlap forced OFF (one
    monolithic psum): the un-hidden collective cost per histogram pass.
  * ``t_live``    — the probe compiled exactly as training compiles it
    (split psums when enabled): the observed cost.

``overlap_efficiency = clamp((t_blocked - t_live) / t_blocked, 0, 1)``
— the fraction of collective time the split schedule hides.  With
overlap disabled (``collective_overlap=off`` or ``LGBMTPU_NO_OVERLAP=1``)
the live probe IS the blocked probe and the gauge reads exactly 0.0,
which is what the A/B test asserts.

Results land as gauges (``overlap_efficiency``, ``collective_s_per_pass``)
on both the booster's registry and ``global_metrics`` — telemetry JSONL
rows and ``bench.py`` payloads pick them up from there — plus a trace
counter when a recorder is active.  Probes are cached per (mesh, shape,
dtype, overlap) and only run when observability is configured, so the
no-outputs path never pays for them.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Tuple

from .metrics import MetricsRegistry, count_event, global_metrics

#: probe results keyed by (mesh signature, shape, dtype, overlap-on);
#: one measurement per compiled configuration per process
_CACHE: Dict[Any, Dict[str, float]] = {}
_CACHE_LOCK = threading.Lock()

#: cap on probe element count — the probe models the histogram
#: all-reduce's SHAPE, not its full size; a bounded payload keeps the
#: measurement cheap while preserving the split-vs-monolithic contrast
_MAX_ELEMS = 1 << 20


def _probe_shape(shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """Shrink trailing dims until the probe payload is bounded, keeping
    the leading (split) axis intact — the overlap split is along axis
    0, so that axis must stay representative."""
    shape = tuple(int(d) for d in shape)
    if not shape:
        return shape
    elems = 1
    for d in shape:
        elems *= max(d, 1)
    out = list(shape)
    i = len(out) - 1
    while elems > _MAX_ELEMS and i > 0:
        factor = min(out[i], max(1, elems // _MAX_ELEMS))
        out[i] = max(1, out[i] // factor)
        elems = 1
        for d in out:
            elems *= max(d, 1)
        i -= 1
    return tuple(out)


def _time_probe(mesh, shape, dtype, overlap_on: bool) -> float:
    """Compile + time one ``reduce_hist`` probe; returns best-of-3
    seconds per pass (min filters scheduler noise)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.histogram import reduce_hist
    from jax import shard_map
    from ..parallel.mesh import DATA_AXIS

    def local(x):
        return reduce_hist(x, DATA_AXIS, overlap_on)

    n_dev = int(mesh.devices.size)
    full = (shape[0] * n_dev,) + tuple(shape[1:]) if shape else (n_dev,)
    fn = jax.jit(shard_map(local, mesh=mesh,
                           in_specs=P(DATA_AXIS),
                           out_specs=P(), check_vma=False))
    x = jnp.ones(full, dtype=dtype)
    fn(x).block_until_ready()            # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def measure_collective(mesh, shape: Tuple[int, ...],
                       dtype: Any = None,
                       overlap: bool = True,
                       metrics: Optional[MetricsRegistry] = None
                       ) -> Dict[str, float]:
    """Measure per-pass collective wall time + overlap efficiency.

    ``shape`` is the per-device histogram shape ``reduce_hist`` sees
    (leading axis = the split axis); ``overlap`` is the booster's
    resolved overlap flag, re-gated through the same
    ``overlap_enabled`` check training uses — including the
    ``LGBMTPU_NO_OVERLAP`` escape hatch.  Returns (and gauges)::

        {"collective_s_per_pass": ..., "collective_s_blocked": ...,
         "overlap_efficiency": ..., "overlap_on": 0.0|1.0}
    """
    import jax.numpy as jnp

    from ..ops.compile_cache import mesh_signature
    from ..ops.histogram import overlap_enabled

    if dtype is None:
        dtype = jnp.float32
    shape = _probe_shape(tuple(shape))
    on = bool(overlap_enabled(overlap)) and len(shape) >= 1 \
        and shape[0] >= 2
    key = (mesh_signature(mesh), shape, str(jnp.dtype(dtype)), on)
    with _CACHE_LOCK:
        cached = _CACHE.get(key)
    if cached is None:
        count_event("collective_probe_runs")
        t_blocked = _time_probe(mesh, shape, dtype, overlap_on=False)
        if on:
            t_live = _time_probe(mesh, shape, dtype, overlap_on=True)
        else:
            t_live = t_blocked
        if on and t_blocked > 0:
            eff = (t_blocked - t_live) / t_blocked
            eff = min(max(eff, 0.0), 1.0)
        else:
            eff = 0.0
        cached = {"collective_s_per_pass": round(t_live, 9),
                  "collective_s_blocked": round(t_blocked, 9),
                  "overlap_efficiency": round(eff, 6),
                  "overlap_on": 1.0 if on else 0.0}
        with _CACHE_LOCK:
            _CACHE[key] = cached
    for registry in (metrics, global_metrics):
        if registry is not None:
            for name, val in cached.items():
                registry.set_gauge(name, val)
    from . import trace as obs_trace
    rec = obs_trace.active()
    if rec is not None:
        rec.add_counter("collective", dict(cached))
    return dict(cached)


def reset_cache() -> None:
    """Drop memoized probe results (tests toggling LGBMTPU_NO_OVERLAP)."""
    with _CACHE_LOCK:
        _CACHE.clear()
