"""Host and device memory observability.

The reference publishes peak RAM as a headline result next to wall-clock
(docs/Experiments.rst: 0.897 GB on Higgs) — memory is a first-class axis of
the perf story, and a regression in it should be as visible as a slowdown.
This module samples:

  * host RSS — current (``/proc/self/statm``) and peak
    (``resource.getrusage`` ``ru_maxrss``, kilobytes on Linux),
  * device memory — ``device.memory_stats()`` where the backend exposes it
    (TPU/GPU runtimes do; CPU may return nothing), reported per-device and
    never assumed present.

Everything degrades to ``None`` rather than raising: a telemetry sample
must never take training down.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def host_rss_mb() -> Optional[float]:
    """Current resident set size in MB (Linux ``/proc``; None elsewhere)."""
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        return rss_pages * _PAGE_SIZE / (1024 * 1024)
    except Exception:
        return None


def peak_host_rss_mb() -> Optional[float]:
    """Process peak RSS in MB (``ru_maxrss``; KB on Linux, bytes on mac)."""
    try:
        import resource
        import sys
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        scale = 1024 * 1024 if sys.platform == "darwin" else 1024
        return peak / scale
    except Exception:
        return None


def device_memory_stats() -> Optional[Dict[str, Any]]:
    """Per-device memory stats where the backend exposes them.

    Returns ``{"platform": ..., "devices": [{"id", "bytes_in_use",
    "peak_bytes_in_use", ...}]}`` or ``None`` when no device reports
    (plain CPU backends).  Only called from cold paths (per-iteration
    telemetry) — it touches the jax backend."""
    try:
        import jax
        devs = jax.local_devices()
    except Exception:
        return None
    rows = []
    platform = None
    for d in devs:
        platform = platform or d.platform
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        rows.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
        })
    if not rows:
        return None
    return {"platform": platform, "devices": rows}


def memory_snapshot() -> Dict[str, Any]:
    """One sample of every memory axis — the record shape shared by the
    telemetry JSONL and ``Booster.telemetry()``.
    Host fields may be ``None`` off-Linux; ``device_memory`` is ``None``
    when no backend device reports stats."""
    dev = device_memory_stats()
    out: Dict[str, Any] = {
        "host_rss_mb": _round(host_rss_mb()),
        "host_peak_rss_mb": _round(peak_host_rss_mb()),
        "device_memory": dev,
    }
    if dev and dev["devices"]:
        # headline scalars for quick JSONL reading (sum over devices)
        out["device_bytes_in_use"] = _sum_field(dev, "bytes_in_use")
        out["device_peak_bytes_in_use"] = _sum_field(dev,
                                                     "peak_bytes_in_use")
    return out


def _round(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 2)


def _sum_field(dev: Dict[str, Any], field: str) -> Optional[int]:
    vals = [r[field] for r in dev["devices"] if r.get(field) is not None]
    return sum(vals) if vals else None
