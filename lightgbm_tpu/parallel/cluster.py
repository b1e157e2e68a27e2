"""Cluster orchestration: the Dask-layer equivalent.

The reference's Dask wrapper (reference: python-package/lightgbm/dask.py)
is the layer that STARTS distributed training rather than participating in
it: it maps workers to machines and open ports (``_machines_to_worker_map``
dask.py:374), ships each worker its data partitions, runs ``_train_part``
(:182-200 — plain ``train()`` with network params) on every worker, and
returns the rank-0 model.  This module plays that role for the
jax.distributed runtime:

* :func:`launch` — spawn one process per rank (locally, or attach to a
  ``machines`` list), negotiate a free coordinator port, shard the data,
  run :func:`..launcher.train_multihost` everywhere, return rank 0's
  Booster.
* :class:`TPULGBMClassifier` / :class:`TPULGBMRegressor` /
  :class:`TPULGBMRanker` — distributed sklearn estimators
  (reference DaskLGBMClassifier/Regressor/Ranker dask.py:1113,1316,1483):
  ``fit`` routes through :func:`launch`, everything else (predict,
  attributes) is the plain in-process estimator surface on the returned
  model.

Worker protocol: the parent writes one npz shard + a JSON job spec per
rank into a scratch directory and starts
``python -m lightgbm_tpu.parallel.cluster <spec.json>``; rank 0 writes the
trained model text back.  No environment variables need to be set by the
caller — rank, coordinator and device flags travel in the spec (the
reference's Dask layer likewise hides machines/ports from the user).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
from typing import Any, Dict, Optional, Sequence

import numpy as np

from ..obs.events import emit_event
from ..obs.metrics import count_event
from ..utils import log


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _machines_to_worker_map(machines: Optional[str], n_workers: int,
                            local_listen_port: int) -> list:
    """Rank -> "host:port" assignment (reference dask.py:374).

    With ``machines=None`` every rank runs locally on a fresh free port;
    with a machines list, entries are assigned to ranks in order (missing
    ports filled from ``local_listen_port``)."""
    if machines:
        hosts = [e.strip() for e in machines.split(",") if e.strip()]
        if len(hosts) < n_workers:
            log.fatal(f"machines lists {len(hosts)} entries for "
                      f"{n_workers} workers")
        return [h if ":" in h else f"{h}:{local_listen_port + i}"
                for i, h in enumerate(hosts[:n_workers])]
    return [f"127.0.0.1:{_free_port()}" for _ in range(n_workers)]


def _shard_rows(n: int, n_workers: int, group: Optional[np.ndarray]) -> list:
    """Per-rank (row_indices, group_sizes) covers; ranking data stripes
    whole queries (a query's rows must stay on one rank).  The single
    source of the striping rule — worker payloads reuse its output."""
    if group is not None and len(group):
        sizes = np.asarray(group, np.int64)
        qid_of_row = np.repeat(np.arange(sizes.shape[0]), sizes)
        out = []
        for r in range(n_workers):
            keep_q = np.arange(sizes.shape[0]) % n_workers == r
            out.append((np.flatnonzero(keep_q[qid_of_row]), sizes[keep_q]))
        return out
    return [(np.arange(r, n, n_workers), None) for r in range(n_workers)]


#: default seconds the startup barrier (every rank through
#: launcher.initialize) may take before the attempt is classified a
#: startup failure and retried; bounded so a hung coordinator
#: negotiation does not burn the whole job deadline per attempt.
#: Large pods with slow multi-host initialize can raise it via the
#: ``startup_window_s`` kwarg of :func:`launch`.
STARTUP_WINDOW_S = 300.0


def _resolve_timeout(params: Dict[str, Any], timeout_s: Optional[float]
                     ) -> float:
    """Worker deadline: explicit ``timeout_s`` kwarg wins, else the
    ``cluster_timeout_s`` param (or its ``cluster_timeout`` alias),
    else 3600 s."""
    if timeout_s is not None:
        return float(timeout_s)
    raw = params.get("cluster_timeout_s",
                     params.get("cluster_timeout", 0))
    try:
        v = float(raw or 0)
    except (TypeError, ValueError):
        v = 0.0
    return v if v > 0 else 3600.0


def _log_tail(path: str, limit: int = 2000) -> str:
    try:
        with open(path, "rb") as fh:
            fh.seek(max(0, os.path.getsize(path) - limit))
            return fh.read().decode(errors="replace")
    except OSError as e:
        return f"<log unreadable: {e}>"


# ---------------------------------------------------------------------------
# shared spawn/barrier plumbing — used by the training cluster below AND
# the serving fleet (serving/fleet.py), which runs the same
# spec-file + subprocess + ready-marker protocol for its replicas
# ---------------------------------------------------------------------------

def worker_env(devices_per_worker: int = 0) -> Dict[str, str]:
    """Environment for a spawned worker process.

    The parent's environment, ``PYTHONPATH`` included (user entries that
    make ``lightgbm_tpu`` importable must survive).  With
    ``devices_per_worker > 0`` the virtual-device XLA flags are set here
    because they MUST land before the worker imports jax (package import
    runs at interpreter start, before any worker main executes)."""
    env = dict(os.environ)
    if devices_per_worker > 0:
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{devices_per_worker}").strip()
        env["JAX_PLATFORMS"] = "cpu"
    return env


def spawn_worker(module: str, spec_path: str, log_path: str, *,
                 devices_per_worker: int = 0):
    """Spawn ``python -m <module> <spec_path>`` with :func:`worker_env`.

    Returns ``(proc, log_file)``.  Worker output goes to a per-worker
    log FILE, never a pipe: a worker blocking on a full 64KB stdout pipe
    mid-collective would deadlock the job.  The opened log handle is
    closed on a failed spawn; the ``OSError`` propagates."""
    env = worker_env(devices_per_worker)
    lf = open(log_path, "wb")
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", module, spec_path],
            env=env, stdout=lf, stderr=subprocess.STDOUT)
    except OSError:
        lf.close()
        raise
    return proc, lf


def wait_for_markers(paths: Sequence[str], timeout_s: float, *,
                     alive=None, poll_s: float = 0.05) -> bool:
    """Bounded startup barrier: poll until every marker file in
    ``paths`` exists.  ``alive()`` (optional) is consulted each pass and
    aborts the wait early when it returns False — a spawned process that
    already died will never write its marker, and waiting out the full
    window for it only delays the failure report.  Returns True when all
    markers landed within ``timeout_s``."""
    import time as _time
    deadline = _time.monotonic() + float(timeout_s)
    while _time.monotonic() < deadline:
        if all(os.path.exists(p) for p in paths):
            return True
        if alive is not None and not alive():
            return False
        _time.sleep(max(0.005, float(poll_s)))
    return all(os.path.exists(p) for p in paths)


def launch(params: Dict[str, Any], data, label=None, *,
           weight: Optional[np.ndarray] = None,
           group: Optional[np.ndarray] = None,
           num_boost_round: int = 100,
           n_workers: int = 2,
           machines: Optional[str] = None,
           local_listen_port: int = 12400,
           devices_per_worker: int = 0,
           timeout_s: Optional[float] = None,
           startup_retries: int = 2,
           startup_window_s: Optional[float] = None,
           faults: Sequence = ()):
    """Run data-parallel training across ``n_workers`` fresh processes and
    return the trained Booster (identical on every rank; rank 0's copy).

    ``data`` may be a [n, F] array (the parent shards rows, ranking data
    by whole queries) or a text-file path (every worker loads its own
    stripe via ``load_rank_shard`` — nothing is shipped).
    ``devices_per_worker`` > 0 forces that many virtual CPU devices per
    worker (the CI configuration; leave 0 to inherit real accelerators).

    Robustness (docs/ROBUSTNESS.md): each worker drops a ready marker
    once it clears the distributed startup barrier.  A crash or hang
    BEFORE every marker exists is a startup failure and is retried with
    backoff up to ``startup_retries`` times (fresh processes, fresh
    logs); a failure after the barrier is a training failure and fails
    fast.  Either way the raised error names the dead/stuck worker ranks
    and carries their log tails.  ``timeout_s=None`` resolves from the
    ``cluster_timeout_s`` param (default 3600 s);
    ``startup_window_s=None`` gives the barrier min(STARTUP_WINDOW_S,
    timeout_s) seconds — raise it for pods with slow multi-host
    initialization.

    Elastic mode (``elastic=on`` in params, docs/ROBUSTNESS.md): workers
    publish per-round heartbeats (robustness/elastic.py markers) and
    rank 0 drops an atomic model snapshot every ``checkpoint_interval``
    rounds.  A post-barrier worker death — or a rank whose heartbeats go
    silent past ``heartbeat_timeout_s`` while its peers advance — is
    EVICTED instead of fatal: the parent re-shards the rows over the
    survivors, bumps the coordination epoch and relaunches them from the
    newest snapshot.  A lagging-but-alive rank only draws a warning and
    the ``elastic_slow_worker_rounds`` counter.  With ``elastic=off``
    (default) the pre-elastic fail-fast behavior is preserved verbatim.
    ``faults`` takes :class:`~..robustness.faults.FaultSpec` entries
    applied (first epoch only) by the matching worker — the scripted
    fault drill's injection channel.
    """
    import time as _time

    from ..basic import Booster
    from ..obs import events as obs_events

    # the parent owns the run-level observability artifacts: its journal
    # (at the configured event_output) carries the coordinator's view —
    # heartbeat suspicion/death, evictions, reshapes, resumes — while
    # each worker writes its own per-rank journal/trace next to the
    # configured paths (see _write_specs); after a successful run the
    # per-rank traces are merged back into the configured trace_output
    trace_base = str(params.get("trace_output", "") or "")
    event_base = str(params.get("event_output", "") or "")

    timeout_s = _resolve_timeout(params, timeout_s)
    elastic_on = str(params.get("elastic", "off") or "off") \
        .strip().lower() == "on"
    hb_cfg = {
        "interval": float(params.get("heartbeat_interval_s", 5.0) or 5.0),
        "timeout": float(params.get("heartbeat_timeout_s", 30.0) or 30.0),
    }
    # parent-side watchtower: the coordinator is the only process that
    # sees every rank's heartbeat age, so the heartbeat_staleness_s SLO
    # lives here (one instance across epochs/attempts — burn-rate state
    # must survive a reshape to catch slow-burn liveness decay)
    hb_tower = _build_heartbeat_tower(params) if elastic_on else None
    snapshot_every = int(params.get("checkpoint_interval", 5) or 5)
    host_entries = None
    if machines:
        host_entries = [e.strip() for e in machines.split(",")
                        if e.strip()]
    with obs_events.session(event_base), \
            tempfile.TemporaryDirectory(prefix="lgbtpu_cluster_") as tmp:
        X = y = None
        if isinstance(data, (str, os.PathLike)):
            if label is not None or weight is not None or group is not None:
                log.fatal("launch(data=<path>): label/weight/group must "
                          "come from the file (each worker loads its own "
                          "stripe); in-memory arrays would be ignored")
        else:
            X = np.asarray(data, np.float64)
            y = None if label is None else np.asarray(label)

        if startup_window_s is None:
            startup_window_s = STARTUP_WINDOW_S
        # the barrier window never exceeds the job deadline — otherwise a
        # pre-barrier hang would hit the main deadline first and be
        # classified 'runtime' (non-retryable)
        startup_window_s = min(float(startup_window_s), timeout_s)

        snapshot_path = os.path.join(tmp, "elastic_snapshot.txt") \
            if elastic_on else None
        n_live = n_workers
        epoch = 0
        while True:
            worker_map = _machines_to_worker_map(
                ",".join(host_entries) if host_entries else None,
                n_live, local_listen_port)
            specs, spec_dicts = _write_specs(
                tmp, params, data, X, y, weight, group, n_live, epoch,
                worker_map, num_boost_round, devices_per_worker,
                snapshot_path, snapshot_every,
                faults if epoch == 0 else ())
            last_fail = None
            runtime_fail = None
            for attempt in range(startup_retries + 1):
                outcome, detail, bad = _run_attempt(
                    specs, spec_dicts, tmp, timeout_s, startup_window_s,
                    attempt, hb=dict(hb_cfg, dir=tmp, epoch=epoch,
                                     tower=hb_tower)
                    if elastic_on else None)
                if outcome == "ok":
                    if hb_tower is not None:
                        # flush + final evaluate while the parent journal
                        # is still active
                        hb_tower.close()
                    _merge_cluster_outputs(trace_base, event_base)
                    with open(spec_dicts[0]["out_path"]) as fh:
                        return Booster(model_str=fh.read())
                if outcome == "runtime":
                    if not elastic_on or not bad or len(bad) >= n_live:
                        # post-barrier death: retrying would redo a long
                        # train on the same inputs that just failed —
                        # fail fast with the named worker's diagnosis
                        # (today's behavior, kept verbatim for
                        # elastic=off)
                        log.fatal(f"cluster launch failed: {detail}")
                    runtime_fail = (detail, bad)
                    break
                last_fail = detail
                if attempt < startup_retries:
                    delay = 2.0 * (attempt + 1)
                    log.warning(
                        "cluster startup attempt %d/%d failed (%s); "
                        "retrying in %.0f s"
                        % (attempt + 1, startup_retries + 1,
                           detail.splitlines()[0], delay))
                    _time.sleep(delay)
            else:
                log.fatal(f"cluster launch failed after "
                          f"{startup_retries + 1} startup attempts: "
                          f"{last_fail}")
            # ---- elastic recovery: evict, reshape, relaunch survivors
            detail, bad = runtime_fail
            count_event("elastic_evictions", len(bad))
            count_event("elastic_reshapes", 1)
            count_event("elastic_resumes", 1)
            has_snap = snapshot_path and os.path.exists(snapshot_path)
            emit_event("worker_evicted", ranks=sorted(bad), epoch=epoch,
                       detail=detail.splitlines()[0])
            emit_event("mesh_reshape", epoch=epoch, mesh_from=n_live,
                       mesh_to=n_live - len(bad))
            emit_event("training_resumed", epoch=epoch + 1,
                       mesh=n_live - len(bad),
                       from_snapshot=bool(has_snap))
            log.warning(
                "elastic: evicting worker(s) %s (%s); reshaping %d->%d "
                "workers and relaunching from %s"
                % (sorted(bad), detail.splitlines()[0], n_live,
                   n_live - len(bad),
                   "the newest model snapshot" if has_snap
                   else "scratch (no snapshot yet)"))
            if host_entries:
                host_entries = [h for r, h in enumerate(host_entries)
                                if r not in set(bad)]
            n_live -= len(bad)
            epoch += 1


def _merge_cluster_outputs(trace_base: str, event_base: str) -> None:
    """Join the workers' per-rank traces into ONE rank-aligned timeline
    at the configured ``trace_output`` path, overlaying every journal
    (the parent's coordinator view + each rank's own) as instant
    events.  A merge failure degrades to a warning — the per-rank files
    survive for manual inspection either way."""
    if not trace_base:
        return
    from ..obs.merge import find_rank_files, merge_rank_traces
    paths = find_rank_files(trace_base)
    if not paths:
        return
    events_paths = []
    if event_base:
        if os.path.exists(event_base):
            events_paths.append(event_base)
        events_paths.extend(find_rank_files(event_base))
    try:
        merge_rank_traces(paths, out_path=trace_base,
                          events_paths=events_paths)
        log.info(f"merged {len(paths)} per-rank trace(s) into "
                 f"{trace_base!r}")
    except (OSError, ValueError) as e:
        log.warning(f"cluster trace merge into {trace_base!r} failed "
                    f"({type(e).__name__}: {e}); per-rank traces kept")


def _write_specs(tmp: str, params: Dict[str, Any], data, X, y, weight,
                 group, n_workers: int, epoch: int, worker_map: list,
                 num_boost_round: int, devices_per_worker: int,
                 snapshot_path: Optional[str], snapshot_every: int,
                 faults: Sequence):
    """Materialise one epoch's per-rank shards + job specs.  Each epoch
    re-stripes the rows over the CURRENT worker count — the reshape half
    of elastic recovery — and threads the heartbeat/snapshot/fault
    plumbing into the worker specs."""
    from ..obs.merge import rank_file_path
    coordinator = worker_map[0]
    shards = None
    if X is not None:
        shards = _shard_rows(X.shape[0], n_workers, group)
    fault_by_rank = {}
    for f in faults:
        fault_by_rank[int(f.rank)] = {
            "kind": f.kind, "at_round": int(f.at_round),
            "seconds": float(getattr(f, "seconds", 0.0))}
    specs = []        # per-rank spec file paths (worker argv)
    spec_dicts = []   # the same specs, kept in memory for the parent
    for rank in range(n_workers):
        # every worker is its own process with its own clock, so the
        # user's observability outputs become a per-(epoch, rank)
        # namespace NEXT TO the configured path (obs/merge.py naming) —
        # the parent merges traces back into the configured path and
        # overlays the journals after a successful run
        worker_params = {k: v for k, v in params.items()}
        for key in ("trace_output", "telemetry_output", "event_output"):
            base = str(params.get(key, "") or "")
            if base:
                worker_params[key] = rank_file_path(base, epoch, rank)
        spec: Dict[str, Any] = {
            "rank": rank, "num_machines": n_workers,
            "machines": ",".join(worker_map),
            "coordinator": coordinator,
            "params": worker_params,
            "num_boost_round": int(num_boost_round),
            "devices_per_worker": int(devices_per_worker),
            "epoch": int(epoch),
            "out_path": os.path.join(tmp, "model.txt"),
            "ready_path": os.path.join(tmp, f"ready_e{epoch}_{rank}"),
        }
        if snapshot_path:
            spec["hb_dir"] = tmp
            spec["epoch"] = int(epoch)
            spec["snapshot_path"] = snapshot_path
            spec["snapshot_interval"] = int(snapshot_every)
            if rank in fault_by_rank:
                spec["fault"] = fault_by_rank[rank]
        if shards is None:
            spec["data_path"] = str(data)
        else:
            idx, grp_sizes = shards[rank]
            shard_path = os.path.join(tmp, f"shard_e{epoch}_{rank}.npz")
            payload = {"X": X[idx]}
            if y is not None:
                payload["y"] = y[idx]
            if weight is not None:
                payload["w"] = np.asarray(weight)[idx]
            if grp_sizes is not None:
                payload["g"] = grp_sizes
            np.savez(shard_path, **payload)
            spec["shard_path"] = shard_path
        spec_path = os.path.join(tmp, f"spec_e{epoch}_{rank}.json")
        with open(spec_path, "w") as fh:
            json.dump(spec, fh)
        specs.append(spec_path)
        spec_dicts.append(spec)
    return specs, spec_dicts


def _build_heartbeat_tower(params: Dict[str, Any]):
    """Parent-side watchtower over elastic liveness.  The coordinator is
    the only process that sees every rank's heartbeat age, so the
    ``heartbeat_staleness_s`` SLO is evaluated here: each monitor poll
    feeds the max observed age as a rollup gauge, and burn-rate breaches
    land in the parent's journal next to the ``heartbeat_suspect``/
    ``heartbeat_dead`` events.  Returns ``None`` — zero extra work in
    the poll loop — unless ``slo_config`` enables the SLO."""
    from ..obs.slo import SloEvaluator, Watchtower, parse_slo_config
    from ..obs.timeseries import Rollup
    try:
        enabled = parse_slo_config(params.get("slo_config", ""))
    except ValueError:
        enabled = {}    # config layer rejects bad specs before launch
    if "heartbeat_staleness_s" not in enabled:
        return None
    rollup = Rollup(
        window_s=float(params.get("rollup_window_s", 60.0) or 60.0),
        count=count_event)
    evaluator = SloEvaluator(enabled, emit=emit_event, count=count_event)
    evaluator.watch_slo("heartbeat_staleness_s")
    return Watchtower(rollup, slo=evaluator)


def _run_attempt(spec_paths, specs, tmp: str, timeout_s: float,
                 startup_window_s: float, attempt: int, hb=None):
    """One spawn-and-wait pass over all ranks (``specs`` are the parsed
    dicts behind ``spec_paths``).  Returns ``("ok", None, [])``,
    ``("startup", msg, ranks)`` (failure before every rank cleared the
    barrier — retryable) or ``("runtime", msg, ranks)`` (failure after —
    fatal unless elastic recovery claims the named ranks).  The message
    names the failing worker(s) and carries their log tails.

    ``hb`` (elastic mode) is ``{dir, epoch, interval, timeout}``: the
    parent then also reads the workers' per-round heartbeat markers.  A
    rank whose marker is stale past ``interval`` while a peer has
    advanced draws a slow-worker warning (once per lagging round); stale
    past ``timeout`` it is declared dead — killed and reported as a
    runtime failure naming it — since a worker can hang without exiting
    (the drop-heartbeats drill).  A GLOBAL stall trips no eviction: if
    no peer advances either, only the overall deadline applies."""
    import time as _time

    ready_paths = [s["ready_path"] for s in specs]
    for rp in ready_paths:           # markers are per-attempt
        try:
            os.remove(rp)
        except OSError:
            pass
    devices_per_worker = int(specs[0].get("devices_per_worker", 0))

    procs = []
    logs = []
    try:
        for rank, spec_path in enumerate(spec_paths):
            try:
                proc, lf = spawn_worker(
                    "lightgbm_tpu.parallel.cluster", spec_path,
                    os.path.join(tmp, f"worker_{rank}.a{attempt}.log"),
                    devices_per_worker=devices_per_worker)
            except OSError as e:
                return "startup", (f"spawning worker {rank} failed: "
                                   f"{type(e).__name__}: {e}"), [rank]
            logs.append(lf)
            procs.append(proc)

        # poll ALL workers against one shared deadline: the first crash
        # kills the survivors immediately (they would otherwise hang in
        # the distributed barrier until the full timeout) and ITS log is
        # the one surfaced.  The startup barrier gets its own bounded
        # window so a hung negotiation is retryable without burning the
        # whole deadline.
        deadline = _time.monotonic() + timeout_s
        barrier_deadline = _time.monotonic() + startup_window_s
        barrier_passed = False
        fail = None
        startup_failure = False
        bad_ranks: list = []
        hb_t0 = None          # wall clock at barrier pass (grace ref for
        hb_warned = set()     # ranks that never published)
        live = dict(enumerate(procs))
        while live and fail is None:
            if not barrier_passed:
                barrier_passed = all(os.path.exists(rp)
                                     for rp in ready_paths)
            for rank in list(live):
                rc = live[rank].poll()
                if rc is None:
                    continue
                del live[rank]
                if rc != 0:
                    logs[rank].flush()
                    ready = os.path.exists(ready_paths[rank])
                    startup_failure = not ready
                    bad_ranks = [rank]
                    if hb is not None and ready:
                        # a post-barrier process death is the hard form
                        # of heartbeat silence — journal it as the same
                        # lifecycle event the timeout path emits
                        emit_event("heartbeat_dead", rank=rank,
                                   reason="process_exit", exit_code=rc)
                    fail = ("worker %d exited %d %s the startup barrier; "
                            "log tail:\n%s"
                            % (rank, rc,
                               "after" if ready else "before",
                               _log_tail(logs[rank].name)))
            if hb is not None and barrier_passed and live and fail is None:
                # elastic liveness: read the workers' per-round heartbeat
                # markers.  Eviction needs BOTH a stale marker and an
                # advanced peer — a global stall (everyone stuck in one
                # collective) is left to the overall deadline.
                from ..robustness.elastic import (heartbeat_path,
                                                  read_heartbeat)
                if hb_t0 is None:
                    hb_t0 = _time.time()
                now_w = _time.time()
                rounds, stamps = {}, {}
                for r in live:
                    d = read_heartbeat(
                        heartbeat_path(hb["dir"], hb["epoch"], r))
                    if d is not None:
                        rounds[r] = int(d.get("round", -1))
                        stamps[r] = float(d.get("unix_time", hb_t0))
                lead = max(rounds.values()) if rounds else -1
                tower = hb.get("tower")
                if tower is not None and live:
                    # max age across live ranks — the SLO watches the
                    # WORST rank, matching the eviction policy above
                    staleness = max(now_w - stamps.get(r, hb_t0)
                                    for r in live)
                    tower.rollup.observe_gauge("heartbeat_staleness_s",
                                               staleness, t=now_w)
                    tower.evaluate()
                for r in sorted(live):
                    rd = rounds.get(r, -1)
                    if rd >= lead or lead < 0:
                        continue
                    age = now_w - stamps.get(r, hb_t0)
                    if age >= hb["timeout"]:
                        logs[r].flush()
                        startup_failure = False
                        bad_ranks = [r]
                        emit_event("heartbeat_dead", rank=r, round_idx=rd,
                                   reason="heartbeat_timeout",
                                   age_s=round(age, 3),
                                   timeout_s=hb["timeout"])
                        fail = ("worker %d heartbeat silent for %.1fs "
                                "(timeout %.1fs) at round %d while peers "
                                "reached round %d; log tail:\n%s"
                                % (r, age, hb["timeout"], rd, lead,
                                   _log_tail(logs[r].name)))
                        break
                    if age >= hb["interval"] and (r, lead) not in hb_warned:
                        hb_warned.add((r, lead))
                        count_event("elastic_slow_worker_rounds", 1)
                        emit_event("heartbeat_suspect", rank=r,
                                   round_idx=rd, age_s=round(age, 3),
                                   timeout_s=hb["timeout"])
                        log.warning(
                            "elastic: worker %d slow (last heartbeat "
                            "%.1fs ago at round %d; peers at round %d, "
                            "timeout %.1fs) — waiting, not evicting"
                            % (r, age, rd, lead, hb["timeout"]))
            if live and fail is None:
                now = _time.monotonic()
                if not barrier_passed and now > barrier_deadline:
                    stuck = sorted(r for r in live
                                   if not os.path.exists(ready_paths[r]))
                    startup_failure = True
                    bad_ranks = stuck
                    for r in stuck[:2]:
                        logs[r].flush()
                    tails = "\n".join(
                        f"--- worker {r} log tail ---\n"
                        f"{_log_tail(logs[r].name)}" for r in stuck[:2])
                    fail = ("workers %s never reached the startup barrier "
                            "within %.0f s\n%s"
                            % (stuck, startup_window_s, tails))
                elif now > deadline:
                    stuck = sorted(live)
                    bad_ranks = stuck
                    for r in stuck[:2]:
                        logs[r].flush()
                    tails = "\n".join(
                        f"--- worker {r} log tail ---\n"
                        f"{_log_tail(logs[r].name)}" for r in stuck[:2])
                    fail = ("workers %s timed out after %.0f s "
                            "(cluster_timeout_s)\n%s"
                            % (stuck, timeout_s, tails))
                else:
                    _time.sleep(0.2)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for lf in logs:
            lf.close()
    if fail is None:
        if not os.path.exists(specs[0]["out_path"]):
            # every worker exited 0 yet rank 0 never wrote the model —
            # still a failure, diagnosed with rank 0's log instead of
            # leaking a FileNotFoundError from the model read
            return "runtime", ("all workers exited 0 but rank 0 never "
                               "wrote the model; rank 0 log tail:\n"
                               + _log_tail(logs[0].name)), []
        return "ok", None, []
    return ("startup" if startup_failure else "runtime"), fail, bad_ranks


def _worker_main(spec_path: str) -> None:
    """Per-rank entry (the reference's _train_part, dask.py:182-200).

    Device-count/platform env travels in the SPAWN env (set by launch());
    by the time this runs, the package import has already imported jax.
    """
    with open(spec_path) as fh:
        spec = json.load(fh)
    from . import launcher
    from ..obs import events as obs_events, trace as obs_trace

    rank, epoch = int(spec["rank"]), int(spec.get("epoch", 0))
    wp = spec.get("params", {})
    trace_path = str(wp.get("trace_output", "") or "")
    event_path = str(wp.get("event_output", "") or "")
    tele_path = str(wp.get("telemetry_output", "") or "")
    # the recorder starts BEFORE the barrier so initialize time is on the
    # timeline; mark_anchor() right after the barrier releases is what
    # lets the parent's merge put every rank on one clock
    recorder = obs_trace.start(trace_path) if trace_path else None
    if recorder is not None:
        recorder.set_meta(rank=rank, epoch=epoch)
    journal = obs_events.start(event_path, rank=rank) \
        if event_path else None

    launcher.initialize(machines=spec["machines"],
                        num_machines=spec["num_machines"],
                        rank=spec["rank"])
    if recorder is not None:
        recorder.mark_anchor()
    obs_events.emit_event("barrier_release", rank=rank, epoch=epoch)
    rp = spec.get("ready_path")
    if rp:
        # startup-barrier marker: the parent's liveness monitor uses it to
        # tell retryable startup failures from mid-training deaths
        with open(rp, "w") as fh:
            fh.write(str(os.getpid()))
    kwargs: Dict[str, Any] = {}
    if "shard_path" in spec:
        z = np.load(spec["shard_path"])
        data = z["X"]
        kwargs["label"] = z["y"] if "y" in z else None
        if "w" in z:
            kwargs["weight"] = z["w"]
        if "g" in z:
            kwargs["group"] = z["g"]
    else:
        data = spec["data_path"]

    def obs_round(it: int) -> None:
        # incremental per-round observability: a worker killed mid-run
        # (fault drill / real preemption) leaves its trace + telemetry
        # readable up to the last COMPLETED round — the merge and the
        # run report are built from exactly these partials
        if tele_path:
            import time as _time

            from ..obs.metrics import global_metrics
            rec = {"rank": rank, "epoch": epoch, "iteration": it,
                   "unix_time": round(_time.time(), 3),
                   "counters": global_metrics.snapshot()["counters"]}
            try:
                with open(tele_path, "a") as fh:
                    fh.write(json.dumps(rec) + "\n")
            except OSError:
                pass
        if recorder is not None:
            try:
                recorder.export(trace_path)
            except OSError:
                pass

    hb_dir = spec.get("hb_dir")
    if hb_dir:
        # elastic plumbing: per-round heartbeat publishing (+ scripted
        # fault execution for drills), rank-0 model snapshots, and
        # continuation from the parent's newest snapshot after a reshape
        import time as _time

        from ..robustness.elastic import publish_heartbeat
        fault = spec.get("fault")

        def on_round(it: int) -> None:
            obs_round(it)
            if fault:
                kind = fault.get("kind")
                at = int(fault.get("at_round", 0))
                if kind == "kill" and it >= at:
                    # abrupt death — no cleanup, no heartbeat, exactly a
                    # preempted host (parent sees the nonzero exit)
                    os._exit(17)
                if kind == "drop_heartbeats" and it >= at:
                    return
                if kind == "stall" and it == at:
                    _time.sleep(float(fault.get("seconds", 0.0)))
            publish_heartbeat(hb_dir, epoch, rank, it)

        kwargs["on_round"] = on_round
        snap = spec.get("snapshot_path")
        if snap:
            if rank == 0:
                kwargs["snapshot_path"] = snap
                kwargs["snapshot_interval"] = int(
                    spec.get("snapshot_interval", 0))
            if epoch > 0 and os.path.exists(snap):
                with open(snap) as fh:
                    kwargs["init_model_text"] = fh.read()
    elif trace_path or tele_path:
        kwargs["on_round"] = obs_round
    try:
        booster = launcher.train_multihost(
            spec["params"], data, num_boost_round=spec["num_boost_round"],
            **kwargs)
    finally:
        obs_events.stop(journal)
        if recorder is not None:
            try:
                obs_trace.stop(recorder, export_path=trace_path)
            except OSError as e:
                obs_trace.stop(recorder)
                log.warning(f"trace export to {trace_path!r} failed "
                            f"({type(e).__name__}: {e})")
    if spec["rank"] == 0:
        with open(spec["out_path"], "w") as fh:
            fh.write(booster.model_to_string())


class _DistributedMixin:
    """fit() through :func:`launch`; predict stays in-process on the
    trained model (reference DaskLGBM* return plain local predictions
    when given local collections)."""

    def _dist_fit(self, X, y, sample_weight=None, group=None, **launch_kw):
        params = self._train_params()
        self._Booster = launch(params, X, y, weight=sample_weight,
                               group=group, **launch_kw)
        self._n_features = np.asarray(X).shape[1]
        return self


def _estimators():
    from ..sklearn import (LGBMClassifier, LGBMRanker, LGBMRegressor)
    return LGBMClassifier, LGBMRegressor, LGBMRanker


# resolve bases lazily to avoid a circular import at package load
def _make_estimators():
    LGBMClassifier, LGBMRegressor, LGBMRanker = _estimators()

    class TPULGBMClassifier(_DistributedMixin, LGBMClassifier):
        """Distributed classifier (reference DaskLGBMClassifier
        dask.py:1113)."""

        def fit(self, X, y, sample_weight=None, *, n_workers: int = 2,
                machines: Optional[str] = None,
                devices_per_worker: int = 0, **kwargs):
            self._classes = np.unique(np.asarray(y))
            self._n_classes = len(self._classes)
            if self._n_classes > 2:
                log.fatal("TPULGBMClassifier currently supports binary "
                          "targets (multihost multiclass pending)")
            y_enc = np.searchsorted(self._classes, np.asarray(y))
            return self._dist_fit(X, y_enc, sample_weight,
                                  n_workers=n_workers, machines=machines,
                                  devices_per_worker=devices_per_worker,
                                  num_boost_round=self.n_estimators)

    class TPULGBMRegressor(_DistributedMixin, LGBMRegressor):
        """Distributed regressor (reference DaskLGBMRegressor
        dask.py:1316)."""

        def fit(self, X, y, sample_weight=None, *, n_workers: int = 2,
                machines: Optional[str] = None,
                devices_per_worker: int = 0, **kwargs):
            return self._dist_fit(X, y, sample_weight,
                                  n_workers=n_workers, machines=machines,
                                  devices_per_worker=devices_per_worker,
                                  num_boost_round=self.n_estimators)

    class TPULGBMRanker(_DistributedMixin, LGBMRanker):
        """Distributed ranker (reference DaskLGBMRanker dask.py:1483)."""

        def fit(self, X, y, sample_weight=None, group=None, *,
                n_workers: int = 2, machines: Optional[str] = None,
                devices_per_worker: int = 0, **kwargs):
            if group is None:
                log.fatal("TPULGBMRanker.fit requires group=")
            return self._dist_fit(X, y, sample_weight, group=group,
                                  n_workers=n_workers, machines=machines,
                                  devices_per_worker=devices_per_worker,
                                  num_boost_round=self.n_estimators)

    return TPULGBMClassifier, TPULGBMRegressor, TPULGBMRanker


def __getattr__(name):
    if name in ("TPULGBMClassifier", "TPULGBMRegressor", "TPULGBMRanker"):
        cls_map = dict(zip(
            ("TPULGBMClassifier", "TPULGBMRegressor", "TPULGBMRanker"),
            _make_estimators()))
        globals().update(cls_map)
        return cls_map[name]
    raise AttributeError(name)


if __name__ == "__main__":
    _worker_main(sys.argv[1])
