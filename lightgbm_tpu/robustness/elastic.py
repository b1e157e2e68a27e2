"""Elastic multi-chip training: detect a silent worker, evict it,
reshape the mesh over the survivors, resume from the newest checkpoint.

Before this module, a post-startup-barrier worker death was terminal:
the cluster layer (parallel/cluster.py) classifies it "runtime" and
fail-fasts the whole job, and the virtual-mesh tiers had no notion of a
worker dying at all.  For long boosting runs on preemptible capacity
that turns one lost host into a full restart.  This module adds the two
missing layers:

  * **Liveness** — each live worker publishes a per-round heartbeat
    marker (:func:`publish_heartbeat`) on the same shared-file substrate
    as the startup-barrier ready markers: a tiny JSON blob written
    atomically (temp + rename, exactly the checkpoint-manifest idiom) to
    the coordination directory.  A :class:`HeartbeatMonitor` reads them
    back and classifies each rank per round:

        ``healthy``  — its marker for the current round has landed;
        ``suspect``  — lagging, but last seen under ``heartbeat_timeout_s``
                       ago: the monitor WAITS (bounded — see
                       :meth:`HeartbeatMonitor.wait_round`), warns once
                       per (rank, round) and bumps the
                       ``elastic_slow_worker_rounds`` counter.  A slow
                       worker is not a dead worker;
        ``dead``     — silent past ``heartbeat_timeout_s``: evicted.

  * **Mesh-reshape recovery** — on eviction (:class:`WorkerEvicted`)
    with ``elastic=on``, the :class:`ElasticSession` drops the dead
    rank, bumps the coordination epoch (fresh marker namespace — a
    stale heartbeat from a zombie cannot alias into the new incarnation),
    rebuilds the device mesh over the survivor window
    (parallel/mesh.py :func:`~..parallel.mesh.device_window` — the
    booster re-pads and re-shards rows through the exact machinery the
    uneven-rows path always used), and resumes from the newest valid
    checkpoint via ``train(resume="auto")``.  With ``elastic=off`` (the
    default) detection still happens but the job fails fast exactly as
    before this module existed.

Bit-identity contract (asserted by tools/fault_drill.py and
tests/test_elastic.py, explained in docs/ROBUSTNESS.md): under the
deterministic quantized config (``use_quantized_grad=true``,
``stochastic_rounding=false``, ``deterministic=true``) every histogram
sum is exact under any reduction order, so training is mesh-size
invariant — the continued run's model text is bit-for-bit identical to
an uninterrupted run at the reduced mesh size AND to the serial run,
even for the rounds trained before the eviction at the larger mesh.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..obs.events import emit_event
from ..obs.metrics import count_event
from ..utils import log
from .faults import FaultSpec

def model_core(text: str) -> str:
    """Model text minus the serialized-parameters trailer.

    Bit-identity comparisons across recovery scenarios must ignore the
    params block: the runs being compared *necessarily* differ in
    bookkeeping keys (``checkpoint_dir`` paths, ``tree_learner``,
    ``elastic``) while their trees/structure — the part that determines
    every prediction — must match byte-for-byte."""
    head, sep, rest = text.partition("parameters:")
    if not sep:
        return text
    _, _, tail = rest.partition("end of parameters")
    return head + tail


HEALTHY = "healthy"
SUSPECT = "suspect"
DEAD = "dead"

#: floor/ceiling for the monitor's poll cadence while waiting on a
#: lagging rank — fine enough to time detection, coarse enough to stay
#: off the filesystem's back
_POLL_MIN_S = 0.01
_POLL_MAX_S = 0.25


# ---------------------------------------------------------------------------
# heartbeat markers (liveness layer)
# ---------------------------------------------------------------------------

def heartbeat_path(coord_dir: str, epoch: int, rank: int) -> str:
    """Marker path for ``rank`` in coordination ``epoch``.  The epoch is
    part of the NAME, not the payload: after a reshape the survivors
    rendezvous on a fresh namespace and stale markers from the previous
    incarnation are simply never read."""
    return os.path.join(coord_dir, f"hb_e{int(epoch)}_r{int(rank)}.json")


def publish_heartbeat(coord_dir: str, epoch: int, rank: int,
                      round_idx: int, now: Optional[float] = None) -> str:
    """Atomically publish ``rank``'s heartbeat for ``round_idx``
    (temp + rename, the checkpoint-manifest idiom: a reader never sees a
    half-written marker, a crashed writer leaves only a ``.tmp`` husk)."""
    os.makedirs(coord_dir, exist_ok=True)
    path = heartbeat_path(coord_dir, epoch, rank)
    payload = {"rank": int(rank), "epoch": int(epoch),
               "round": int(round_idx),
               "unix_time": float(time.time() if now is None else now),
               "pid": os.getpid()}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


def read_heartbeat(path: str) -> Optional[dict]:
    """Parse a heartbeat marker; ``None`` for missing/torn files (a torn
    read is treated as no-news, never as a crash of the MONITOR)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def age_state(age_s: float, *, interval_s: float, timeout_s: float) -> str:
    """Classify a heartbeat by wall-clock AGE alone (serving-replica
    liveness, serving/fleet.py).  Training liveness is round-anchored —
    :meth:`HeartbeatMonitor.classify` calls a marker carrying the
    expected round healthy regardless of age — but serving replicas beat
    on wall time with no round to anchor on, so state is pure staleness:
    fresh under two beat intervals is HEALTHY (one marker may always be
    in flight), silent past ``timeout_s`` is DEAD (evict + respawn), and
    the band between is SUSPECT — deprioritized by the router, not
    evicted."""
    if age_s >= float(timeout_s):
        return DEAD
    if age_s >= 2.0 * float(interval_s):
        return SUSPECT
    return HEALTHY


@dataclass
class LivenessReport:
    """One classification pass over the live ranks at a given round."""
    round_idx: int
    states: Dict[int, str]
    ages: Dict[int, float]

    @property
    def suspect(self) -> List[int]:
        return [r for r, s in self.states.items() if s == SUSPECT]

    @property
    def dead(self) -> List[int]:
        return [r for r, s in self.states.items() if s == DEAD]

    @property
    def all_healthy(self) -> bool:
        return all(s == HEALTHY for s in self.states.values())


class WorkerEvicted(Exception):
    """Raised by the monitor when rank(s) stay silent past
    ``heartbeat_timeout_s``.  Carries enough for the recovery layer (and
    the drill report) to act without re-reading markers."""

    def __init__(self, ranks: Sequence[int], round_idx: int,
                 detect_s: float):
        self.ranks = sorted(int(r) for r in ranks)
        self.round_idx = int(round_idx)
        self.detect_s = float(detect_s)
        super().__init__(
            f"worker(s) {self.ranks} silent past heartbeat timeout at "
            f"round {self.round_idx} (detected after {self.detect_s:.2f}s)")


class HeartbeatMonitor:
    """Reads the heartbeat markers of one coordination epoch and decides
    healthy / suspect / dead per rank.

    The monitor never blocks unboundedly: :meth:`wait_round` polls at
    most ``heartbeat_timeout_s`` of wall time with an explicit attempt
    cap, after which any rank still lagging has by construction aged
    past the timeout and is classified dead.
    """

    def __init__(self, coord_dir: str, ranks: Sequence[int], *,
                 epoch: int = 0, interval_s: float = 5.0,
                 timeout_s: float = 30.0, metrics=None):
        self.coord_dir = coord_dir
        self.ranks = [int(r) for r in ranks]
        self.epoch = int(epoch)
        self.interval_s = float(interval_s)
        self.timeout_s = float(timeout_s)
        self.metrics = metrics
        self.slow_rounds = 0          # (rank, round) pairs seen slow
        self._t0 = time.time()        # grace reference: never-published
        self._warned: set = set()     # (rank, round) warned already

    def classify(self, expect_round: int,
                 now: Optional[float] = None) -> LivenessReport:
        """One non-blocking pass: where is every rank relative to
        ``expect_round``?"""
        now = time.time() if now is None else now
        states: Dict[int, str] = {}
        ages: Dict[int, float] = {}
        for r in self.ranks:
            hb = read_heartbeat(heartbeat_path(self.coord_dir,
                                               self.epoch, r))
            last = float(hb["unix_time"]) if hb else self._t0
            age = max(0.0, now - last)
            ages[r] = age
            if hb is not None and int(hb.get("round", -1)) >= expect_round:
                states[r] = HEALTHY
            elif age >= self.timeout_s:
                states[r] = DEAD
            else:
                states[r] = SUSPECT
        return LivenessReport(expect_round, states, ages)

    def _note_slow(self, report: LivenessReport) -> None:
        for r in report.suspect:
            # only count a rank as SLOW once its silence exceeds the
            # publish interval — below that it is merely "not yet
            # arrived this poll", which every rank transits every round
            if report.ages[r] < self.interval_s:
                continue
            key = (r, report.round_idx)
            if key in self._warned:
                continue
            self._warned.add(key)
            self.slow_rounds += 1
            count_event("elastic_slow_worker_rounds", 1, self.metrics)
            emit_event("heartbeat_suspect", rank=r,
                       round_idx=report.round_idx,
                       age_s=round(report.ages[r], 3),
                       timeout_s=self.timeout_s)
            log.warning(
                f"elastic: worker {r} slow at round {report.round_idx} "
                f"(last heartbeat {report.ages[r]:.2f}s ago, timeout "
                f"{self.timeout_s:.1f}s) — waiting, not evicting")

    def wait_round(self, expect_round: int, *,
                   tick: Optional[Callable[[], None]] = None,
                   sleep: Callable[[float], None] = time.sleep
                   ) -> LivenessReport:
        """Block (boundedly) until every rank has published
        ``expect_round`` or someone ages past the timeout.

        ``tick`` is called once per poll — the in-process session uses
        it to service scheduled deferred publishes (stall faults); the
        cluster parent passes the child-process liveness probe.

        Raises :class:`WorkerEvicted` for ranks classified dead.  The
        wait is bounded twice over: a wall-clock deadline of
        ``timeout_s`` past entry plus an explicit attempt cap, so a
        frozen clock cannot spin it forever.
        """
        # the wait's own deadline/elapsed arithmetic runs on the
        # monotonic clock (immune to wall steps); only marker AGING
        # (classify) uses wall time, the one clock all hosts share
        t_enter = time.monotonic()
        poll = min(max(self.interval_s / 10.0, _POLL_MIN_S), _POLL_MAX_S)
        max_attempts = int(self.timeout_s / poll) + 2
        deadline = t_enter + self.timeout_s + poll
        if tick is not None:
            tick()
        report = self.classify(expect_round)
        attempts = 0
        while (not report.all_healthy and not report.dead
               and attempts < max_attempts
               and time.monotonic() < deadline):
            self._note_slow(report)
            sleep(poll)
            attempts += 1
            if tick is not None:
                tick()
            report = self.classify(expect_round)
        if not report.all_healthy and not report.dead:
            # deadline/attempts exhausted with ranks still lagging: by
            # construction they have aged past timeout_s — reclassify so
            # the two bounds agree on the verdict
            report = self.classify(expect_round,
                                   now=time.time() + self.timeout_s)
        if report.dead:
            for r in report.dead:
                emit_event("heartbeat_dead", rank=r,
                           round_idx=expect_round,
                           age_s=round(report.ages.get(r, -1.0), 3),
                           timeout_s=self.timeout_s)
            raise WorkerEvicted(report.dead, expect_round,
                                time.monotonic() - t_enter)
        return report


# ---------------------------------------------------------------------------
# elastic session (mesh-reshape recovery layer)
# ---------------------------------------------------------------------------

@dataclass
class _EvictionRecord:
    ranks: List[int]
    round_idx: int
    detect_s: float
    epoch: int


@dataclass
class ElasticReport:
    """What a session did — the drill (tools/fault_drill.py) serializes
    this into its JSON report."""
    epochs: List[dict] = field(default_factory=list)
    evictions: List[dict] = field(default_factory=list)
    slow_rounds: int = 0
    resumes: int = 0
    final_mesh: int = 0

    def to_dict(self) -> dict:
        return {"epochs": self.epochs, "evictions": self.evictions,
                "slow_rounds": self.slow_rounds, "resumes": self.resumes,
                "final_mesh": self.final_mesh}


class ElasticSession:
    """In-process elastic trainer over the virtual mesh.

    Each live *virtual worker* owns one device slot of the mesh; worker
    ``r``'s liveness is represented by its per-round heartbeat marker.
    The session trains through the ordinary engine
    (``train(resume="auto")`` + checkpoints), with one extra callback
    that (a) publishes every live rank's heartbeat after each round —
    applying any scripted :class:`~.faults.FaultSpec` — and (b) runs the
    monitor's bounded wait.  A dead rank surfaces as
    :class:`WorkerEvicted` aborting the epoch mid-run, exactly where a
    real collective would have hung; recovery then reshapes and resumes.

    This is the layer the bit-identity drills run against.  The real
    multi-process cluster (parallel/cluster.py) reuses the same markers,
    monitor and config keys, but its recovery restarts workers from the
    rank-0 model snapshot rather than the full engine checkpoint — see
    docs/ROBUSTNESS.md for the contract each tier carries.
    """

    def __init__(self, params: dict, X, y, *, num_boost_round: int,
                 n_workers: int, workdir: str,
                 faults: Sequence[FaultSpec] = (),
                 callbacks: Optional[list] = None):
        from ..config import Config
        self.params = dict(params)
        self.params.setdefault("checkpoint_dir",
                               os.path.join(workdir, "ckpt"))
        cfg = Config(dict(self.params))
        self.interval_s = float(cfg.heartbeat_interval_s)
        self.timeout_s = float(cfg.heartbeat_timeout_s)
        self.elastic_on = str(cfg.elastic) == "on"
        # the SESSION owns the observability artifacts, not the inner
        # train() runs: one trace/journal must span every epoch, or the
        # eviction/reshape/resume events emitted BETWEEN epochs would be
        # dropped and each epoch's export would overwrite the last
        self.trace_output = str(getattr(cfg, "trace_output", "") or "")
        self.event_output = str(getattr(cfg, "event_output", "") or "")
        self.X, self.y = X, y
        self.num_boost_round = int(num_boost_round)
        self.n_workers = int(n_workers)
        self.coord_dir = os.path.join(workdir, "coord")
        self.faults = list(faults)
        self.user_callbacks = list(callbacks or [])
        self.report = ElasticReport()
        # stall faults become deferred publishes: (due_time, epoch,
        # rank, round); flushed by the monitor's per-poll tick
        self._deferred: List[Tuple[float, int, int, int]] = []

    # -- fault plan -----------------------------------------------------

    def _publish_or_fault(self, epoch: int, rank: int,
                          round_idx: int) -> None:
        for f in self.faults:
            if f.rank != rank:
                continue
            if f.kind in ("kill", "drop_heartbeats") \
                    and round_idx >= f.at_round:
                return      # silent from at_round on
            if f.kind == "stall" and round_idx == f.at_round:
                self._deferred.append(
                    (time.time() + f.seconds, epoch, rank, round_idx))
                return      # lands late, via _flush_deferred
        publish_heartbeat(self.coord_dir, epoch, rank, round_idx)

    def _flush_deferred(self) -> None:
        now = time.time()
        due = [d for d in self._deferred if d[0] <= now]
        self._deferred = [d for d in self._deferred if d[0] > now]
        for _, epoch, rank, round_idx in due:
            publish_heartbeat(self.coord_dir, epoch, rank, round_idx)

    def _survivors(self, live: List[int], dead: List[int]) -> List[int]:
        out = [r for r in live if r not in set(dead)]
        if not out:
            log.fatal("elastic: every worker evicted — no survivor set "
                      "to reshape onto")
        return out

    # -- per-epoch callback --------------------------------------------

    def _liveness_callback(self, live: List[int],
                           monitor: HeartbeatMonitor) -> Callable:
        epoch = monitor.epoch

        def _callback(env) -> None:
            for r in live:
                self._publish_or_fault(epoch, r, env.iteration)
            monitor.wait_round(env.iteration, tick=self._flush_deferred)
        # after the checkpoint callback (order 40): a kill detected on a
        # checkpoint round must not roll back that round's snapshot
        _callback.order = 60
        return _callback

    # -- the epoch loop -------------------------------------------------

    def train(self):
        """Run to ``num_boost_round`` rounds, reshaping through as many
        evictions as the fault plan (or real silence) produces.  Returns
        the final Booster; ``self.report`` holds the drill telemetry."""
        from ..obs import events as obs_events, trace as obs_trace
        from ..utils.paths import check_output_path
        trace_path = self.trace_output
        if trace_path and obs_trace.active() is None and \
                not check_output_path(trace_path, key="trace_output"):
            trace_path = ""
        event_path = self.event_output
        if event_path and obs_events.active() is None and \
                not check_output_path(event_path, key="event_output"):
            event_path = ""
        recorder = obs_trace.start(trace_path) if trace_path else None
        journal = obs_events.start(event_path) if event_path else None
        try:
            return self._train_epochs()
        finally:
            obs_events.stop(journal)
            try:
                obs_trace.stop(recorder, export_path=trace_path or None)
            except OSError as e:
                obs_trace.stop(recorder)
                log.warning(f"trace export to {trace_path!r} failed "
                            f"({type(e).__name__}: {e}); trace discarded")

    def _train_epochs(self):
        from ..basic import Dataset
        from ..engine import train as _train
        from ..parallel.mesh import device_window
        from ..utils.timer import phase

        live = list(range(self.n_workers))
        epoch = 0
        while True:
            monitor = HeartbeatMonitor(
                self.coord_dir, live, epoch=epoch,
                interval_s=self.interval_s, timeout_s=self.timeout_s)
            cbs = self.user_callbacks + [
                self._liveness_callback(live, monitor)]
            self.report.epochs.append(
                {"epoch": epoch, "mesh": len(live), "ranks": list(live)})
            try:
                # each epoch is a nested scope on the merged timeline:
                # the reshape boundary shows as a span break
                with phase("elastic_epoch", epoch=epoch,
                           mesh=len(live)), \
                        device_window(len(live)):
                    ds = Dataset(self.X, label=self.y)
                    booster = _train(dict(self.params), ds,
                                     num_boost_round=self.num_boost_round,
                                     callbacks=cbs, resume="auto")
                self.report.slow_rounds = monitor.slow_rounds
                self.report.final_mesh = len(live)
                return booster
            except WorkerEvicted as ev:
                self.report.slow_rounds += monitor.slow_rounds
                if not self.elastic_on:
                    # elastic=off: detection exists, recovery does not —
                    # today's fail-fast contract, verbatim
                    log.fatal(
                        f"worker(s) {ev.ranks} lost at round "
                        f"{ev.round_idx} and elastic=off: failing fast "
                        "(set elastic=on to evict and resume)")
                survivors = self._survivors(live, ev.ranks)
                count_event("elastic_evictions", len(ev.ranks))
                count_event("elastic_reshapes", 1)
                count_event("elastic_resumes", 1)
                emit_event("worker_evicted", round_idx=ev.round_idx,
                           ranks=list(ev.ranks), epoch=epoch,
                           detect_s=round(ev.detect_s, 3))
                emit_event("mesh_reshape", round_idx=ev.round_idx,
                           epoch=epoch, mesh_from=len(live),
                           mesh_to=len(survivors))
                emit_event("training_resumed", round_idx=ev.round_idx,
                           epoch=epoch + 1, mesh=len(survivors))
                self.report.evictions.append(
                    {"ranks": ev.ranks, "round": ev.round_idx,
                     "detect_s": round(ev.detect_s, 3), "epoch": epoch})
                self.report.resumes += 1
                log.warning(
                    f"elastic: evicting worker(s) {ev.ranks} (silent at "
                    f"round {ev.round_idx}, detected in "
                    f"{ev.detect_s:.2f}s); reshaping mesh "
                    f"{len(live)}->{len(survivors)} and resuming from "
                    "the newest checkpoint")
                # faults against evicted ranks are spent; survivors keep
                # theirs (a stall can straddle a reshape)
                self.faults = [f for f in self.faults
                               if f.rank in survivors]
                live = survivors
                epoch += 1


def run_elastic_training(params: dict, X, y, *, num_boost_round: int,
                         n_workers: int, workdir: str,
                         faults: Sequence[FaultSpec] = (),
                         callbacks: Optional[list] = None):
    """Convenience wrapper: build an :class:`ElasticSession`, train,
    return ``(booster, report_dict)``."""
    session = ElasticSession(params, X, y,
                             num_boost_round=num_boost_round,
                             n_workers=n_workers, workdir=workdir,
                             faults=faults, callbacks=callbacks)
    booster = session.train()
    return booster, session.report.to_dict()
