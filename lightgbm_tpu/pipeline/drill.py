"""Pipeline fault-drill child driver (``python -m
lightgbm_tpu.pipeline.drill <spec.json>``).

One invocation = one trainer lifetime against a shared workdir: it
builds the deterministic drifting stream named by the spec, brings up
an in-process ``PredictionServer``, starts a client hammer thread (so
"zero requests fail during any publish" is continuously exercised, not
just asserted at the end), then runs ``ContinuousTrainer`` with
``resume="auto"``.  A ``kill`` spec arms the SIGKILL seam
(robustness/faults.py ``pipeline_kill_hook``): the process nukes ITSELF
at the named boundary commit — a real, uncatchable SIGKILL with no
cleanup, which is exactly what the crash-safety contract must survive.
The parent (tools/fault_drill.py) chains invocations over the same
workdir, killing at each successive boundary, and asserts everything
from the durable artifacts: journal, exports, provenance ledger and the
client observation log this process appends to.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np


def make_drift_stream(seed: int, n_chunks: int, rows_per_chunk: int,
                      n_features: int):
    """Deterministic drifting binary stream: chunk ``i`` draws from a
    decision boundary rotated by ``i`` steps, so fresh chunks shift the
    distribution and a model that keeps learning beats any frozen
    snapshot on current-distribution data.  Returns ``(X, y)`` stacked
    over all chunks (chunk boundaries every ``rows_per_chunk`` rows)."""
    xs, ys = [], []
    for i in range(int(n_chunks)):
        rng = np.random.default_rng(int(seed) * 100003 + i)
        X = rng.normal(size=(int(rows_per_chunk), int(n_features)))
        w = _drift_weights(i, n_chunks, n_features)
        logit = X @ w + 0.25 * np.sin(3.0 * X[:, 0])
        p = 1.0 / (1.0 + np.exp(-logit))
        y = (rng.random(int(rows_per_chunk)) < p).astype(np.float64)
        xs.append(X)
        ys.append(y)
    return np.concatenate(xs, axis=0), np.concatenate(ys, axis=0)


def _drift_weights(i: int, n_chunks: int, n_features: int) -> np.ndarray:
    """Chunk ``i``'s true weight vector: a slow rotation in the first
    two feature dimensions (about a quarter turn over the stream)."""
    theta = 0.5 * np.pi * (i / max(1, int(n_chunks)))
    w = np.zeros(int(n_features))
    w[0] = 1.5 * np.cos(theta)
    w[1 % n_features] = 1.5 * np.sin(theta)
    if n_features > 2:
        w[2] = 0.75
    return w


def _client_hammer(server, name: str, probe: np.ndarray, log_path: str,
                   stop: threading.Event, served: threading.Event) -> None:
    """Continuously serve ``probe`` against the live registry, appending
    one JSONL observation per request.  'No model yet' is a wait, not a
    failure; any exception once a model exists IS a failure — the drill
    asserts zero of those across every publish."""
    with open(log_path, "a") as fh:
        # bounded by the drill's stop event, not a deadline — the hammer
        # must outlive every publish the trainer performs
        while not stop.is_set():  # tpulint: disable=RBS501
            if name not in server.registry.names():
                time.sleep(0.005)
                continue
            try:
                _, version = server.serve(name, probe)
                rec = {"ok": True, "version": int(version)}
            except Exception as e:          # any failure is drill evidence
                rec = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            fh.write(json.dumps(rec) + "\n")
            fh.flush()
            served.set()
            time.sleep(0.002)


def run_spec(spec: dict) -> dict:
    from ..serving.server import PredictionServer
    from .trainer import ContinuousTrainer, ServerTarget

    X, y = make_drift_stream(spec["seed"], spec["num_chunks"],
                             spec["rows_per_chunk"], spec["num_features"])
    server = PredictionServer(params=dict(spec.get("server_params") or {}))
    target = ServerTarget(server)

    stop, served = threading.Event(), threading.Event()
    hammer = None
    if spec.get("client_log"):
        probe = X[:8]
        hammer = threading.Thread(
            target=_client_hammer,
            args=(server, spec["name"], probe, spec["client_log"], stop,
                  served),
            daemon=True)
        hammer.start()

    hook = None
    kill = spec.get("kill")
    if kill and kill["boundary"] == "ingest_stripe":
        # SIGKILL inside the sharded-ingest collect, right after stripe
        # ``stripe``'s commit file lands — a crash point the phase hook
        # cannot reach (it only fires at cycle-boundary commits).  The
        # committed stripe must survive the resume without re-reading.
        from ..io import sharded
        from ..robustness.faults import sharded_stripe_kill_hook
        sharded._stripe_hook = sharded_stripe_kill_hook(
            kill["stripe"], sharded.PASS_COLLECT)
    elif kill:
        from ..robustness.faults import pipeline_kill_hook
        hook = pipeline_kill_hook(kill["boundary"], kill["cycle"])

    trainer = ContinuousTrainer(
        dict(spec["params"]), X, target, label=y, name=spec["name"],
        resume="auto", chunks_per_cycle=int(spec.get("chunks_per_cycle", 1)),
        chunk_rows=int(spec["rows_per_chunk"]), phase_hook=hook)
    try:
        summary = trainer.run(num_cycles=spec.get("num_cycles"))
        if hammer is not None and spec["name"] in server.registry.names():
            # a one-cycle run publishes as it returns: the hammer's first
            # request (it compiles) must land before it is told to stop
            served.wait(timeout=60.0)
    finally:
        stop.set()
        if hammer is not None:
            hammer.join(timeout=5.0)
    return summary


def main(argv) -> int:
    with open(argv[0]) as fh:
        spec = json.load(fh)
    summary = run_spec(spec)
    sys.stdout.write(json.dumps(summary) + "\n")
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main(sys.argv[1:]))
