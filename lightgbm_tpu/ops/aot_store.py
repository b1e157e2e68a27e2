"""Disk-backed ahead-of-time (AOT) executable store.

The serving tier's zero-recompile contract (serving/predictor.py) is
process-scoped: ops/compile_cache.py keeps compiled programs alive
*within* a process, but every respawned fleet replica, fresh
``ContinuousTrainer`` incarnation and cold CLI process still pays the
full ``jaxpr -> MLIR -> XLA`` pipeline to re-create executables that
already existed a moment ago in a sibling process.  This module makes
that cost a one-time event per (program geometry, machine): the first
build serializes the compiled executable
(``jax.experimental.serialize_executable``) into a store directory;
every later process deserializes and calls it with ZERO lowerings and
ZERO backend compiles — warm time becomes O(disk read), which is what
lets a SIGKILLed replica rejoin at process-spawn speed
(serving/fleet.py) and an autoscaled slot come up before the latency
breach it was spawned for has passed.

Store contract:

  * **Keyed on geometry** — the store key is the compile-cache key the
    caller already uses (``ops/compile_cache.py`` ``sig`` /
    ``mesh_signature`` components): pure hashable primitives whose
    ``repr`` is deterministic across processes.  Anchor tokens (process
    identities) never reach the store.  Array *contents* are arguments
    of the compiled program, so two models with identical geometry
    correctly share one artifact.
  * **Fingerprinted, never trusted** — every artifact records the
    (jax version, backend platform, device topology) fingerprint it was
    compiled under.  A mismatching fingerprint is STALE: the artifact
    is evicted and rebuilt live, never loaded (a deserialized
    executable for the wrong topology is undefined behavior, not a
    slow path).
  * **Torn/corrupt-safe** — artifacts are written temp+rename-atomic
    with an fsync, carry a sha256 in a sidecar meta file, and every
    load re-verifies it.  Any failure (torn pair, bad hash, unpickle
    error) degrades to a live lowering with a warning and an
    ``aot_store_stale_evictions`` bump — never a crash (the
    utils/paths.py failure-path contract).
  * **Counted** — ``aot_store_hits`` / ``aot_store_misses`` /
    ``aot_store_stale_evictions`` / ``aot_store_writes``
    (obs/metrics.py) plus the ``aot_store_miss`` journal event, so a
    cold warm that unexpectedly lowered is visible in the journal.

Layout under the store root (persisted next to the ``FleetRegistry``
manifest by serving/fleet.py, under the pipeline workdir by
pipeline/trainer.py)::

    aot_store.json        store header (format version) — the marker
                          tools/checkpoint_inspect.py detects stores by
    <keyhash>.aotx        pickled (payload, in_tree, out_tree) triple
    <keyhash>.json        sidecar meta: key repr, sha256, fingerprint
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

from ..obs.metrics import MetricsRegistry, count_event
from ..utils import log
from ..utils.paths import check_output_path, write_atomic

#: store header file name — presence marks a directory as an AOT store
HEADER_NAME = "aot_store.json"

#: artifact / sidecar-meta suffixes
ARTIFACT_SUFFIX = ".aotx"
META_SUFFIX = ".json"

#: bumped when the artifact encoding changes; readers refuse unknown
#: formats the same way they refuse stale fingerprints
FORMAT = 1


#: one store compile at a time has JAX's persistent cache switched off
_FRESH_COMPILE_LOCK = threading.Lock()


@contextlib.contextmanager
def _fresh_compile():
    """JAX's persistent compilation cache out of the way for the block,
    and back as it was after it.  An executable that cache hands back was
    itself deserialised, and XLA:CPU serialises such a one into an
    artifact that loads and then fails at its first call (``NOT_FOUND:
    Function <name> not found``): the store keeps only what was compiled
    for it.  It IS the disk tier of the programs it holds, so nothing is
    compiled twice for want of the other cache.  (The switch is the
    process's: a compile on another thread meanwhile misses that cache
    once, no more.)"""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    with _FRESH_COMPILE_LOCK:
        # (jax's own config, not this package's: hence the three waivers)
        was = jax.config.jax_enable_compilation_cache  # tpulint: disable=CFG201
        jax.config.update(  # tpulint: disable=CFG201
            "jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()     # drops the memoised "in use"
        try:
            yield
        finally:
            jax.config.update(  # tpulint: disable=CFG201
                "jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def runtime_fingerprint() -> Dict[str, Any]:
    """The (jax version, backend platform, device topology) triple an
    artifact is only valid under.  JSON-stable: lists of primitives,
    so the round-trip through the sidecar meta compares ``==``."""
    import jax
    return {
        "jax": str(jax.__version__),
        "backend": str(jax.default_backend()),
        "topology": [[str(d.platform),
                      str(getattr(d, "device_kind", "")), int(d.id)]
                     for d in jax.devices()],
    }


def key_hash(key: Hashable) -> str:
    """Stable artifact name for a compile-cache geometry key.  Keys are
    nested tuples of primitives (ops/compile_cache.py ``sig`` output),
    whose ``repr`` is deterministic across processes and pythons."""
    return hashlib.sha256(repr(key).encode("utf-8")).hexdigest()[:40]


def is_aot_store(path: str) -> bool:
    """Does ``path`` hold an AOT store header?"""
    return os.path.isfile(os.path.join(str(path), HEADER_NAME))


def _atomic_bytes(path: str, payload: bytes) -> None:
    write_atomic(path, payload)


class AOTStore:
    """One store directory: load/save of serialized compiled
    executables, verified on every read.  Thread-safe (one lock around
    the filesystem mutations; loads are lock-free reads of immutable,
    atomically-renamed files)."""

    def __init__(self, root: str,
                 metrics: Optional[MetricsRegistry] = None,
                 probe: bool = True) -> None:
        self.root = str(root)
        self.metrics = metrics
        #: all writes route through the shared utils/paths.py probe —
        #: an unwritable store degrades saving to a warning (loads are
        #: still attempted: a read-only prewarmed store is legitimate)
        self.writable = check_output_path(self.root, key="aot_store",
                                          kind="dir") if probe else True
        self._fp = runtime_fingerprint()
        self._lock = threading.Lock()
        self._serialize_broken = False
        if self.writable:
            header = os.path.join(self.root, HEADER_NAME)
            if not os.path.isfile(header):
                try:
                    _atomic_bytes(header, json.dumps(
                        {"format": FORMAT,
                         "created_unix": time.time()}).encode())
                except OSError as e:
                    log.warning(f"aot_store: cannot write store header "
                                f"under {self.root!r} ({e}); store "
                                "disabled for writes")
                    self.writable = False

    # ------------------------------------------------------------ paths
    def _artifact_path(self, h: str) -> str:
        return os.path.join(self.root, h + ARTIFACT_SUFFIX)

    def _meta_path(self, h: str) -> str:
        return os.path.join(self.root, h + META_SUFFIX)

    # ------------------------------------------------------------- load
    def load(self, key: Hashable) -> Optional[Callable]:
        """Deserialize the executable stored for ``key``; None on any
        miss/stale/corrupt condition (the caller then builds live).
        Stale (wrong fingerprint/format) and corrupt (bad sha, torn
        pair, unpickle failure) artifacts are EVICTED, warned about and
        counted on ``aot_store_stale_evictions`` — never loaded, never
        a crash."""
        h = key_hash(key)
        art, meta_p = self._artifact_path(h), self._meta_path(h)
        meta = self._read_meta(meta_p)
        payload = self._read_bytes(art)
        if meta is None and payload is None:
            self._miss(h, "absent")
            return None
        if meta is None or payload is None:
            self._evict(h, "torn artifact/meta pair")
            self._miss(h, "torn")
            return None
        if int(meta.get("format", -1)) != FORMAT:
            self._evict(h, f"unknown format {meta.get('format')!r}")
            self._miss(h, "format")
            return None
        if meta.get("fingerprint") != self._fp:
            self._evict(
                h, "stale fingerprint (backend/jax-version/topology "
                f"changed: stored {meta.get('fingerprint')!r}, "
                f"running {self._fp!r})")
            self._miss(h, "stale_fingerprint")
            return None
        if hashlib.sha256(payload).hexdigest() != meta.get("sha256"):
            self._evict(h, "artifact sha256 mismatch (corrupt)")
            self._miss(h, "corrupt")
            return None
        try:
            from jax.experimental import serialize_executable
            serialized, in_tree, out_tree = pickle.loads(payload)
            # the devices the executable was compiled for: left to its
            # default, the installed jax loads it onto EVERY device of
            # the backend and a one-device program then asks for one
            # argument shard per device
            import jax
            by_id = {d.id: d for d in jax.devices()}
            ids = meta.get("device_ids")
            fn = serialize_executable.deserialize_and_load(
                serialized, in_tree, out_tree,
                execution_devices=None if ids is None
                else [by_id[i] for i in ids])
        except Exception as e:   # any decode failure = corrupt artifact
            self._evict(h, f"undeserializable ({type(e).__name__}: {e})")
            self._miss(h, "undeserializable")
            return None
        count_event("aot_store_hits", 1, self.metrics)
        return fn

    # ------------------------------------------------------------- save
    def save(self, key: Hashable, compiled: Any) -> bool:
        """Serialize ``compiled`` (a ``jax.stages.Compiled``) under
        ``key``: artifact first, sidecar meta second, both
        temp+rename-atomic — a crash between the two leaves a torn pair
        the loader evicts, never a half-read."""
        if not self.writable or self._serialize_broken:
            return False
        try:
            from jax.experimental import serialize_executable
            payload = pickle.dumps(
                serialize_executable.serialize(compiled),
                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:
            # some backends/executables cannot serialize; warm stays
            # process-local for them, which is the pre-store behavior
            self._serialize_broken = True
            log.warning(f"aot_store: executable serialization "
                        f"unavailable ({type(e).__name__}: {e}); "
                        "store writes disabled for this process")
            return False
        h = key_hash(key)
        import jax
        shardings = jax.tree.leaves((compiled.input_shardings,
                                     compiled.output_shardings))
        meta = {"format": FORMAT, "key": repr(key),
                "sha256": hashlib.sha256(payload).hexdigest(),
                "bytes": len(payload), "fingerprint": self._fp,
                "device_ids": sorted(d.id for d in shardings[0].device_set),
                "unix_time": time.time()}
        try:
            with self._lock:
                _atomic_bytes(self._artifact_path(h), payload)
                _atomic_bytes(self._meta_path(h),
                              json.dumps(meta).encode())
        except OSError as e:
            log.warning(f"aot_store: write of {h} failed ({e}); "
                        "continuing without the artifact")
            return False
        count_event("aot_store_writes", 1, self.metrics)
        return True

    def compile_and_save(self, key: Hashable, fn: Callable,
                         args: Tuple[Any, ...]) -> Callable:
        """AOT-compile ``fn`` at the concrete ``args`` and persist the
        executable.  Returns the compiled executable (so the caller's
        first invocation pays no second trace), or ``fn`` unchanged
        when lowering/serialization is impossible — the live path is
        always the fallback, never an error."""
        try:
            import jax
            with _fresh_compile():
                compiled = jax.jit(fn).lower(*args).compile()
        except Exception as e:
            log.warning(f"aot_store: AOT lowering failed "
                        f"({type(e).__name__}: {e}); using the live "
                        "path for this program")
            return fn
        self.save(key, compiled)
        return compiled

    # ------------------------------------------------------------ admin
    def entries(self) -> List[Dict[str, Any]]:
        """Sidecar meta of every artifact in the store (admin/tools)."""
        out = []
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return []
        for name in names:
            if not name.endswith(ARTIFACT_SUFFIX):
                continue
            h = name[:-len(ARTIFACT_SUFFIX)]
            meta = self._read_meta(self._meta_path(h)) or {}
            meta["key_hash"] = h
            out.append(meta)
        return out

    def __len__(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.root)
                       if n.endswith(ARTIFACT_SUFFIX))
        except OSError:
            return 0

    # -------------------------------------------------------- internals
    def _read_meta(self, path: str) -> Optional[dict]:
        try:
            with open(path) as fh:
                meta = json.load(fh)
            return meta if isinstance(meta, dict) else None
        except (OSError, ValueError):
            return None

    def _read_bytes(self, path: str) -> Optional[bytes]:
        try:
            with open(path, "rb") as fh:
                return fh.read()
        except OSError:
            return None

    def _miss(self, h: str, reason: str) -> None:
        count_event("aot_store_misses", 1, self.metrics)
        from ..obs.events import emit_event
        emit_event("aot_store_miss", key_hash=h, reason=reason)

    def _evict(self, h: str, reason: str) -> None:
        log.warning(f"aot_store: evicting artifact {h} under "
                    f"{self.root!r}: {reason}; falling back to a live "
                    "lowering")
        count_event("aot_store_stale_evictions", 1, self.metrics)
        with self._lock:
            for path in (self._artifact_path(h), self._meta_path(h)):
                try:
                    os.remove(path)
                except OSError:
                    pass


# --------------------------------------------------------------- verify
def find_aot_stores(root: str, max_depth: int = 3) -> List[str]:
    """Store directories under ``root`` (header-file marker), ``root``
    itself included — the discovery hook behind
    ``tools/checkpoint_inspect.py --verify-all``."""
    root = str(root)
    found: List[str] = []
    base_depth = root.rstrip(os.sep).count(os.sep)
    for dirpath, dirnames, filenames in os.walk(root):
        if dirpath.rstrip(os.sep).count(os.sep) - base_depth >= max_depth:
            dirnames[:] = []
            continue
        if HEADER_NAME in filenames:
            found.append(dirpath)
    return sorted(found)


def verify_store(root: str,
                 check_runtime: bool = True) -> Dict[str, Any]:
    """Offline integrity report for one store directory: every
    artifact's sha256 must match its sidecar meta, every meta must
    share ONE fingerprint (a mixed store is stale), and — when jax is
    importable and ``check_runtime`` — that fingerprint must match the
    running backend/version/topology.  ``findings`` lists every torn or
    stale condition; ``valid`` is their absence."""
    root = str(root)
    findings: List[str] = []
    entries: List[Dict[str, Any]] = []
    try:
        names = sorted(os.listdir(root))
    except OSError as e:
        return {"path": root, "valid": False, "artifacts": [],
                "findings": [f"unreadable store dir ({e})"]}
    hashes = {n[:-len(ARTIFACT_SUFFIX)] for n in names
              if n.endswith(ARTIFACT_SUFFIX)}
    metas = {n[:-len(META_SUFFIX)] for n in names
             if n.endswith(META_SUFFIX) and n != HEADER_NAME}
    fingerprints: List[Any] = []
    for h in sorted(hashes | metas):
        art = os.path.join(root, h + ARTIFACT_SUFFIX)
        meta_p = os.path.join(root, h + META_SUFFIX)
        entry: Dict[str, Any] = {"key_hash": h}
        problems: List[str] = []
        meta = None
        if h not in metas:
            problems.append("artifact without sidecar meta (torn)")
        elif h not in hashes:
            problems.append("sidecar meta without artifact (torn)")
        else:
            try:
                with open(meta_p) as fh:
                    meta = json.load(fh)
            except (OSError, ValueError) as e:
                problems.append(f"unreadable meta ({e})")
        if meta is not None:
            entry["bytes"] = meta.get("bytes")
            if int(meta.get("format", -1)) != FORMAT:
                problems.append(
                    f"unknown format {meta.get('format')!r} (stale)")
            try:
                with open(art, "rb") as fh:
                    got = hashlib.sha256(fh.read()).hexdigest()
                if got != meta.get("sha256"):
                    problems.append("sha256 mismatch (torn/corrupt)")
            except OSError as e:
                problems.append(f"unreadable artifact ({e})")
            fingerprints.append(meta.get("fingerprint"))
        entry["valid"] = not problems
        entry["problems"] = problems
        entries.append(entry)
        for p in problems:
            findings.append(f"{h}: {p}")
    distinct = [f for i, f in enumerate(fingerprints)
                if f not in fingerprints[:i]]
    if len(distinct) > 1:
        findings.append(
            f"mixed fingerprints across artifacts ({len(distinct)} "
            "distinct) — store is stale")
    if distinct and check_runtime:
        try:
            fp = runtime_fingerprint()
        except Exception:
            fp = None   # no jax in the inspecting process: skip
        if fp is not None and any(f != fp for f in distinct):
            findings.append(
                "artifact fingerprint differs from the running "
                "backend/jax-version/topology — store is stale here")
    return {"path": root, "valid": not findings, "artifacts": entries,
            "findings": findings}
