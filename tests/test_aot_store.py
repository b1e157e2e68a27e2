"""Disk-backed AOT executable store tests (lightgbm_tpu/ops/aot_store.py).

The PR16 contract under test:

  * round trip — a ``jax.jit(...).lower(...).compile()`` executable
    serialized into the store loads back (same process AND a fresh one)
    and computes identical outputs, with the load firing ZERO
    ``xla_program_lowerings``;
  * staleness — an artifact whose runtime fingerprint (backend / jax
    version / device topology) does not match the running process is
    NEVER loaded: it is evicted (``aot_store_stale_evictions``) and the
    program is rebuilt live;
  * poison — a corrupt or truncated artifact degrades to a live
    lowering with a warning, never a crash (sha256 catches bit rot; a
    sha-valid-but-unloadable blob is caught at deserialize);
  * probe — store writes route through the utils/paths.py writability
    probe: an unwritable root degrades the feature, it does not raise;
  * the serving tier — ``PredictionServer`` with ``aot_store=`` warms
    its whole bucket ladder from a populated store with zero XLA
    lowerings in a FRESH process (the respawn cold-start contract),
    and ``tools/checkpoint_inspect.py`` verifies store integrity.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import compile_events
from lightgbm_tpu.obs.metrics import global_metrics
from lightgbm_tpu.ops import compile_cache as cc
from lightgbm_tpu.ops.aot_store import (ARTIFACT_SUFFIX, META_SUFFIX,
                                        AOTStore, find_aot_stores,
                                        is_aot_store, key_hash,
                                        runtime_fingerprint, verify_store)
from lightgbm_tpu.serving import PredictionServer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counter(name):
    return int(global_metrics.counter(name))


def _toy(a, b):
    return a @ b + 1.0


def _toy_args():
    import jax.numpy as jnp
    return (jnp.asarray(np.arange(16, dtype=np.float32).reshape(4, 4)),
            jnp.asarray(np.ones((4, 4), np.float32)))


# ------------------------------------------------------------- round trip
def test_store_round_trip_and_counters(tmp_path):
    store = AOTStore(str(tmp_path / "s"))
    assert store.writable
    assert is_aot_store(str(tmp_path / "s"))
    args = _toy_args()
    key = ("toy", cc.sig(args))
    writes0 = _counter("aot_store_writes")
    compiled = store.compile_and_save(key, _toy, args)
    assert _counter("aot_store_writes") == writes0 + 1
    assert len(store) == 1

    # a second store over the same directory is a fresh reader
    hits0 = _counter("aot_store_hits")
    loaded = AOTStore(str(tmp_path / "s")).load(key)
    assert loaded is not None
    assert _counter("aot_store_hits") == hits0 + 1
    np.testing.assert_array_equal(np.asarray(loaded(*args)),
                                  np.asarray(compiled(*args)))
    np.testing.assert_array_equal(np.asarray(loaded(*args)),
                                  np.asarray(_toy(*args)))


def test_the_store_keeps_a_fresh_compile_not_the_persistent_caches_copy(
        tmp_path):
    """JAX's persistent compilation cache holds the program already (a
    compile of over a second puts it there, and under six loaded workers a
    tiny one takes that long): what ``compile()`` then hands back was
    itself deserialised, and XLA:CPU serialises such an executable into an
    artifact that loads and fails at its first call (``NOT_FOUND:
    Function ... not found``; the driver's PR 45 run of
    ``tests/test_pipeline.py``).  The store compiles with that cache out of
    the way and puts it back as it was."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "xla"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        args = _toy_args()
        shifted = lambda a, b: _toy(a, b) * 3.0 - a    # this test's program
        want = np.asarray(jax.jit(shifted)(*args))     # ... in the cache
        assert os.listdir(tmp_path / "xla")
        jax.clear_caches()
        store = AOTStore(str(tmp_path / "s"))
        key = ("shifted", cc.sig(args))
        store.compile_and_save(key, shifted, args)
        # ... and the cache takes the next program as it did before
        held = len(os.listdir(tmp_path / "xla"))
        jax.jit(lambda a: a * 5.0 + 2.0)(args[0])
        assert len(os.listdir(tmp_path / "xla")) > held
        loaded = AOTStore(str(tmp_path / "s")).load(key)
        np.testing.assert_array_equal(np.asarray(loaded(*args)), want)
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def test_store_miss_reasons_and_events(tmp_path):
    store = AOTStore(str(tmp_path / "s"))
    args = _toy_args()
    misses0 = _counter("aot_store_misses")
    assert store.load(("absent", cc.sig(args))) is None
    assert _counter("aot_store_misses") == misses0 + 1


def test_stale_fingerprint_never_loaded(tmp_path):
    """Wrong backend/version/topology fingerprint -> evicted, never
    loaded, rebuilt live."""
    root = str(tmp_path / "s")
    store = AOTStore(root)
    args = _toy_args()
    key = ("toy", cc.sig(args))
    store.compile_and_save(key, _toy, args)
    h = key_hash(key)
    meta_path = os.path.join(root, h + META_SUFFIX)
    meta = json.loads(open(meta_path).read())
    meta["fingerprint"] = {"jax": "0.0.0", "backend": "nonsense",
                           "topology": []}
    with open(meta_path, "w") as fh:
        json.dump(meta, fh)

    evict0 = _counter("aot_store_stale_evictions")
    assert AOTStore(root).load(key) is None
    assert _counter("aot_store_stale_evictions") == evict0 + 1
    # both files gone: the stale artifact cannot resurface
    assert not os.path.exists(meta_path)
    assert not os.path.exists(os.path.join(root, h + ARTIFACT_SUFFIX))
    # rebuild lands a fresh, loadable artifact
    store2 = AOTStore(root)
    store2.compile_and_save(key, _toy, args)
    assert store2.load(key) is not None


def test_corrupt_artifact_degrades_to_live_lowering(tmp_path):
    """Poisoned artifact bytes (sha-valid or not) fall back to a live
    build through the compile-cache disk tier — never a crash."""
    root = str(tmp_path / "s")
    store = AOTStore(root)
    args = _toy_args()
    key = ("toy", cc.sig(args))
    store.compile_and_save(key, _toy, args)
    h = key_hash(key)
    art = os.path.join(root, h + ARTIFACT_SUFFIX)

    # flipped bytes: sha256 verification evicts
    with open(art, "r+b") as fh:
        fh.seek(0)
        fh.write(b"\x00garbage\x00")
    evict0 = _counter("aot_store_stale_evictions")
    assert AOTStore(root).load(key) is None
    assert _counter("aot_store_stale_evictions") == evict0 + 1

    # sha-VALID poison (meta rewritten to match garbage): survives the
    # hash check, dies in deserialize, still evict + None, no raise
    import hashlib
    store3 = AOTStore(root)
    store3.compile_and_save(key, _toy, args)
    poison = b"not a pickled executable"
    with open(art, "wb") as fh:
        fh.write(poison)
    meta_path = os.path.join(root, h + META_SUFFIX)
    meta = json.loads(open(meta_path).read())
    meta["sha256"] = hashlib.sha256(poison).hexdigest()
    meta["bytes"] = len(poison)
    with open(meta_path, "w") as fh:
        json.dump(meta, fh)
    evict0 = _counter("aot_store_stale_evictions")
    cache = cc.CompileCache(max_entries=4)
    fn = cache.get_or_build(key, lambda: (lambda a, b: _toy(a, b)),
                            store=AOTStore(root), aot_args=args)
    assert fn is not None   # live fallback built the program
    assert _counter("aot_store_stale_evictions") > evict0
    np.testing.assert_array_equal(np.asarray(fn(*args)),
                                  np.asarray(_toy(*args)))


def test_torn_pair_is_a_miss(tmp_path):
    root = str(tmp_path / "s")
    store = AOTStore(root)
    args = _toy_args()
    key = ("toy", cc.sig(args))
    store.compile_and_save(key, _toy, args)
    os.remove(os.path.join(root, key_hash(key) + META_SUFFIX))
    assert AOTStore(root).load(key) is None


def test_unwritable_root_degrades(tmp_path):
    # a store root nested under a regular FILE can never be created —
    # unwritable even for root, which CI often runs as
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    store = AOTStore(str(blocker / "s"))
    assert not store.writable
    # saving is a no-op warning, not a crash
    args = _toy_args()
    compiled = store.compile_and_save(("k", cc.sig(args)), _toy, args)
    assert compiled is not None
    # the server keeps aot_store=None when the probe fails
    srv = PredictionServer({"serving_buckets": [1],
                            "aot_store": str(blocker / "s2")})
    assert srv.aot_store is None


# ------------------------------------------------- compile-cache disk tier
def test_compile_cache_disk_tier_counters(tmp_path):
    """memory miss + disk hit -> {ns}_compile_misses AND aot_store_hits
    (the disk tier saves the lowering, not the cache lookup)."""
    store = AOTStore(str(tmp_path / "s"))
    args = _toy_args()
    key = ("tier-test", cc.sig(args))
    store.compile_and_save(key, _toy, args)

    cache = cc.CompileCache(max_entries=4)
    hits0 = _counter("aot_store_hits")
    misses0 = _counter("round_compile_misses")
    fn = cache.get_or_build(key, lambda: (lambda a, b: _toy(a, b)),
                            store=store, aot_args=args)
    assert _counter("aot_store_hits") == hits0 + 1
    assert _counter("round_compile_misses") == misses0 + 1
    np.testing.assert_array_equal(np.asarray(fn(*args)),
                                  np.asarray(_toy(*args)))
    # second lookup: pure memory hit, disk untouched
    fn2 = cache.get_or_build(key, lambda: (lambda a, b: _toy(a, b)),
                             store=store, aot_args=args)
    assert fn2 is fn
    assert _counter("aot_store_hits") == hits0 + 1


# ---------------------------------------------------------- verify surface
def test_verify_store_and_inspector(tmp_path):
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        import checkpoint_inspect
    finally:
        sys.path.pop(0)
    root = str(tmp_path / "s")
    store = AOTStore(root)
    args = _toy_args()
    key = ("toy", cc.sig(args))
    store.compile_and_save(key, _toy, args)

    assert find_aot_stores(str(tmp_path)) == [root]
    rep = verify_store(root)
    assert rep["valid"] and not rep["findings"]
    assert checkpoint_inspect.main([root, "--format", "json"]) == 0

    # torn pair -> finding, exit 1
    os.remove(os.path.join(root, key_hash(key) + ARTIFACT_SUFFIX))
    rep = verify_store(root)
    assert not rep["valid"]
    assert any("torn" in f for f in rep["findings"])
    assert checkpoint_inspect.main([root, "--format", "json"]) == 1

    # fingerprint chain: runtime fingerprint matches this process
    assert runtime_fingerprint()["jax"]


# ------------------------------------------------ fresh-process serve warm
_CHILD = r"""
import os, sys
import numpy as np
from lightgbm_tpu.obs import compile_events
from lightgbm_tpu.obs.metrics import global_metrics
compile_events.install()
from lightgbm_tpu.serving import PredictionServer
store_dir, model_file = sys.argv[1], sys.argv[2]
srv = PredictionServer({"serving_buckets": [1, 8, 64],
                        "aot_store": store_dir})
base = global_metrics.counter("xla_program_lowerings")
srv.publish("m", model_file=model_file, warmup=True)
rng = np.random.default_rng(4)
X = rng.normal(size=(130, 6))
for i in range(30):
    n = int(rng.integers(1, 130))
    srv.predict("m", X[:n], raw_score=(i % 2 == 0))
delta = int(global_metrics.counter("xla_program_lowerings") - base)
hits = int(global_metrics.counter("aot_store_hits"))
print("RESULT %d %d" % (delta, hits))
"""


@pytest.mark.slow
def test_fresh_process_warms_with_zero_lowerings(tmp_path):
    """The tentpole acceptance gate: a brand-new process pointed at a
    populated store publishes + serves a mixed request stream with ZERO
    XLA lowerings — every serve program deserializes from disk."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 6))
    y = X[:, 0] + rng.normal(scale=0.1, size=400)
    bst = lgb.train({"objective": "regression", "num_iterations": 5,
                     "num_leaves": 7, "min_data_in_leaf": 5,
                     "verbosity": -1}, lgb.Dataset(X, label=y))
    model_file = str(tmp_path / "model.txt")
    bst.save_model(model_file)
    store_dir = str(tmp_path / "aot")

    # populate: a first server publishes FROM THE FILE (the path a
    # respawned replica takes) and saves every bucket's programs
    srv = PredictionServer({"serving_buckets": [1, 8, 64],
                            "aot_store": store_dir})
    srv.publish("m", model_file=model_file, warmup=True)
    assert len(srv.aot_store) >= 3

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, store_dir, model_file],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=_REPO)
    assert out.returncode == 0, out.stderr
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT")][-1]
    delta, hits = int(line.split()[1]), int(line.split()[2])
    assert delta == 0, \
        f"fresh process lowered {delta} programs (store was bypassed?)\n" \
        + out.stderr
    assert hits >= 3


def test_server_warm_detail_splits_load_vs_lower(tmp_path):
    """warmup_ex() attributes each bucket's warm cost to lower_s on a
    store miss and aot_load_s on a store hit."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 5))
    y = X[:, 0] + rng.normal(scale=0.1, size=300)
    bst = lgb.train({"objective": "regression", "num_iterations": 4,
                     "num_leaves": 7, "min_data_in_leaf": 5,
                     "verbosity": -1}, lgb.Dataset(X, label=y))
    model_file = str(tmp_path / "m.txt")
    bst.save_model(model_file)
    store_dir = str(tmp_path / "aot")

    s1 = PredictionServer({"serving_buckets": [1, 8],
                           "aot_store": store_dir})
    s1.publish("m", model_file=model_file, warmup=True)
    d1 = s1.entry_warm_detail()
    assert set(d1) == {1, 8}
    assert all(d["lower_s"] > 0 and d["aot_load_s"] == 0.0
               for d in d1.values())

    s2 = PredictionServer({"serving_buckets": [1, 8],
                           "aot_store": store_dir})
    s2.publish("m", model_file=model_file, warmup=True)
    d2 = s2.entry_warm_detail()
    assert all(d["aot_load_s"] > 0 and d["lower_s"] == 0.0
               for d in d2.values())
    # parity across the two warm paths
    np.testing.assert_array_equal(s1.predict("m", X[:5]),
                                  s2.predict("m", X[:5]))
