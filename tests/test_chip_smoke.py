"""chip_smoke.py refuses to report success without a chip, and the
compile-cache helper leaves the cache where the machine placed it."""

import json
import os
import subprocess
import sys

import jax

from lightgbm_tpu.ops.compile_cache import use_persistent_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_chip():
    """On a CPU backend, without --rehearse-cpu: non-zero exit, before
    any phase, and never the success line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert '"phase"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_rehearsal_runs_every_phase_but_cannot_succeed():
    """--rehearse-cpu drives every phase at a tiny size (rows cut to the
    100,000 at which auto mode still engages) and its last line is never
    the success line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                          "--rehearse-cpu"],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    assert [ln.get("phase") for ln in lines[:-1]] == [
        "device", "data", "train", "bundled", "categorical", "ranking",
        "wide", "predict", "save_load", "serve"]
    bundled = lines[3]
    assert bundled["bundle_expand_calls"] == 0 and bundled["bundles"] < 76
    assert bundled["bundle_space_search_rounds"] == bundled["iters"]
    categorical = lines[4]
    assert (categorical["cat_features"], categorical["cat_subset_features"]) \
        == (3, 2)
    assert categorical["cat_other_rows"] > 0 < categorical["cat_subset_splits"]
    assert categorical["train_score_gap"] < 1e-4
    # ... and the job whose categorical columns are all one-hot
    assert categorical["onehot_only_cat_splits"] > 0
    assert categorical["onehot_only_train_score_gap"] < 1e-4
    ranking = lines[5]
    assert ranking["buckets"] >= 4 and ranking["rank_slot_rows"] > ranking["rows"]
    assert ranking["device_against_host_ndcg"] < 1e-5
    assert ranking["valid_ndcg10_last"] > ranking["valid_ndcg10_first"]
    wide = lines[6]
    assert (wide["features"], wide["hist_col_blocks"]) == (2000, 63)
    assert wide["hist_state_bytes"] == 15 * 2000 * 256 * 16
    assert wide["hist_vmem_budget_bytes"] == 72 << 20
    assert lines[-1]["ok"] is False and "rehearsal" in lines[-1]
    assert '"ok": true' not in out.stdout


def test_persistent_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code (JAX
    reads the variable itself).  Unset: the fixed path the caller names."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert use_persistent_cache("/checkout/.jax_cache") is None
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert use_persistent_cache("/checkout/.jax_cache") \
        == "/checkout/.jax_cache"
    assert calls == [("jax_compilation_cache_dir", "/checkout/.jax_cache")]


def test_chip_smoke_runs_the_wide_phase_alone():
    """``--only wide``: the device phase and the 2,000-column job, which is
    what a kernel PR sends to the chip before the cell ``epsilon-train``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                          "--rehearse-cpu", "--only", "wide"],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    assert [ln.get("phase") for ln in lines[:-1]] == ["device", "wide"]
    assert lines[-1] == {"ok": False, "only": "wide",
                         "device": lines[-1]["device"]}
