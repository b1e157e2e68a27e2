"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width of the Higgs shape (28 features, 255 bins, 255 leaves, auto
mode: K=42 split batch, int8 histogram kernels); rows are the one
reduction (``--rows``, default 1,000,000 of the published 10.5M) and the
weights are whatever ``--iters`` rounds learn from ``--seed``:

    device -> train (fused scan + valid set) -> bundled (CSR in, EFB)
           -> categorical (a 600-level column, splits by sets of bins)
           -> ranking -> wide (2,000 dense columns: the histogram
           kernels' output in column blocks) -> predict -> save/load
           -> serve

One process, no children that touch JAX, JAX imported once.  Every phase
prints one JSON line as it finishes and raises on any failure, so the
exit code is non-zero unless every phase passed.  The LAST line of
standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only on a TPU.  Without an accelerator the script exits
non-zero before any phase.  ``--rehearse-cpu`` is the one exception: it
runs the same phases at a tiny size on the CPU backend to find wrong
paths and arguments before chip time is spent, skips the checks only a
chip can pass, and can never print the success line.

``--only wide`` runs the device phase and the wide phase alone.
``--chips 4`` runs ONLY the four-chip path and what it is compared with:
the same job trained ``tree_learner=data`` over all four chips and
``tree_learner=serial`` on device 0.

Timings on the phase lines are smoke timings (one cold run, compile
included where it says so) — not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import tempfile
import time
from importlib import metadata

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FEATURES = 28
#: f32-rounding tolerance of device predict vs the host f64 walk
#: (tests/test_engine.py test_device_predict_parity_paths)
RTOL, ATOL = 2e-5, 2e-6
PARAMS = {"objective": "binary", "metric": "auc", "num_leaves": 255,
          "max_bin": 255, "min_sum_hessian_in_leaf": 100, "verbose": -1}


def _require(ok, what) -> None:
    """A check that decides the result: raises (``assert`` would vanish
    under ``python -O`` and the smoke would pass without checking)."""
    if not ok:
        raise AssertionError(what)


def _emit(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase,
                      "smoke_seconds": round(time.time() - t0, 2),
                      **fields}), flush=True)


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _device_arrays(obj):
    """Every jax.Array held by ``obj``'s attributes (lists, tuples and
    dicts included) — the booster's device state."""
    import jax

    def walk(v):
        if isinstance(v, jax.Array):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                yield from walk(x)
        elif isinstance(v, dict):
            for x in v.values():
                yield from walk(x)

    for name, v in vars(obj).items():
        for a in walk(v):
            yield name, a


def _aval(a):
    """Shape of a live array as ``jit`` saw it: its sharding only where
    it spans devices (a one-device array is uncommitted to ``jit``, and
    naming its device would lower a different module — a cache miss)."""
    import jax
    sharding = a.sharding if len(a.devices()) > 1 else None
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _fused_program_text(gb) -> str:
    """Compiled text of the round runner ``train_fused`` cached for
    ``gb`` (re-lowered at the live arrays' shapes; with the persistent
    compilation cache on, the compile itself is a cache read)."""
    import jax
    import jax.numpy as jnp
    (T, has_fm, _nvalid, es), runner = next(iter(gb._fused_cache.items()))
    _require(not has_fm and es is None, "unexpected fused runner key")
    k = gb.num_tree_per_iteration
    S = jax.ShapeDtypeStruct
    lowered = runner.lower(
        _aval(gb.scores), _aval(gb.bins),
        None if gb.bins_words is None else _aval(gb.bins_words),
        S((T, 2), jnp.uint32), S((T, k, 2), jnp.uint32), None,
        S((T,), jnp.int32), tuple(_aval(v) for v in gb.valid_scores), (),
        jax.tree.map(_aval, gb._fused_operands()))
    return lowered.compile().as_text()


def _sharded_program_text(gb) -> str:
    """Compiled text of one tree grown by ``gb``'s distributed learner
    (``GBDT._grow`` traced at the live gradients' shapes and shardings:
    the shard_map round program ``train()`` ran, under one outer jit)."""
    import jax
    import jax.numpy as jnp
    g, h = gb.boosting_gradients()
    scales = jax.ShapeDtypeStruct((2,), jnp.float32)
    one_tree = jax.jit(lambda g, h, hs: gb._grow(g, h, None, None, None, hs))
    return one_tree.lower(_aval(g[:, 0]), _aval(h[:, 0]),
                          scales).compile().as_text()


def _require_kernel(text: str, kernel: str, scope: str, what: str) -> None:
    """The compiled program ``text`` holds the Pallas kernel ``kernel``
    under the device scope ``scope``."""
    _require(any("tpu_custom_call" in line and f"%{kernel}" in line
                 and scope in line for line in text.splitlines()), what)


def _require_partition_kernel(text: str, where: str) -> None:
    """Rows move by the fused partition kernel, which reads the resident
    transposed bins as they lie: a ``pad`` under the scope is a copy of
    the whole bin matrix every round pass (21-49 ms a round in the
    benchmark's cells until PR 41)."""
    _require_kernel(text, "partition_select_pallas", "partition",
                    f"the partition is not the fused "
                    f"partition_select_pallas kernel in {where}")
    pads = [re.search(r"= \w+\[([\d,]+)\]", line)
            for line in text.splitlines()
            if " pad(" in line and "partition/" in line]
    # (gathers of the K split descriptors leave pads of a few words)
    big = [m.group(0) for m in pads
           if m and math.prod(int(d) for d in m.group(1).split(",")) >= 1 << 16]
    _require(not big, f"a pad of {big} under the scope partition in {where}")


def _require_compaction_kernel(text: str, where: str) -> None:
    """A compacted histogram pass gets its rows from the streaming
    compaction kernel (ops/hist_pallas.py), never from a sort of the
    keys and XLA's lane gather (2.2 GB/s: 121 ms a pass at 13M rows)."""
    _require_kernel(text, "compact_payload_pallas", "hist_compact",
                    f"no compact_payload_pallas kernel under hist_compact "
                    f"in {where}")


_HLO_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_HLO_INSTR = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_HLO_ARRAY = re.compile(r"\w+\[([\d,]*)\](?:\{([^}]*)\})?")
_HLO_CALLED = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation|"
    r"branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")


def _hlo_arrays(types: str) -> list:
    """``(element count, dims, tiling)`` of every array in an HLO type."""
    return [(math.prod(int(d) for d in dims.split(",") if d), dims,
             re.sub(r"S\(\d+\)", "", tiles))
            for dims, tiles in _HLO_ARRAY.findall(types)]


def _hlo_operands(m) -> list:
    args = re.sub(r"/\*.*?\*/", "", m.group(4)).split(")", 1)[0]
    return [a.split()[-1].lstrip("%") for a in args.split(",") if a.strip()]


def _hlo_called(m) -> list:
    return [c.lstrip("%") for attr in _HLO_CALLED.findall(m.group(4))
            for c in re.findall(r"%?[\w.\-]+", attr)]


def _hlo_loops(text: str):
    """``(computations, looped)`` of a compiled program's text: every
    computation as ``{instruction name: match of _HLO_INSTR}``, and the
    names of those a ``while`` runs: its body and condition and what they
    call, a fusion's own computation left out (the fusion stands for it)."""
    comps, name = {}, None
    for line in text.splitlines():
        head = _HLO_HEAD.match(line)
        if head:
            name = head.group(1)
            comps[name] = {}
        elif line.startswith("}"):
            name = None
        elif name is not None:
            m = _HLO_INSTR.match(line.strip())
            if m:
                comps[name][m.group(1)] = m
    todo = [c for instrs in comps.values() for m in instrs.values()
            if m.group(3) == "while" for c in _hlo_called(m)]
    looped = set()
    while todo:
        c = todo.pop()
        if c in comps and c not in looped:
            looped.add(c)
            todo.extend(d for m in comps[c].values()
                        if m.group(3) != "fusion" for d in _hlo_called(m))
    return comps, looped


def _hist_state_copies(text: str, counts) -> list:
    """The instructions of the compiled program ``text`` that move the
    per-leaf histogram state inside a loop, where ``counts`` holds the
    state's element counts.  Listed is a ``copy``, ``copy-start``,
    ``transpose`` or ``fusion`` in a ``while`` body, or in a computation
    one calls,

    - one of whose results (a fusion may give a tuple) has the state's
      count, under any logical shape or layout, unless the fusion's root
      writes that result in place: a ``dynamic-update-slice`` into a
      parameter of the result's own shape and layout;
    - or which takes the state and gives half of its count or more back in
      other buffers (the compiler's blocked gather did: seven column
      blocks of ``st["hist"]`` a round pass).

    The grower carries the state in ONE layout and reads and writes it by
    leading-axis slabs (learner/batch_grower.py ``write_children``), so
    this list is empty.  Until PR 46 it held two conversions of the whole
    state a round pass, 2.09 GB each at 2,000 columns (PERF.md section 6)."""
    comps, looped = _hlo_loops(text)

    def in_place(m):
        """Which results of the fusion ``m`` its root writes in place."""
        fused = comps.get(_hlo_called(m)[0], {}) if _hlo_called(m) else {}
        root = next((r for r in fused.values()
                     if r.group(0).startswith("ROOT ")), None)
        if root is None:
            return []
        outs = [fused.get(o) for o in _hlo_operands(root)] \
            if root.group(3) == "tuple" else [root]
        flags = []
        for out in outs:
            into = fused.get(_hlo_operands(out)[0]) if out is not None \
                and out.group(3) == "dynamic-update-slice" else None
            flags.append(into is not None and into.group(3) == "parameter"
                         and _hlo_arrays(into.group(2)) == _hlo_arrays(out.group(2)))
        return flags

    found = []
    for c in sorted(looped):
        for name, m in comps[c].items():
            if m.group(3) not in ("copy", "copy-start", "transpose", "fusion"):
                continue
            res = _hlo_arrays(m.group(2))
            if m.group(3) == "fusion":
                res = [r for r, kept in zip(res, in_place(m) + [False] * len(
                    res)) if not kept]
            takes = any(a[0] in counts for o in _hlo_operands(m)
                        if o in comps[c] for a in _hlo_arrays(comps[c][o].group(2)))
            if any(r[0] in counts for r in res) or (
                    takes and 2 * sum(r[0] for r in res) >= min(counts)):
                found.append(f"%{name} = {m.group(3)} -> {m.group(2)} in {c}")
    return found


def _search_candidate_arrays(text: str, kids: int, features: int,
                             bins: int) -> list:
    """What the split search of the compiled program ``text`` still holds
    or moves of its candidates inside a loop: the instructions under the
    scope ``find_splits`` (by their ``op_name``) in a ``while`` body, or
    in a computation one calls,

    - one of whose results has ``kids x features x bins x V`` elements for
      2 <= V <= 5: the gains of V variants side by side, under whatever
      shape (until PR 47 ``[2K, F, bins, 5]`` and ``[2K, F x bins x 5]``).
      The children's own slabs ``[kids, features, bins, 4]``, joined under
      the scope, are the search's input and do not count; the kernels'
      ``[K, F, bins, 4]`` has the count of V = 2 and is ``round_hist``'s;
    - or which is a ``copy``, ``transpose`` or ``reshape`` of ``kids x
      features x bins`` elements or more: a layout converted.

    ``find_best_split`` reduces the live variants elementwise and takes
    the winner in two steps (ops/split.py), so this list is empty; the
    three it held at 2,000 columns were 140 ms of a round (PERF.md section
    6, PR 47)."""
    from lightgbm_tpu.ops.split import NUM_VARIANTS
    comps, looped = _hlo_loops(text)
    per_variant = kids * features * bins
    stacked = {per_variant * v for v in range(2, NUM_VARIANTS + 1)}
    slabs = f"{kids},{features},{bins},4"
    found = []
    for c in sorted(looped):
        for name, m in comps[c].items():
            scope = re.search(r'op_name="([^"]*)"', m.group(4))
            if scope is None or "find_splits" not in scope.group(1):
                continue
            res = _hlo_arrays(m.group(2))
            if any(n in stacked and dims != slabs for n, dims, _ in res) or (
                    m.group(3) in ("copy", "copy-start", "transpose",
                                   "reshape")
                    and any(n >= per_variant for n, _, _ in res)):
                found.append(f"%{name} = {m.group(3)} -> {m.group(2)} in {c}")
    return found


def _synth_higgs(n, f, rng, w=None):
    """Higgs-shaped synthetic binary data (separable-ish continuous
    features; BASELINE.md pairs its 130.094 s with AUC 0.845724 on the real
    set — the synthetic task reports ITS OWN auc next to wall-clock so perf
    is always gated on accuracy).  Pass ``w`` to draw train/test sets from
    the SAME task."""
    if w is None:
        w = rng.normal(size=f)
    feat = rng.normal(size=(n, f)).astype(np.float32)
    logits = feat @ w * 0.5
    label = (logits + rng.normal(scale=1.0, size=n) > 0).astype(np.float32)
    return feat, label, w


def _make_data(args):
    rng = np.random.default_rng(args.seed)
    X, y, w = _synth_higgs(args.rows, FEATURES, rng)
    Xv, yv, _ = _synth_higgs(args.valid_rows, FEATURES, rng, w=w)
    return X, y, Xv, yv


def _train(lgb, params, ds, dv, iters):
    evals = {}
    t0 = time.time()
    bst = lgb.train(params, ds, num_boost_round=iters, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(evals)])
    return bst, evals["valid_0"]["auc"], time.time() - t0


def _check_auc(auc, iters) -> None:
    _require(len(auc) == iters and np.isfinite(auc).all(),
             f"valid AUC per round: {auc}")
    _require(auc[-1] > 0.75, f"valid AUC {auc[-1]} <= 0.75")
    _require(auc[-1] > auc[0],
             f"valid AUC did not rise: {auc[0]} -> {auc[-1]}")


def _took_fused_path(bst, iters) -> bool:
    """Every round ran inside ``GBDT.train_fused``'s scan (engine.py
    takes that path only when ``supports_fused()`` holds)."""
    gb = bst._gbdt
    return bool(gb.supports_fused()
                and gb.metrics.counter("fused_rounds") == iters)


# ------------------------------------------------------------------ phases

def phase_device(args):
    t0 = time.time()
    import jax
    import jaxlib
    from lightgbm_tpu.ops.compile_cache import use_persistent_cache
    from lightgbm_tpu.ops.histogram import use_pallas

    cache_dir = use_persistent_cache(os.path.join(HERE, ".jax_cache"))
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not (args.rehearse_cpu and platform == "cpu"):
        raise SystemExit(f"chip_smoke: no TPU (jax platform is "
                         f"'{platform}'); nothing was run")
    if args.rehearse_cpu and platform != "cpu":
        raise SystemExit("chip_smoke: --rehearse-cpu is for the CPU backend")
    if args.chips == 4 and len(devs) != 4:
        raise SystemExit(f"chip_smoke: --chips 4 needs exactly 4 devices, "
                         f"jax sees {len(devs)}")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    try:
        from lightgbm_tpu import native
        native._load()
        parser = "built"
    except ImportError:
        parser = "numpy"
    if platform == "tpu":
        _require(use_pallas(), "Pallas kernels must be selected on a TPU")
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _emit("device", t0, **device, jax=jax.__version__,
          jaxlib=jaxlib.__version__, libtpu=libtpu,
          use_pallas=use_pallas(), native_parser=parser,
          hbm_bytes_limit=(devs[0].memory_stats() or {}).get("bytes_limit"),
          compile_cache_dir=cache_dir
          or os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    return device


def phase_train(args, lgb, data):
    import jax
    from lightgbm_tpu.ops.compile_cache import GLOBAL_COMPILE_CACHE

    t0 = time.time()
    X, y, Xv, yv = data
    ds = lgb.Dataset(X, label=y, params=PARAMS).construct()
    dv = ds.create_valid(Xv, label=yv)
    construct_s = time.time() - t0
    bst, auc, first_s = _train(lgb, PARAMS, ds, dv, args.iters)
    gb = bst._gbdt
    cfg = gb.config
    _require((int(cfg.tpu_split_batch), gb.hp.hist_dtype) == (42, "int8"),
             "auto mode did not pick K=42 / int8")
    _require(_took_fused_path(bst, args.iters), "fused path not taken")
    _check_auc(auc, args.iters)
    on_tpu = jax.devices()[0].platform == "tpu"
    calls = None
    if on_tpu:
        text = _fused_program_text(gb)
        calls = text.count("tpu_custom_call")
        _require(calls > 0,
                 "no Pallas kernel in the compiled round program")
        # auto mode renews leaf values from the true gradients; on the
        # chip their per-leaf sums are ops/table.py's kernel, never
        # XLA's scatter-add (0.9 GB/s: 232 ms a round at 13M rows)
        _require(bool(cfg.quant_train_renew_leaf),
                 "auto mode did not turn on leaf renewal")
        _require_kernel(text, "_sum_pallas", "leaf_renew",
                        "leaf renewal's sums are not the _sum_pallas kernel "
                        "in the compiled round program")
        _require_partition_kernel(text, "the compiled round program")
        _require_compaction_kernel(text, "the compiled round program")
        off = [n for n, a in _device_arrays(gb)
               if {d.platform for d in a.devices()} != {"tpu"}]
        _require(not off, f"booster state not on the TPU: {off}")
    # the other bin count swaps the full-pass kernel (packed words) and
    # the compaction's source (the resident word mirror): a short job
    p63 = {**PARAMS, "max_bin": 63}
    ds63 = lgb.Dataset(X, label=y, params=p63).construct()
    dv63 = ds63.create_valid(Xv, label=yv)
    bst63, auc63, secs63 = _train(lgb, p63, ds63, dv63, args.iters)
    _require(_took_fused_path(bst63, args.iters),
             "fused path not taken at 63 bins")
    _check_auc(auc63, args.iters)
    if on_tpu:
        _require_compaction_kernel(_fused_program_text(bst63._gbdt),
                                   "the 63-bin round program")
    # same shapes, same datasets, same process: nothing may compile again
    misses0 = GLOBAL_COMPILE_CACHE.stats()["misses"]
    bst2, auc2, second_s = _train(lgb, PARAMS, ds, dv, args.iters)
    new_misses = GLOBAL_COMPILE_CACHE.stats()["misses"] - misses0
    _require(new_misses == 0 and
             bst2._gbdt.metrics.counter("round_compile_misses") == 0,
             f"second train() compiled {new_misses} round program(s) again")
    _require(auc2 == auc, "second train() of the same job gave another AUC")
    _emit("train", t0, rows=args.rows, published_rows=10_500_000,
          reduced="rows only; widths as published", features=FEATURES,
          valid_rows=args.valid_rows, iters=args.iters,
          split_batch=int(cfg.tpu_split_batch), hist_dtype=gb.hp.hist_dtype,
          fused=True, tpu_custom_calls=calls, state_arrays_on_tpu=on_tpu,
          valid_auc_first=auc[0], valid_auc_last=auc[-1],
          second_train_round_compile_misses=new_misses,
          smoke_construct_s=round(construct_s, 2),
          smoke_first_train_s=round(first_s, 2),
          smoke_second_train_s=round(second_s, 2),
          smoke_compile_s=round(first_s - second_s, 2),
          valid_auc_last_63bin=auc63[-1], smoke_train_63bin_s=round(secs63, 2),
          peak_bytes_in_use=_peak_bytes(jax.devices()[0]))
    return bst


def _one_hot_csr(rows: int, seed: int):
    """A few hundred one-hot columns beside four numeric ones, as scipy
    CSR: five blocks of 60 levels, one level a row and block, skewed.
    Labels follow two of the numeric columns and each block's low
    levels."""
    import scipy.sparse as sp
    rng = np.random.default_rng([seed, 34])
    numeric, blocks, levels = 4, 5, 60
    x = rng.normal(size=(rows, numeric)).astype(np.float32)
    level = np.minimum((levels * rng.random((rows, blocks)) ** 2.5).astype(np.int64),
                       levels - 1)
    logit = 1.5 * x[:, 0] - 0.8 * x[:, 1] + (level < 6).sum(1) - 1.2
    y = (logit + rng.logistic(size=rows) > 0).astype(np.float32)
    width = numeric + blocks
    data = np.ones((rows, width), np.float64)
    data[:, :numeric] = x
    indices = np.empty((rows, width), np.int32)
    indices[:, :numeric] = np.arange(numeric)
    indices[:, numeric:] = numeric + levels * np.arange(blocks) + level
    return sp.csr_matrix((data.ravel(), indices.ravel(),
                          np.arange(rows + 1, dtype=np.int64) * width),
                         shape=(rows, numeric + blocks * levels)), y


def phase_bundled(args, lgb):
    """A short bundled job: scipy CSR in, a few hundred one-hot columns
    packed into feature bundles, the split search in bundle space (scope
    ``bundle_search``), no expansion of a bundle histogram to
    virtual-feature space, and the fused partition kernel routing rows
    by range predicates on the bundle columns."""
    import jax
    from lightgbm_tpu.obs.metrics import global_metrics

    t0 = time.time()
    rows = args.rows
    X, y = _one_hot_csr(rows + args.valid_rows, args.seed)
    params = {**PARAMS, "min_sum_hessian_in_leaf": 20}
    ds = lgb.Dataset(X[:rows], label=y[:rows], params=params).construct()
    dv = ds.create_valid(X[rows:], label=y[rows:])
    before = global_metrics.counter("bundle_expand_calls")
    bst, auc, secs = _train(lgb, params, ds, dv, args.iters)
    gb = bst._gbdt
    _require(gb.bundle is not None and gb.bundle.search is not None,
             "the CSR job was not bundled with ranges")
    bundles = int(gb.metrics.counter("efb_bundles"))
    _require(bundles < X.shape[1] // 4,
             f"{bundles} bundle columns of {X.shape[1]} features")
    _require(_took_fused_path(bst, args.iters), "fused path not taken")
    _require(gb.metrics.counter("bundle_space_search_rounds") == args.iters,
             "not every round searched its splits in bundle space")
    expanded = global_metrics.counter("bundle_expand_calls") - before
    _require(expanded == 0,
             f"a bundle histogram was expanded to virtual space {expanded}x")
    _check_auc(auc, args.iters)
    calls = None
    if jax.devices()[0].platform == "tpu":
        text = _fused_program_text(gb)
        calls = text.count("tpu_custom_call")
        _require("bundle_search" in text,
                 "no operation under the scope bundle_search in the "
                 "compiled round program")
        _require_partition_kernel(text, "the bundled round program")
        _require_compaction_kernel(text, "the bundled round program")
    _emit("bundled", t0, rows=rows, features=X.shape[1], bundles=bundles,
          iters=args.iters, bundle_space_search_rounds=args.iters,
          bundle_expand_calls=int(expanded), tpu_custom_calls=calls,
          valid_auc_first=auc[0], valid_auc_last=auc[-1],
          smoke_train_s=round(secs, 2))


def _claims(rows: int, seed: int):
    """Four numeric columns and three categorical ones: 600 levels
    (zipf-distributed, codes permuted), 3 and 20."""
    rng = np.random.default_rng([seed, 43])
    p = 1.0 / np.arange(1, 601) ** 1.1
    level = rng.choice(600, size=rows, p=p / p.sum())
    small, mid = rng.integers(0, 3, rows), rng.integers(0, 20, rows)
    x = rng.standard_normal((rows, 4))
    z = x[:, 0] + rng.normal(size=600)[level] + 0.5 * (small == 1) \
        + 0.3 * np.sin(mid)
    y = (z + rng.standard_normal(rows) > 0).astype(np.float32)
    X = np.column_stack([x, rng.permutation(600)[level], small, mid])
    return X.astype(np.float64), y


def phase_categorical(args, lgb):
    """A short job with categorical columns, one of 600 levels (more than
    a column has bins for): the partition by sets of bins in the fused
    kernel (``fused_partition_declined`` 0), the split search's scopes
    ``cat_subset`` and ``cat_bitset`` by name, and the training scores
    the job holds equal to what the model it returns says of the raw
    training rows (a level without a bin goes right in both).  Then
    eight rounds with the 3-level column alone categorical: a job whose
    categorical columns are all one-hot trains on the same path."""
    import jax

    t0 = time.time()
    rows = args.rows
    X, y = _claims(rows + args.valid_rows, args.seed)
    ds = lgb.Dataset(X[:rows], label=y[:rows], params=PARAMS,
                     categorical_feature=[4, 5, 6]).construct()
    dv = ds.create_valid(X[rows:], label=y[rows:])
    bst, auc, secs = _train(lgb, PARAMS, ds, dv, args.iters)
    gb = bst._gbdt
    _require(_took_fused_path(bst, args.iters), "fused path not taken")
    counted = {c: int(gb.metrics.counter(c)) for c in (
        "cat_features", "cat_subset_features", "cat_other_rows", "cat_splits",
        "cat_subset_splits", "fused_partition_declined")}
    _require((counted["cat_features"], counted["cat_subset_features"])
             == (3, 2), f"categorical columns counted {counted}")
    _require(counted["cat_other_rows"] > 0 and counted["cat_subset_splits"] > 0,
             f"no row in an other bin or no split by a set: {counted}")
    _check_auc(auc, args.iters)
    held = np.asarray(gb.scores)[:, 0]
    said = bst.predict(X[:rows], raw_score=True)
    gap = float(np.abs(held - said).max())
    _require(gap < 1e-4, f"the training scores are not the returned "
                         f"model's: worst row {gap}")
    calls = None
    if jax.devices()[0].platform == "tpu":
        _require(counted["fused_partition_declined"] == 0,
                 f"{counted['fused_partition_declined']} rounds' partition "
                 "took the XLA path")
        text = _fused_program_text(gb)
        calls = text.count("tpu_custom_call")
        for scope in ("cat_subset", "cat_bitset"):
            _require(scope in text, f"no operation under the scope {scope} "
                                    "in the compiled round program")
        _require_partition_kernel(text, "the categorical round program")
    # the same rows with the 3-level column alone handed over: every
    # categorical column of the job is one-hot, the list of subset columns
    # is empty and the round program traces no scan
    flag = lgb.Dataset(X[:rows], label=y[:rows], params=PARAMS,
                       categorical_feature=[5]).construct()
    bst1, _, _ = _train(lgb, PARAMS, flag,
                        flag.create_valid(X[rows:], label=y[rows:]), 8)
    gb1 = bst1._gbdt
    _require(_took_fused_path(bst1, 8) and gb1.hp.cat_subset_cols == (),
             f"the one-hot-only job: fused {_took_fused_path(bst1, 8)}, "
             f"subset columns {gb1.hp.cat_subset_cols}")
    flag_splits = int(gb1.metrics.counter("cat_splits"))
    flag_gap = float(np.abs(np.asarray(gb1.scores)[:, 0] - bst1.predict(
        X[:rows], raw_score=True)).max())
    _require(flag_splits > 0 and flag_gap < 1e-4
             and gb1.metrics.counter("cat_subset_splits") == 0,
             f"the one-hot-only job: {flag_splits} categorical splits, "
             f"worst row {flag_gap}")
    if jax.devices()[0].platform == "tpu":
        _require(gb1.metrics.counter("fused_partition_declined") == 0,
                 "the one-hot-only job's partition took the XLA path")
    _emit("categorical", t0, rows=rows, iters=args.iters, **counted,
          train_score_gap=gap, tpu_custom_calls=calls,
          onehot_only_cat_splits=flag_splits,
          onehot_only_train_score_gap=flag_gap,
          valid_auc_first=auc[0], valid_auc_last=auc[-1],
          smoke_train_s=round(secs, 2))


RANK_SCOPES = ("rank_gather", "rank_sort", "rank_pairs", "rank_accumulate",
               "ndcg_sort")


def _ranking_docs(rows: int, seed: int):
    """Query-grouped docs: skewed query lengths (8 to 1,000 docs), eight
    features, relevance 0-4 (most docs 0) from a latent relevance over
    three of the features and a per-query offset."""
    rng = np.random.default_rng([seed, 37])
    sizes = []
    while sum(sizes) < rows:
        sizes.append(int(np.clip(rng.lognormal(4.6, 0.8), 8, 1000)))
    sizes[-1] -= sum(sizes) - rows
    sizes = np.asarray([s for s in sizes if s > 0], np.int64)
    x = rng.normal(size=(rows, 8)).astype(np.float32)
    z = 0.8 * x[:, 0] - 0.5 * x[:, 1] + 0.3 * x[:, 2] * x[:, 3] \
        + 0.4 * np.repeat(rng.normal(size=len(sizes)), sizes) \
        + 0.6 * rng.normal(size=rows)
    y = np.searchsorted([1.4, 1.9, 2.3, 2.8], z).astype(np.float32)
    return x, y, sizes


def phase_ranking(args, lgb):
    """A short lambdarank job in the fused scan: pairwise gradients per
    query-length bucket, NDCG@1,3,5,10 of a held-out query set on the
    device every round, held against the host's ``NDCGMetric.eval`` of the
    final scores; the ranking scopes required by name in the compiled
    round program (the benchmark's ``rank_*`` readers read them)."""
    import jax

    t0 = time.time()
    rows, held = args.rows, args.valid_rows
    x, y, sizes = _ranking_docs(rows, args.seed)
    xv, yv, sizes_v = _ranking_docs(held, args.seed + 1)
    ks = [1, 3, 5, 10]
    params = {**PARAMS, "objective": "lambdarank", "metric": "ndcg",
              "eval_at": ks, "min_sum_hessian_in_leaf": 1e-3}
    ds = lgb.Dataset(x, label=y, group=sizes, params=params).construct()
    dv = ds.create_valid(xv, label=yv, group=sizes_v)
    evals = {}
    t1 = time.time()
    bst = lgb.train(params, ds, num_boost_round=args.iters, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(evals)])
    secs = time.time() - t1
    gb = bst._gbdt
    _require(_took_fused_path(bst, args.iters), "fused path not taken")
    _require(gb.metrics.counter("rank_queries") == len(sizes)
             and gb.metrics.counter("rank_docs") == rows,
             "the program did not count the job's queries and docs")
    buckets = int(gb.metrics.gauge("rank_bucket_count"))
    _require(buckets >= 4, f"{buckets} query-length buckets")
    recorded = np.array([evals["valid_0"][f"ndcg@{k}"] for k in ks])
    _require(recorded.shape == (len(ks), args.iters),
             f"NDCG recorded as {recorded.shape}")
    want = [v for _, v in gb.valid_metrics[0][0].eval(
        np.asarray(gb.valid_scores[0][:, 0], np.float64))]
    gap = float(np.abs(recorded[:, -1] - want).max())
    _require(gap < 1e-5, f"device NDCG {recorded[:, -1]} against the host's "
                         f"{want}")
    _require(recorded[-1, -1] > recorded[-1, 0] + 0.01,
             f"held-out NDCG@10 did not climb: {recorded[-1]}")
    calls = None
    if jax.devices()[0].platform == "tpu":
        text = _fused_program_text(gb)
        calls = text.count("tpu_custom_call")
        missing = [s for s in RANK_SCOPES if s not in text]
        _require(not missing, f"no operation under the scopes {missing} in "
                              "the compiled round program")
        _require_partition_kernel(text, "the ranking round program")
        _require_compaction_kernel(text, "the ranking round program")
    _emit("ranking", t0, rows=rows, queries=len(sizes), buckets=buckets,
          iters=args.iters, tpu_custom_calls=calls,
          rank_slot_rows=int(gb.metrics.counter("rank_slot_rows")),
          valid_ndcg10_first=float(recorded[-1, 0]),
          valid_ndcg10_last=float(recorded[-1, -1]),
          device_against_host_ndcg=gap, smoke_train_s=round(secs, 2))


WIDE_FEATURES = 2000


def phase_wide(args, lgb):
    """A short wide dense job: 2,000 numeric columns (upstream's Epsilon
    width, a tenth of the rows a phase takes), 255 bins, 255 leaves.  Every
    histogram kernel's output is blocked over the columns under the one
    VMEM budget (ops/hist_pallas.py ``col_blocks``): the booster counts
    the blocks, the per-leaf state's bytes and the budget, every round runs
    in the fused scan, and on a chip the compiled round program holds the
    payload, compaction and partition kernels under their scopes, moves
    nothing of the per-leaf state's size inside a tree's loop
    (``hist_state_copies``: 0), and its split search holds no array of the
    candidates' variants and converts no layout
    (``search_candidate_arrays``: 0)."""
    import jax

    t0 = time.time()
    tiny = args.rehearse_cpu
    rows = 4096 if tiny else max(args.rows // 10, 100_000)
    valid_rows = max(args.valid_rows // 10, 1024)
    rng = np.random.default_rng(args.seed + 45)
    w = rng.normal(size=WIDE_FEATURES)
    w *= 3.0 / np.linalg.norm(w)
    y = (rng.random(rows + valid_rows) < 0.5).astype(np.float64)
    X = rng.standard_normal((rows + valid_rows, WIDE_FEATURES),
                            dtype=np.float32).astype(np.float64)
    X += (2 * y - 1)[:, None] * w[None, :]
    params = {**PARAMS, "min_data_in_leaf": 1,
              "bin_construct_sample_cnt": 20_000}
    if tiny:
        # under 100,000 rows auto mode takes the strict grower: ask for
        # the batched grower and int8 histograms by name
        params.update(num_leaves=15, min_sum_hessian_in_leaf=1,
                      tpu_split_batch=8, tpu_hist_dtype="int8",
                      use_quantized_grad=True, quant_train_renew_leaf=True)
    ds = lgb.Dataset(X[:rows], label=y[:rows], params=params).construct()
    dv = ds.create_valid(X[rows:], label=y[rows:])
    bst, auc, secs = _train(lgb, params, ds, dv, args.iters)
    gb = bst._gbdt
    _require(_took_fused_path(bst, args.iters), "fused path not taken")
    blocks = int(gb.metrics.counter("hist_col_blocks"))
    state = int(gb.metrics.counter("hist_state_bytes"))
    budget = int(gb.metrics.counter("hist_vmem_budget_bytes"))
    _require(blocks > 1, f"{blocks} column block(s) at {WIDE_FEATURES} columns")
    _require(state == gb.hp.num_leaves * WIDE_FEATURES * gb.hp.n_bins * 16,
             f"hist_state_bytes {state}")
    # (a direction over 2,000 columns takes more rounds than a smoke has)
    _require(len(auc) == args.iters and 0.6 < auc[0] < auc[-1],
             f"valid AUC per round: {auc}")
    calls = copies = stacked = None
    if jax.devices()[0].platform == "tpu":
        text = _fused_program_text(gb)
        calls = text.count("tpu_custom_call")
        # the state with and without its spare row (write_children)
        moved = _hist_state_copies(text, {
            (gb.hp.num_leaves + spare) * 4 * WIDE_FEATURES * gb.hp.n_bins
            for spare in (0, 1)})
        copies = len(moved)
        _require(not moved, f"the per-leaf histogram state is copied or "
                 f"re-tiled inside the tree loop: {moved}")
        kept = _search_candidate_arrays(
            text, 2 * int(gb.config.tpu_split_batch), WIDE_FEATURES,
            gb.hp.n_bins)
        stacked = len(kept)
        _require(not kept, f"the split search holds its candidates' variants "
                 f"side by side or converts a layout: {kept}")
        _require_kernel(text, "histogram_payload_pallas", "hist_kernel",
                        "no payload kernel under hist_kernel in the wide "
                        "round program")
        _require_partition_kernel(text, "the wide round program")
        _require_compaction_kernel(text, "the wide round program")
    _emit("wide", t0, rows=rows, features=WIDE_FEATURES, iters=args.iters,
          hist_col_blocks=blocks, hist_state_bytes=state,
          hist_vmem_budget_bytes=budget, tpu_custom_calls=calls,
          hist_state_copies=copies, search_candidate_arrays=stacked,
          valid_auc_first=auc[0], valid_auc_last=auc[-1],
          smoke_train_s=round(secs, 2))


def phase_predict(args, bst, X):
    import jax
    from lightgbm_tpu.boosting.gbdt import GBDT

    t0 = time.time()
    gb = bst._gbdt
    trees = len(gb.models)
    if args.rehearse_cpu:
        # tiny rehearsal input: lower the switch so the device program runs
        GBDT.DEVICE_PREDICT_MIN_WORK = X.shape[0] * trees
    n_host = min(10_000, X.shape[0] // 2)
    _require(X.shape[0] * trees >= GBDT.DEVICE_PREDICT_MIN_WORK,
             "input too small: predict would walk the trees on the host")
    _require(n_host * trees < GBDT.DEVICE_PREDICT_MIN_WORK,
             "reference slice too large: it would not walk on the host")
    misses0 = gb.metrics.counter("round_compile_misses")
    raw = bst.predict(X, raw_score=True)
    _require(gb.metrics.counter("round_compile_misses") > misses0,
             "device predict program was not built")
    _require(raw.shape == (X.shape[0],) and np.isfinite(raw).all(),
             "device predict: wrong shape or non-finite values")
    dev_raw = raw[:n_host]
    host_raw = gb.predict_raw(X[:n_host])          # host f64 Tree.predict
    np.testing.assert_allclose(dev_raw, host_raw, rtol=RTOL, atol=ATOL)
    _emit("predict", t0, rows=X.shape[0], trees=trees,
          device_predict_min_work=GBDT.DEVICE_PREDICT_MIN_WORK,
          host_walk_rows=n_host,
          max_abs_diff_vs_host_f64=float(np.abs(dev_raw - host_raw).max()),
          peak_bytes_in_use=_peak_bytes(jax.devices()[0]))


def phase_save_load(lgb, bst, X):
    t0 = time.time()
    n = min(50_000, X.shape[0])
    want = bst.predict(X[:n])
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.txt")
        bst.save_model(path)
        loaded = lgb.Booster(model_file=path)
        size = os.path.getsize(path)
    got = loaded.predict(X[:n])
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    _emit("save_load", t0, rows=n, model_bytes=size,
          max_abs_diff=float(np.abs(got - want).max()))


def phase_serve(bst, X):
    from lightgbm_tpu.serving.server import PredictionServer

    t0 = time.time()
    server = PredictionServer()
    try:
        server.publish("higgs", booster=bst)
        publish_s = time.time() - t0
        predictor = server.registry.get("higgs").predictor
        sizes = (1, 64, 4096)
        for n in sizes:
            out = server.predict("higgs", X[:n], raw_score=False)
            np.testing.assert_allclose(out, bst.predict(X[:n]),
                                       rtol=RTOL, atol=ATOL)
            _, stats = predictor.predict_ex(X[:n])
            _require(not stats.fallback,
                     "request served by the host booster")
        fallbacks = server.metrics.counter("serve_host_fallback_requests")
        _require(fallbacks == 0, f"{fallbacks} host-fallback requests")
        _emit("serve", t0, request_rows=sizes,
              requests=server.metrics.counter("serve_requests"),
              host_fallback_requests=fallbacks,
              smoke_publish_warm_s=round(publish_s, 2))
    finally:
        server.close()


def phase_four_chips(args, lgb, data):
    """tree_learner=data over all four chips against tree_learner=serial
    on device 0: same data, same process."""
    import jax

    t0 = time.time()
    X, y, Xv, yv = data
    runs = {}
    for tl in ("data", "serial"):
        p = {**PARAMS, "tree_learner": tl}
        ds = lgb.Dataset(X, label=y, params=p).construct()
        dv = ds.create_valid(Xv, label=yv)
        bst, auc, secs = _train(lgb, p, ds, dv, args.iters)
        _check_auc(auc, args.iters)
        runs[tl] = {"bst": bst, "auc": auc[-1], "secs": round(secs, 2)}
    gb_d, gb_s = runs["data"]["bst"]._gbdt, runs["serial"]["bst"]._gbdt
    _require(gb_d.parallel_mode == "data" and gb_d.mesh.devices.size == 4,
             "tree_learner=data did not build a four-device mesh")
    for name in ("bins", "scores"):
        a = getattr(gb_d, name)
        shards = {s.device: s.data.shape[0] for s in a.addressable_shards}
        _require(len(shards) == 4,
                 f"{name} lives on {len(shards)} device(s)")
        _require(set(shards.values()) == {a.shape[0] // 4},
                 f"{name} is not split evenly: {shards}")
    _require(len(gb_s.bins.devices()) == 1, "serial run spans devices")
    # the serial comparison takes the fused scan; tree_learner=data keeps
    # the per-iteration loop over the shard_map grower (supports_fused()
    # admits no explicit-collective mode), so what is asserted is that its
    # sharded round program ran — and holds the histogram all-reduce
    _require(_took_fused_path(runs["serial"]["bst"], args.iters),
             "serial run did not take the fused path")
    _require(not gb_d.supports_fused(),
             "tree_learner=data now supports the fused scan: assert it ran")
    text = _sharded_program_text(gb_d)
    n_allreduce = text.count("all-reduce(") + text.count("all-reduce-start(")
    _require(n_allreduce > 0, "no all-reduce in the sharded round program")
    # by name too: the trace reduction of the four-chip cell finds the
    # histograms' exchange under this scope (docs/OBSERVABILITY.md)
    _require("hist_allreduce" in text,
             "no operation under the scope hist_allreduce in the sharded "
             "round program")
    if jax.devices()[0].platform == "tpu":
        _require_partition_kernel(text, "the sharded round program")
        _require_compaction_kernel(text, "the sharded round program")
    auc_d, auc_s = runs["data"]["auc"], runs["serial"]["auc"]
    _require(abs(auc_d - auc_s) < 5e-3,
             f"valid AUC data {auc_d} vs serial {auc_s}")
    on_tpu = jax.devices()[0].platform == "tpu"
    _emit("four_chips", t0, rows=args.rows, iters=args.iters,
          data_shards=4, serial_fused=True, data_fused=False,
          data_path="per-iteration loop over the shard_map batched grower",
          all_reduces_in_sharded_program=n_allreduce,
          tpu_custom_calls=text.count("tpu_custom_call") if on_tpu else None,
          valid_auc_data=auc_d, valid_auc_serial=auc_s,
          smoke_train_data_s=runs["data"]["secs"],
          smoke_train_serial_s=runs["serial"]["secs"],
          peak_bytes_in_use=[_peak_bytes(d) for d in jax.devices()])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="training rows (default 1,000,000; the published "
                         "Higgs set has 10,500,000)")
    ap.add_argument("--iters", type=int, default=None,
                    help="boosting rounds (default 20)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only tree_learner=data on four chips and "
                         "its serial comparison")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="rehearse the phases on the CPU backend at a tiny "
                         "size; never prints the success line")
    ap.add_argument("--only", choices=("wide",), default=None,
                    help="run the device phase and this one phase alone")
    args = ap.parse_args(argv)
    tiny = args.rehearse_cpu
    # 100,000 rows is the least at which auto mode engages (K=42 / int8)
    args.rows = args.rows or (100_000 if tiny else 1_000_000)
    args.iters = args.iters or (2 if tiny else 20)
    args.valid_rows = 20_000 if tiny else 200_000

    device = phase_device(args)
    import lightgbm_tpu as lgb
    if args.only == "wide":
        phase_wide(args, lgb)
        print(json.dumps({"ok": not tiny, "only": "wide", "device": device}),
              flush=True)
        return 0
    t0 = time.time()
    data = _make_data(args)
    _emit("data", t0, seed=args.seed, rows=args.rows,
          valid_rows=args.valid_rows)
    if args.chips == 4:
        phase_four_chips(args, lgb, data)
    else:
        bst = phase_train(args, lgb, data)
        phase_bundled(args, lgb)
        phase_categorical(args, lgb)
        phase_ranking(args, lgb)
        phase_wide(args, lgb)
        phase_predict(args, bst, data[0])
        phase_save_load(lgb, bst, data[0])
        phase_serve(bst, data[0])
    if args.rehearse_cpu:
        print(json.dumps({"ok": False, "rehearsal": "cpu: every phase "
                          "passed, nothing was shown about the chip",
                          "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
