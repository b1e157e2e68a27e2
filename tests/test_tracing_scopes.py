"""What the program names itself (PR 28): the device scopes of the round
program, the one span API on the profiler's clock, the counted rows of
the histogram passes and the compile seconds.

A ``jax.named_scope`` writes operation metadata only, so the first thing
held here is that naming changes no number."""

import glob
import json
import re

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import gbdt as gbdt_mod
from lightgbm_tpu.obs import compile_events, trace as obs_trace
from lightgbm_tpu.obs.metrics import COUNTERS, global_metrics
from lightgbm_tpu.utils.timer import phase

PARAMS = {"objective": "binary", "metric": ["auc"], "num_leaves": 15,
          "min_data_in_leaf": 5, "verbose": -1, "tpu_split_batch": 4,
          "use_quantized_grad": True, "quant_train_renew_leaf": True}


def _data(n=3000, f=8, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    w = rng.normal(size=f)
    y = ((X @ w + 0.3 * X[:, 0] * X[:, 1]
          + rng.normal(scale=0.5, size=n)) > 0).astype(np.float64)
    return X, y


def _train(rounds, n=3000, n_valid=600, seed=5, **extra):
    X, y = _data(n + n_valid, seed=seed)
    params = {**PARAMS, **extra}
    ds = lgb.Dataset(X[:n], label=y[:n], params=params)
    dv = ds.create_valid(X[n:], label=y[n:])
    evals = {}
    bst = lgb.train(params, ds, num_boost_round=rounds, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(evals)])
    assert bst._gbdt.metrics.counter("fused_rounds") == rounds
    return bst, X[:n]


# ------------------------------------------------------------ device scopes
def _runner_paths(seed):
    captured = {}
    real = gbdt_mod.cc_get_or_build

    def spy(key, build, **kw):
        fn = real(key, build, **kw)

        def call(*args):
            # full paths exist only once XLA has inlined the calls: the
            # compiled text, from the one compile the job pays anyway
            compiled = fn.lower(*args).compile()
            captured["text"] = compiled.as_text()
            return compiled(*args)
        return call

    gbdt_mod.cc_get_or_build = spy
    try:
        _train(8, seed=seed)
    finally:
        gbdt_mod.cc_get_or_build = real
    paths = set(re.findall(r'op_name="([^"]*)"', captured["text"]))
    assert paths, "the lowered text carries no operation name"
    return [p.split("/") for p in paths]


@pytest.fixture(scope="module")
def lowered_paths():
    """Every operation name path (the metadata's ``op_name``) of the
    fused runner of a small binary job with a valid set."""
    return _runner_paths(11)


@pytest.fixture(scope="module")
def lowered_paths_with_the_sum_kernel():
    """The same with leaf renewal's sums on the path a TPU takes
    (``ops/table.py _sum_pallas``, here in interpret mode)."""
    from lightgbm_tpu.ops import quantize, table
    from lightgbm_tpu.ops.compile_cache import GLOBAL_COMPILE_CACHE

    def kernel_sums(idx, g, h, mask, size):
        return table._sum_pallas(idx, g, h, mask, size=size, interpret=True)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(quantize, "sum_small_table", kernel_sums)
        try:
            return _runner_paths(14)
        finally:
            GLOBAL_COMPILE_CACHE.clear()     # a runner traced with the patch


@pytest.fixture(scope="module")
def lowered_paths_with_the_payload_kernels():
    """The same with the compacted pass on the path a TPU takes (the
    compaction kernel and the payload kernel of ops/hist_pallas.py, here
    in interpret mode)."""
    from lightgbm_tpu.ops import histogram
    from lightgbm_tpu.ops.compile_cache import GLOBAL_COMPILE_CACHE

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(histogram, "_PAYLOAD_TEST_INTERPRET", True)
        # the hook is read when the jitted grower is traced: no trace
        # from before the patch may answer, none with it may stay
        jax.clear_caches()
        try:
            return _runner_paths(15)
        finally:
            GLOBAL_COMPILE_CACHE.clear()
            jax.clear_caches()


def _nested(parts, names):
    """``names`` appear in ``parts`` in this order (not necessarily
    adjacent: ``while/body`` and ``jit(...)`` parts sit between)."""
    it = iter(parts)
    return all(any(p == n for p in it) for n in names)


# n = 3000 rows, blocks of 2048: the row ladder has the one bucket 2048
SCOPES = ["gradients", "quantize", "tree_root", "tree_select", "leaf_renew",
          "score_update", "valid_score", "valid_metric",
          "round_hist/hist_compact", "round_hist/hist_kernel",
          "round_hist/hist_update", "round_hist/hist_rows_full",
          "round_hist/hist_rows_2048",
          "round_hist/hist_rows_full/hist_kernel",
          "round_hist/hist_rows_2048/hist_compact",
          "round_hist/hist_rows_2048/hist_kernel",
          "tree_root/hist_rows_full/hist_kernel",
          "tree_select/partition", "tree_select/round_hist",
          "tree_select/find_splits"]


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_runner_carries_scope(lowered_paths, scope):
    names = scope.split("/")
    assert any(_nested(parts, names) for parts in lowered_paths), scope


def test_leaf_renew_holds_the_sum_kernel(lowered_paths,
                                         lowered_paths_with_the_sum_kernel):
    """``score_update_ms`` reads scope ``leaf_renew`` whichever path the
    sums take: XLA's scatter-add off the TPU, the kernel under its own
    name (the trace's ``_sum_pallas`` line) on it."""
    assert any(_nested(parts, ["leaf_renew"])
               and any(p.startswith("scatter") for p in parts)
               for parts in lowered_paths)
    assert not any("jit(_sum_pallas)" in parts for parts in lowered_paths)
    inside = [parts for parts in lowered_paths_with_the_sum_kernel
              if "jit(_sum_pallas)" in parts]
    assert inside
    assert all(_nested(parts, ["leaf_renew", "jit(_sum_pallas)"])
               for parts in inside)
    assert not any(_nested(parts, ["leaf_renew"])
                   and any(p.startswith("scatter") for p in parts)
                   for parts in lowered_paths_with_the_sum_kernel)
    # the other scopes are where they were
    for scope in ("score_update", "tree_select/round_hist", "valid_score"):
        assert any(_nested(parts, scope.split("/"))
                   for parts in lowered_paths_with_the_sum_kernel), scope


def test_a_compacted_pass_is_two_kernels_and_one_counted_pass(
        lowered_paths_with_the_payload_kernels):
    """``benchmark/harness/scoped.py`` counts a pass as one operation
    whose path ends in ``pallas_call`` under a ``hist_rows_*`` scope, and
    gives time to the innermost scope.  A compacted pass is the ranks of
    its selected rows (``compaction_ranks``: XLA operations on the keys),
    the compaction kernel and the payload kernel: the first two sit under
    ``hist_compact`` and under NO ``hist_rows_`` part, so the pass counts
    once and ``hist_compact_ms`` holds all of the compaction's time; the
    payload kernel sits under exactly one."""
    paths = lowered_paths_with_the_payload_kernels
    # (every operation of the jitted kernel function: the interpreter
    # unrolls the ``pallas_call`` that ends the path on the chip)
    compact = [parts for parts in paths
               if "jit(compact_payload_pallas)" in parts]
    ranks = [parts for parts in paths if "jit(compaction_ranks)" in parts]
    payload = [parts for parts in paths
               if "jit(histogram_payload_pallas)" in parts]
    assert compact and ranks and payload
    for parts in compact:
        assert _nested(parts, ["round_hist", "hist_compact",
                               "jit(compact_payload_pallas)"])
        assert not any(p.startswith("hist_rows_") for p in parts)
        assert "hist_kernel" not in parts
    # what precedes the kernel is part of the compaction, not a pass
    for parts in ranks:
        assert _nested(parts, ["round_hist", "hist_compact",
                               "jit(compact_payload_pallas)",
                               "jit(compaction_ranks)"])
    assert any(parts[-1].startswith("dot_general") for parts in ranks)
    for parts in payload:
        assert _nested(parts, ["round_hist", "hist_rows_2048", "hist_kernel",
                               "jit(histogram_payload_pallas)"])
        assert sum(p.startswith("hist_rows_") for p in parts) == 1
        assert "hist_compact" not in parts
    # the sort of the keys and the row gather are off this path
    assert not any(_nested(parts, ["round_hist", "hist_compact"])
                   and parts[-1].startswith(("sort", "gather"))
                   for parts in paths)


@pytest.mark.parametrize("scope", ["partition", "round_hist", "find_splits"])
def test_accepted_scopes_stay_path_parts_of_their_own(lowered_paths, scope):
    # benchmark/harness/tracered.py matches these three as whole parts
    assert any(scope in parts for parts in lowered_paths)
    # and no other scope's name holds one of them (jitted functions'
    # own names, ``jit(partition_select_pallas)``, are not scopes)
    assert not any(scope in p and p != scope and not p.startswith("jit(")
                   for parts in lowered_paths for p in parts), scope


def test_rows_scopes_sit_outside_compact_and_kernel(lowered_paths):
    for parts in lowered_paths:
        rows = [i for i, p in enumerate(parts) if p.startswith("hist_rows_")]
        if rows:
            before = parts[:rows[0]]
            assert "hist_compact" not in before and "hist_kernel" not in before


# ------------------------------------------------- naming changes no number
def _fingerprint(bst):
    return bst.model_to_string(), np.asarray(bst._gbdt.scores).tobytes()


@pytest.fixture(scope="module")
def plain_fingerprint():
    return _fingerprint(_train(8, seed=12)[0])


def test_bit_identical_under_a_profiler_session(plain_fingerprint, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _fingerprint(_train(8, seed=12)[0])
    finally:
        jax.profiler.stop_trace()
    assert traced == plain_fingerprint


def test_bit_identical_with_trace_output(plain_fingerprint, tmp_path):
    out = tmp_path / "spans.json"
    recorded = _fingerprint(_train(8, seed=12, trace_output=str(out))[0])
    # the parameter is part of the model text's parameter block only
    strip = lambda text: re.sub(r"\[trace_output: [^\]]*\]\n", "", text)
    assert strip(recorded[0]) == strip(plain_fingerprint[0])
    assert recorded[1] == plain_fingerprint[1]
    names = {e["name"] for e in json.loads(out.read_text())["traceEvents"]}
    assert {"train", "booster_init", "fused_round_scan",
            "dispatch_done"} <= names


# ------------------------------------------ spans on the profiler's clock
def _program_spans(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name[len("lgbtpu."):], int(ev.start_ns),
                              int(ev.start_ns + ev.duration_ns),
                              {k: v for k, v in ev.stats})
                             for ev in line.events
                             if ev.name.startswith("lgbtpu."))
    return sorted(spans, key=lambda s: s[1])


def test_job_spans_nest_under_train_in_the_host_plane(tmp_path):
    rounds = 42                     # two dispatches of 21 rounds
    assert gbdt_mod.GBDT.fused_chunks(rounds) == [21, 21]
    jax.profiler.start_trace(str(tmp_path))
    try:
        _train(rounds, seed=13)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(tmp_path)
    (train,) = [s for s in spans if s[0] == "train"]
    by_name = {}
    for name, a, b, counts in spans:
        by_name.setdefault(name, []).append((a, b, counts))
        if name != "train":
            assert train[1] <= a and b <= train[2], name
    assert set(by_name) >= {"booster_init", "train_fused", "fused_prepare",
                            "fused_round_scan", "fused_chunk_transfer",
                            "tree_finalize", "callbacks", "dispatch_done"}
    for name in ("fused_prepare", "fused_round_scan", "fused_chunk_transfer",
                 "dispatch_done"):
        assert len(by_name[name]) == 2, name
    assert len(by_name["tree_finalize"]) == rounds
    assert len(by_name["callbacks"]) == rounds
    # construction ends before the first dispatch is prepared, and every
    # phase of a dispatch lies inside train_fused, in order
    (fused,) = by_name["train_fused"]
    assert by_name["booster_init"][0][1] <= by_name["fused_prepare"][0][0]
    for i in range(2):
        order = [by_name[n][i] for n in ("fused_prepare", "fused_round_scan",
                                         "fused_chunk_transfer",
                                         "dispatch_done")]
        assert all(x[1] <= y[0] for x, y in zip(order, order[1:]))
        assert fused[0] <= order[0][0] and order[-1][1] <= fused[1]
    done = [c for _, _, c in by_name["dispatch_done"]]
    assert sum(int(c["rounds"]) for c in done) == rounds
    assert sum(int(c["trees"]) for c in done) == rounds
    assert all(int(c["hist_rows_selected"]) > 0 for c in done)


# ------------------------------------------------------ hist_rows_selected
def _brute_force_rows(bst, X):
    """Per tree: every row for the root pass plus, per split, the rows
    of the smaller child, from where the rows really land."""
    total = 0
    for tree in bst._gbdt.models:
        leaves = np.bincount(tree.predict_leaf_index(X),
                             minlength=tree.num_leaves)

        def count(child):
            if child < 0:
                return int(leaves[-child - 1])
            return count(int(tree.left_child[child])) \
                + count(int(tree.right_child[child]))
        total += len(X)
        for node in range(tree.num_leaves - 1):
            total += min(count(int(tree.left_child[node])),
                         count(int(tree.right_child[node])))
    return total


def test_hist_rows_selected_equals_a_brute_force_count():
    before = global_metrics.counter("hist_rows_selected")
    bst, X = _train(8, n=2000, seed=14)
    counted = bst._gbdt.metrics.counter("hist_rows_selected")
    assert counted == _brute_force_rows(bst, X)
    assert global_metrics.counter("hist_rows_selected") - before == counted
    assert bst.telemetry()["counters"]["hist_rows_selected"] == counted
    assert "hist_rows_selected" in COUNTERS


def test_hist_rows_selected_is_what_the_histogram_call_was_given(monkeypatch):
    from lightgbm_tpu.learner import batch_grower
    seen = []
    real = batch_grower.histogram_for_leaves_auto

    def spy(*args, counts=None, **kw):
        jax.debug.callback(lambda c: seen.append(float(c)),
                           counts.sum())
        return real(*args, counts=counts, **kw)

    monkeypatch.setattr(batch_grower, "histogram_for_leaves_auto", spy)
    n = 2003                        # a shape no other test has traced
    bst, _ = _train(1, n=n, seed=15)
    jax.effects_barrier()
    assert seen, "the patched histogram call was never traced"
    assert bst._gbdt.metrics.counter("hist_rows_selected") == n + sum(seen)


def test_other_modes_leave_the_count_out():
    bst, _ = _train(8, n=2000, seed=14, histogram_pool_size=0.05)
    if 0 < bst._gbdt.hp.hist_pool_slots < bst._gbdt.hp.num_leaves:
        assert bst._gbdt.metrics.counter("hist_rows_selected") == 0
    else:
        pytest.skip("the pool did not bind at this size")


# -------------------------------------------------- what a span costs off
def test_no_session_no_recorder_a_span_appends_nothing(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("an annotation was built with no session open")
    monkeypatch.setattr(obs_trace, "_TraceAnnotation",
                        type("NoSession", (), {
                            "is_enabled": staticmethod(lambda: False),
                            "__init__": boom}))
    assert obs_trace.active() is None
    bst, _ = _train(8, seed=16)
    assert bst._gbdt.timer.as_dict() == {}
    span = phase("anything", bst._gbdt.timer, rows=3)
    with span:
        pass
    assert span._t0 is None and span._ann is None
    assert bst._gbdt.timer.as_dict() == {} and obs_trace.active() is None


def _span_counts(tmp_path, n, tag):
    out = tmp_path / f"{tag}.json"
    _train(42, n=n, seed=17, trace_output=str(out))
    counts = {}
    for e in json.loads(out.read_text())["traceEvents"]:
        # the compile stages' spans (``jit_*``, obs/compile_events.py)
        # follow the programs a process has yet to compile, not the job
        if e.get("ph") == "X" and not e["name"].startswith("jit_"):
            counts[e["name"]] = counts.get(e["name"], 0) + 1
    return counts


def test_span_count_follows_dispatches_and_trees_not_rows(tmp_path):
    small = _span_counts(tmp_path, 1000, "small")
    large = _span_counts(tmp_path, 3000, "large")
    assert small == large
    dispatches, trees = 2, 42
    # the train and the valid Dataset construct inside the job's span
    # (a valid set takes the training set's bin mappers) and one valid
    # set's bins are placed and mirrored in ``booster_init``; a serial
    # booster places its scores and the objective's row array, a valid
    # set's scores and bins
    assert small == {"train": 1, "booster_init": 1, "objective_init": 1,
                     "place": 4, "valid_mirror": 1,
                     "train_fused": 1, "fused_operands": 1,
                     "construct": 2,
                     "dense_bin_mappers": 1, "dense_bin_matrix": 2,
                     "fused_prepare": dispatches,
                     "fused_round_scan": dispatches,
                     "fused_chunk_transfer": dispatches,
                     "dispatch_done": dispatches,
                     "tree_finalize": trees, "callbacks": trees}


# --------------------------------------------------------- compile seconds
SECONDS = ("jaxpr_trace_s", "xla_lowering_s", "xla_backend_compile_s",
           "xla_cache_load_s")


def _seconds():
    return {name: global_metrics.counter(name) for name in SECONDS}


def test_compile_seconds_rise_on_a_first_compile_only():
    assert compile_events.install()
    salt = float(np.random.default_rng().integers(1, 1 << 30))
    fn = jax.jit(lambda x: (x * salt + 1.0).sum())
    x = np.arange(7.0)
    before, lowered = _seconds(), global_metrics.counter("xla_program_lowerings")
    fn(x).block_until_ready()
    first = _seconds()
    assert first["jaxpr_trace_s"] > before["jaxpr_trace_s"]
    assert first["xla_lowering_s"] > before["xla_lowering_s"]
    assert first["xla_backend_compile_s"] + first["xla_cache_load_s"] \
        > before["xla_backend_compile_s"] + before["xla_cache_load_s"]
    assert global_metrics.counter("xla_program_lowerings") == lowered + 1
    fn(x).block_until_ready()       # the in-process cache serves it
    assert _seconds() == first
    assert global_metrics.counter("xla_program_lowerings") == lowered + 1


def test_cache_retrieval_is_taken_out_of_the_backend_seconds():
    assert compile_events.install()
    before = _seconds()
    events = global_metrics.counter("xla_compile_events")
    # what jax 0.9 emits on a persistent-cache hit: the retrieval, then
    # the backend event that spans it
    compile_events._on_duration_event(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    compile_events._on_duration_event(
        "/jax/core/compile/backend_compile_duration", 0.75, fun_name="f")
    after = _seconds()
    assert after["xla_cache_load_s"] - before["xla_cache_load_s"] \
        == pytest.approx(0.5)
    assert after["xla_backend_compile_s"] - before["xla_backend_compile_s"] \
        == pytest.approx(0.25)
    assert global_metrics.counter("xla_compile_events") == events + 1
    # a compile with no retrieval before it keeps all of its seconds
    compile_events._on_duration_event(
        "/jax/core/compile/backend_compile_duration", 2.0)
    assert _seconds()["xla_backend_compile_s"] \
        - after["xla_backend_compile_s"] == pytest.approx(2.0)


def test_nested_traces_count_their_outermost_only():
    assert compile_events.install()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    before = global_metrics.counter("jaxpr_trace_s")
    # outer opens, two inner traces open and close inside it
    compile_events._on_scalar_event(trace, 100.0, fun_name="outer")
    for _ in range(2):
        compile_events._on_scalar_event(trace, 100.5, fun_name="inner")
        compile_events._on_duration_event(trace, 1.0, fun_name="inner")
    compile_events._on_duration_event(trace, 3.0, fun_name="outer")
    assert global_metrics.counter("jaxpr_trace_s") - before \
        == pytest.approx(3.0)
    # and for real: an outer jit whose trace holds an inner jit's trace
    # reports less than the two durations added
    seen = []
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(
        lambda e, d, **kw: seen.append(d) if "jaxpr_trace" in e else None)
    salt = float(np.random.default_rng().integers(1, 1 << 30))
    inner = jax.jit(lambda x: x * salt)
    outer = jax.jit(lambda x: inner(x) + inner(x + 1.0))
    before = global_metrics.counter("jaxpr_trace_s")
    outer(np.arange(5.0)).block_until_ready()
    counted = global_metrics.counter("jaxpr_trace_s") - before
    assert len(seen) >= 2 and 0 < counted < sum(seen)
    assert counted == pytest.approx(max(seen))


@pytest.mark.parametrize("name", SECONDS + ("hist_rows_selected",))
def test_new_counters_are_declared(name):
    assert name in COUNTERS
