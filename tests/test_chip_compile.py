"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached (Higgs widths: F=28, int8, the auto mode's kernel choices).

Interpret mode — what every other test runs the kernels in — cannot see
what the chip's compiler refuses: unaligned slices, too much VMEM, an
operand whose layout pads it past HBM.  These compiles can, at no chip
time.  Nothing runs, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports this file), compiled in the test's own process, with the
persistent compilation cache off (an executable compiled for a described
device cannot be read back without one).  Code that asks
``jax.default_backend()`` would take its CPU branch here; each test
steers it to the TPU branch with ``monkeypatch``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from lightgbm_tpu.learner import batch_grower
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.hist_pallas import (compact_payload_pallas,
                                          histogram_payload_pallas)
from lightgbm_tpu.ops.table import (_sum_per_shard, _take_per_shard,
                                    sum_small_table, take_small_table)

F = 28                      # Higgs features
W = 7                       # packed words per row (4 bins each)
N = 1_048_576
HIGGS_ROWS_PADDED = 10_500_096   # 10.5M rounded up to the 2048-row block
CRITEO_SHARE_ROWS = 13_281_250   # the benchmark's cells: no block divides it


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the program's platform branches (ops/histogram.py
    ``use_pallas``, ops/table.py) to the TPU for the trace."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(one_chip, fn, *shapes):
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in shapes]
    return jax.jit(fn).lower(*avals).compile()


def _assert_kernel(compiled, name):
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    assert name in text, f"{name} is not the kernel the dispatch picked"


ROWS = [((F, N), jnp.uint8), ((N,), jnp.float32), ((N,), jnp.float32),
        ((N,), jnp.int32)]        # bins_t, grad levels, hess levels, lor


@pytest.mark.parametrize("n_bins,K,hist_kernel,words,kernel", [
    (256, 42, "auto", False, "histogram_leaves_radix2_pallas"),
    (256, 4, "auto", False, "histogram_radix_joint_pallas"),
    (64, 42, "auto", True, "histogram_leaves_packed_pallas"),
    (64, 42, "onehot", False, "_histogram_leaves_impl"),
], ids=["radix2-K42-256", "radix_joint-K4-256", "packed-K42-64",
        "flat-K42-64"])
def test_masked_histogram_kernel_compiles(one_chip, on_tpu, n_bins, K,
                                          hist_kernel, words, kernel):
    """The masked multi-leaf pass, through the dispatch the grower calls,
    so block sizes and radix widths are the default path's own."""
    def fn(bins_t, g, h, lor, leaves, words_t=None):
        return H.histogram_for_leaves_masked(
            bins_t, g, h, lor, leaves, n_bins=n_bins, rows_per_block=8192,
            hist_dtype="int8", hist_kernel=hist_kernel,
            bins_words_t=words_t)

    shapes = ROWS + [((K,), jnp.int32)]
    if words:
        shapes.append(((W, N), jnp.int32))
    c = _compile(one_chip, fn, *shapes)
    _assert_kernel(c, kernel)


def test_root_histogram_radix_single_compiles(one_chip, on_tpu):
    def fn(bins_t, g, h):
        return H.root_histogram(bins_t, g, h, n_bins=256,
                                rows_per_block=8192, hist_dtype="int8")

    c = _compile(one_chip, fn, *ROWS[:3])
    _assert_kernel(c, "histogram_radix_single_pallas")


CRITEO_F, CRITEO_W = 67, 17
CRITEO_BUCKET = 3_321_856   # the n/4 bucket of CRITEO_SHARE_ROWS


@pytest.mark.parametrize(
    "source", [((CRITEO_F, CRITEO_SHARE_ROWS), jnp.uint8),
               ((CRITEO_W, CRITEO_SHARE_ROWS), jnp.int32)],
    ids=["bins_t-u8", "words_t-i32"])
def test_payload_histogram_kernel_compiles(one_chip, source):
    """A compacted pass at the cells' rows and largest bucket (n/4): the
    streaming compaction from either resident source and the lane-dense
    payload kernel that reads its result.  Nothing sits between the two:
    no gather, no sort, and no copy re-laying the ``[W+3, S]`` payload
    (the ``[S, 20]`` operand this replaced was padded to 128 lanes by
    one, 1.7 GB a pass)."""
    n, S = CRITEO_SHARE_ROWS, CRITEO_BUCKET

    def fn(src, key, g, h, lor, leaves, cnt):
        pc = compact_payload_pallas(src, key, g, h, lor, size=S)
        return histogram_payload_pallas(
            pc, leaves, cnt, num_f=CRITEO_F, n_bins=256,
            rows_per_block=H._pallas_blk("int8", 256),
            compute_dtype=jnp.int8)

    c = _compile(one_chip, fn, source, ((n,), jnp.int32),
                 ((n,), jnp.float32), ((n,), jnp.float32), ((n,), jnp.int32),
                 ((42,), jnp.int32), ((), jnp.int32))
    _assert_kernel(c, "compact_payload_pallas")
    _assert_kernel(c, "histogram_payload_pallas")
    text = c.as_text()
    assert " gather(" not in text and " sort(" not in text
    moved = [line for line in text.splitlines()
             if " copy(" in line and str(S) in line]
    assert not moved, moved
    # the program's temporaries: the payload, [24, S] i32; what precedes
    # the kernel (``compaction_ranks``: the selected rows' output columns,
    # i32 [n], and the [n/128, 128] product they are cut from); and one
    # [n] word vector that XLA prefetches for the kernel
    assert c.memory_analysis().temp_size_in_bytes <= 24 * S * 4 + 13 * n
    # the counts before every run of rows reach the kernel by scalar
    # prefetch: one a run and the total
    call = [line for line in text.splitlines()
            if "%compact_payload_pallas" in line.split("=")[0]]
    # (the n/4 bucket: runs of 512 rows, eight to a step)
    assert len(call) == 1 and f"s32[{-(-n // 4096) * 8 + 1}]" in call[0]


@pytest.mark.parametrize("n_bins,mirror", [(256, False), (64, True)],
                         ids=["255bins-bins_t", "63bins-words_t"])
def test_compacted_branches_hold_no_gather_and_no_relayout(one_chip, on_tpu,
                                                           n_bins, mirror):
    """The row ladder the grower calls, at the cells' shapes: the n/4 and
    n/8 branches are the two kernels back to back; the keys are not
    sorted, the payload is neither gathered nor copied, and the
    ``[n, 20]`` concatenate (1.27 GB a pass) is gone with them."""
    n = CRITEO_SHARE_ROWS

    def fn(bins, bins_t, g, h, lor, leaves, counts, key, words_t=None):
        return H.histogram_for_leaves_auto(
            bins, bins_t, g, h, lor, leaves, None, n_bins=n_bins,
            rows_per_block=8192, hist_dtype="int8", buckets=(4, 8),
            counts=counts, sort_key=key, bins_words_t=words_t)

    shapes = [((n, CRITEO_F), jnp.uint8), ((CRITEO_F, n), jnp.uint8),
              ((n,), jnp.float32), ((n,), jnp.float32), ((n,), jnp.int32),
              ((42,), jnp.int32), ((42,), jnp.float32), ((n,), jnp.int32)]
    if mirror:
        shapes.append(((CRITEO_W, n), jnp.int32))
    text = _compile(one_chip, fn, *shapes).as_text()
    source = f"s32[{CRITEO_W},{n}]" if mirror else f"u8[{CRITEO_F},{n}]"
    for S in (CRITEO_BUCKET, 1_660_928):
        compact = [line for line in text.splitlines()
                   if "%compact_payload_pallas" in line.split("=")[0]
                   and f"s32[24,{S}]" in line]
        assert len(compact) == 1 and source in compact[0], (S, compact)
        name = compact[0].split("=")[0].strip()
        readers = [line for line in text.splitlines()
                   if name + "," in line or name + ")" in line]
        assert len(readers) == 1, readers
        assert "%histogram_payload_pallas" in readers[0].split("=")[0]
        assert f"hist_rows_{S}/hist_kernel" in readers[0]
        assert "hist_rows_" not in compact[0] and "hist_compact" in compact[0]
        # the pass is counted by its ONE kernel under hist_rows_<S>
        assert sum("tpu_custom_call" in line and f"hist_rows_{S}/" in line
                   for line in text.splitlines()) == 1
    # what precedes the compaction kernel (the ranks, on the keys) is the
    # compaction's own time: under hist_compact, in no hist_rows_ scope
    ranks = [line for line in text.splitlines()
             if "jit(compaction_ranks)" in line]
    assert any("convolution" in line for line in ranks)
    assert all("hist_compact/jit(compact_payload_pallas)" in line
               and "hist_rows_" not in line for line in ranks)
    assert " gather(" not in text and " sort(" not in text
    assert ",20]" not in text


CRITEO_HALF = 6_641_664     # the n/2 bucket of CRITEO_SHARE_ROWS


@pytest.mark.parametrize("K,fallback", [
    (42, "_histogram_leaves_impl"), (16, "histogram_leaves_radix2_pallas")],
    ids=["K42-flat", "K16-radix2"])
def test_half_rows_rung_compiles_at_the_benchmark_rows(one_chip, on_tpu, K,
                                                       fallback):
    """The row ladder's top rung under the K = 42 and K = 16 round bodies
    at 255 bins, at the cells' shape (13,281,250 x 67, int8): the bucket
    of n/2 rows is the two kernels back to back under the scope a trace
    counts it by, the full pass's kernel stays as the fallback branch,
    and the ``i32[24, n/2]`` payload adds nothing to the program's
    temporaries."""
    n, S = CRITEO_SHARE_ROWS, CRITEO_HALF
    assert H.hist_dispatch("auto", 256, K, CRITEO_F).top_rung == 2

    def fn(bins, bins_t, g, h, lor, leaves, counts, key):
        return H.histogram_for_leaves_auto(
            bins, bins_t, g, h, lor, leaves, None, n_bins=256,
            rows_per_block=8192, hist_dtype="int8", buckets=(),
            counts=counts, sort_key=key)

    c = _compile(one_chip, fn, ((n, CRITEO_F), jnp.uint8),
                 ((CRITEO_F, n), jnp.uint8), ((n,), jnp.float32),
                 ((n,), jnp.float32), ((n,), jnp.int32), ((K,), jnp.int32),
                 ((K,), jnp.float32), ((n,), jnp.int32))
    text = c.as_text()
    _assert_kernel(c, fallback)
    compact = [line for line in text.splitlines()
               if "%compact_payload_pallas" in line.split("=")[0]]
    assert len(compact) == 1 and f"s32[24,{S}]" in compact[0], compact
    assert "hist_rows_" not in compact[0] and "hist_compact" in compact[0]
    reader = [line for line in text.splitlines()
              if "%histogram_payload_pallas" in line.split("=")[0]]
    assert len(reader) == 1 and f"hist_rows_{S}/hist_kernel" in reader[0]
    assert " gather(" not in text and " sort(" not in text
    # the branches exclude each other, so the payload (638 MB) lies in
    # the bytes of the full branch's padded operands: 70 feature rows of
    # u8 and three word vectors, 1.09 GB, and a word vector to spare
    assert 24 * S * 4 < c.memory_analysis().temp_size_in_bytes \
        <= (70 + 3 * 4 + 4) * (n + 2048)


K_ARGS = [((42,), jnp.int32)] * 10   # cols lo hi pos default_left miss parents new valid smaller


def _partition(bins_t, lor, mask, *per_slot):
    # the kernel batch_grower's default path selects
    return batch_grower.partition_select_pallas(
        bins_t, lor, mask, *per_slot, rows_per_block=2048)


@pytest.mark.parametrize("f,n", [(F, N), (67, CRITEO_SHARE_ROWS),
                                 (220, 7_325_625)],
                         ids=["higgs", "criteo_share", "istella"])
def test_partition_kernel_compiles(one_chip, f, n):
    """At the cells' own shapes, whose row counts no block divides, the
    kernel reads the resident bins as they lie: its last block is ragged,
    so the program pads nothing and holds no copy of the bin matrix."""
    c = _compile(one_chip, _partition, ((f, n), jnp.uint8),
                 ((n,), jnp.int32), ((n,), jnp.int32), *K_ARGS)
    _assert_kernel(c, "partition_select_pallas")
    u8_pads = [line for line in c.as_text().splitlines()
               if " pad(" in line and "u8[" in line]
    assert not u8_pads, u8_pads
    assert c.memory_analysis().temp_size_in_bytes < f * n


def test_partition_kernel_with_left_sets_compiles(one_chip):
    """The static variant a job with categorical columns runs (cell
    ``allstate-cat-train``: 13,184,290 x 32, K = 42): every slot goes by
    its left set, 8 words of 32 bins (a numeric slot's made from its
    ranges before the kernel); the word by compares, the bit by a shift.
    No ``[K, n]`` temporary (42 x 13.18M i32 would be 2.2 GB), no
    gather."""
    f, n = 32, 13_184_290
    c = _compile(one_chip, _partition, ((f, n), jnp.uint8),
                 ((n,), jnp.int32), ((n,), jnp.int32), *K_ARGS,
                 ((8, 42), jnp.int32), ((42,), jnp.int32))
    _assert_kernel(c, "partition_select_pallas")
    assert " gather(" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 4 * n


@pytest.mark.parametrize("n,size", [(N, 255), (CRITEO_SHARE_ROWS, 31),
                                    (CRITEO_SHARE_ROWS, 255),
                                    (CRITEO_SHARE_ROWS, 2048)])
def test_take_small_table_kernel_compiles(one_chip, on_tpu, n, size):
    """At the cells' row count and the smallest and largest one-hot of
    ``hi`` (16 and 128 sublanes).  The program around the call keeps the
    shapes every cell's round program compiled with: the index padded to
    whole blocks, the result sliced back (``_take_pallas`` says why)."""
    c = _compile(one_chip, take_small_table, ((size,), jnp.float32),
                 ((n,), jnp.int32))
    _assert_kernel(c, "_take_pallas")
    assert c.memory_analysis().temp_size_in_bytes <= 8 * (n + 8192)


def test_take_small_table_compiles_per_shard_for_four_chips(topo):
    """tree_learner=data updates scores from the row-sharded leaf map a
    shard_map grower returns.  The chip refuses the bare kernel there
    ("Mosaic kernels cannot be automatically partitioned", first met on
    four chips in PR 24); the per-shard form take_small_table routes to
    compiles for the 2x2 mesh, at the four-chip cell's rows a chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices), ("data",))
    n = 4 * CRITEO_SHARE_ROWS
    idx = jax.ShapeDtypeStruct((n,), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    table = jax.ShapeDtypeStruct((255,), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    c = _take_per_shard(mesh, P("data")).lower(idx, table).compile()
    _assert_kernel(c, "_take_pallas")
    assert c.memory_analysis().temp_size_in_bytes <= 8 * (
        CRITEO_SHARE_ROWS + 8192)


@pytest.mark.parametrize("masked", [False, True], ids=["all_rows", "masked"])
def test_sum_small_table_kernel_compiles_at_the_benchmark_rows(
        one_chip, on_tpu, masked):
    """Leaf renewal's sums at the cells' own row count, whose tail the
    kernel masks by row number: no operand is padded, so the program
    holds no temporary at all."""
    n = CRITEO_SHARE_ROWS
    rows = [((n,), jnp.int32), ((n,), jnp.float32), ((n,), jnp.float32)]
    if masked:
        c = _compile(one_chip, lambda i, g, h, m: sum_small_table(
            i, g, h, m, 255), *rows, ((n,), jnp.bool_))
    else:
        c = _compile(one_chip, lambda i, g, h: sum_small_table(
            i, g, h, None, 255), *rows)
    _assert_kernel(c, "_sum_pallas")
    assert "scatter" not in c.as_text()
    # the mask alone is widened for the kernel (bool -> i32, 53 MB)
    assert c.memory_analysis().temp_size_in_bytes <= (4 * n + 4096) * masked


def test_sum_small_table_compiles_per_shard_for_four_chips(topo):
    """tree_learner=data renews leaves from row-sharded gradients and a
    row-sharded leaf map: the kernel runs on each chip's rows and one
    all-reduce adds the [255] partial sums (the bare kernel is refused
    there, as the score update's was in PR 24)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices), ("data",))
    rows = NamedSharding(mesh, P("data"))
    avals = [jax.ShapeDtypeStruct((N,), d, sharding=rows)
             for d in (jnp.int32, jnp.float32, jnp.float32, jnp.bool_)]
    for masked in (False, True):
        c = _sum_per_shard(mesh, P("data"), masked, 255).lower(
            *avals[:3 + masked]).compile()
        _assert_kernel(c, "_sum_pallas")
        assert "all-reduce" in c.as_text()


def _fused_round_text(monkeypatch, one_chip, X, y, params, rounds=8):
    """Compiled text of the round program ``train_fused`` builds for the
    job, for the described chip (the booster is built on the CPU; only
    the runner's trace sees a TPU)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting import gbdt as gbdt_mod
    from lightgbm_tpu.ops.compile_cache import GLOBAL_COMPILE_CACHE

    class Captured(Exception):
        pass

    captured = {}
    real = gbdt_mod.cc_get_or_build

    def spy(key, build, **kw):
        fn = real(key, build, **kw)

        def call(*args):
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            avals = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip), args)
            captured["text"] = fn.lower(*avals).compile().as_text()
            raise Captured
        return call

    monkeypatch.setattr(gbdt_mod, "cc_get_or_build", spy)
    try:
        with pytest.raises(Captured):
            lgb.train(params, lgb.Dataset(X, label=y, params=params),
                      num_boost_round=rounds)
    finally:
        GLOBAL_COMPILE_CACHE.clear()     # a runner traced for the TPU
    return captured["text"]


def test_fused_round_program_renews_leaves_with_the_kernel(topo, one_chip,
                                                           monkeypatch):
    """The round program ``train_fused`` builds for a quantised job,
    compiled for the described chip: ``leaf_renew`` holds the kernel by
    name (what the benchmark's breakdown and chip_smoke.py look for) and
    the scatter-add is gone from it."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(3210, 8))
    y = (X @ rng.normal(size=8) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, "tpu_split_batch": 4,
              "use_quantized_grad": True, "quant_train_renew_leaf": True}
    text = _fused_round_text(monkeypatch, one_chip, X, y, params)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "leaf_renew" in line]
    assert len(calls) == 1
    assert "%_sum_pallas" in calls[0]
    assert "leaf_renew/jit(_sum_pallas)/pallas_call" in calls[0]
    assert not any("leaf_renew" in line and "scatter" in line
                   for line in text.splitlines())


@pytest.mark.parametrize("f", [2000, 67])
def test_fused_round_program_carries_the_histogram_state_in_one_layout(
        topo, one_chip, monkeypatch, f):
    """The round program of a 2,000-column job and of a 67-column one,
    compiled for the described chip: the per-leaf histogram state has ONE
    tiled layout in every loop (bins on the lanes, a leaf's slab
    contiguous), its slabs are read by ``dynamic-slice`` and written in
    place by ``dynamic-update-slice``, and nothing in a ``while`` body
    gives the state, or half of it in blocks, anew (``chip_smoke.py``
    counts the same on the chip: ``hist_state_copies``).  Until PR 46 the
    loop carried the state as ``[L, F, C, B]`` tiles for the scatters and
    converted all of it to ``[L, C, F, B]`` tiles twice a round pass for
    the gathers, which themselves copied seven column blocks of it out at
    2,000 columns and re-tiled all of it at 67.  And the split search
    under ``find_splits`` holds no array of its candidates' variants side
    by side and converts no layout (``search_candidate_arrays``): until PR
    47 a ``[2K, F, 256, 5]`` fusion with the variants on the sublanes, its
    ``reshape`` to ``[2K, F x 1280]`` and that one's ``copy``, before one
    argmax."""
    import re
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    try:
        import chip_smoke
    finally:
        sys.path.remove(repo)
    rng = np.random.default_rng(46)
    leaves, batch = 15, 8
    X = rng.normal(size=(2048, f))
    y = (X[:, :50].sum(axis=1) > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": leaves, "verbose": -1,
              "min_data_in_leaf": 1, "tpu_split_batch": batch, "max_bin": 255,
              "tpu_hist_dtype": "int8", "use_quantized_grad": True,
              "quant_train_renew_leaf": True}
    text = _fused_round_text(monkeypatch, one_chip, X, y, params)
    state = rf"f32\[{leaves + 1},4,{f},256\]"
    # one tiling, whichever memory space (``S(1)``) a small state is given
    layouts = {re.sub(r"S\(\d+\)", "", tiles)
               for tiles in re.findall(state + r"\{([^}]*)\}", text)}
    assert layouts == {"3,2,1,0:T(8,128)"}, layouts
    assert re.search(state + r"\{[^}]*\} dynamic-update-slice\(", text)
    counts = {(leaves + spare) * 4 * f * 256 for spare in (0, 1)}
    assert chip_smoke._hist_state_copies(text, counts) == []
    assert 'find_splits/' in text
    assert chip_smoke._search_candidate_arrays(text, 2 * batch, f, 256) == []


# ------------------------------------------- 2,000 columns (PR 45: Epsilon)
EPSILON = (2000, 400_000)       # the cell epsilon-train: columns, rows
ISTELLA = (220, 7_325_696)      # istella-rank-train's rows, whole blocks


def _scoped_bytes(compiled, kernel):
    """What each custom call of ``kernel`` states as its scoped VMEM."""
    import re
    return [int(m.group(1)) for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and f"%{kernel}" in line
            for m in [re.search(r'scoped_memory_configs":\[\{"memory_space":'
                                r'"1","offset":"0","size":"(\d+)"', line)] if m]


def _rows(f, n):
    return [((f, n), jnp.uint8), ((n,), jnp.float32), ((n,), jnp.float32),
            ((n,), jnp.int32)]


# (the float32 kernels' six-pass products take the compiler 210-260 s
# each here: they run with the slow tests)
@pytest.mark.parametrize("K,dtype,kernel", [
    (42, "int8", "_histogram_leaves_impl"),
    pytest.param(42, "float32", "_histogram_leaves_impl",
                 marks=pytest.mark.slow),
    (4, "int8", "histogram_radix_joint_pallas"),
    pytest.param(4, "float32", "histogram_radix_joint_pallas",
                 marks=pytest.mark.slow),
], ids=["flat-K42-int8", "flat-K42-f32", "joint-K4-int8", "joint-K4-f32"])
def test_masked_pass_compiles_at_2000_columns(one_chip, on_tpu, K, dtype,
                                              kernel):
    """A full masked pass at the Epsilon job's shapes through the dispatch
    the grower calls: the parent held ``[3K, 2000 x 256]`` resident (258 MB
    at K = 42) and unrolled 125 chunks; column blocks of one tile (32
    columns) fit the budget the kernel states."""
    from lightgbm_tpu.ops import hist_pallas as HP

    def fn(bins_t, g, h, lor, leaves):
        return H.histogram_for_leaves_masked(
            bins_t, g, h, lor, leaves, n_bins=256, rows_per_block=8192,
            hist_dtype=dtype)

    c = _compile(one_chip, fn, *_rows(*EPSILON), ((K,), jnp.int32))
    _assert_kernel(c, kernel)
    stated = _scoped_bytes(c, kernel)
    assert stated and max(stated) <= HP.VMEM_BUDGET_BYTES, stated


def test_root_pass_compiles_at_2000_columns(one_chip, on_tpu):
    """The root's radix kernel in 63 column blocks (500 unrolled chunks
    in one block took the compiler 21 minutes)."""
    def fn(bins_t, g, h):
        return H.root_histogram(bins_t, g, h, n_bins=256,
                                rows_per_block=8192, hist_dtype="int8")

    c = _compile(one_chip, fn, *_rows(*EPSILON)[:3])
    _assert_kernel(c, "histogram_radix_single_pallas")


@pytest.mark.parametrize("K,dtype", [
    (42, "int8"), (4, "int8"),
    pytest.param(42, "float32", marks=pytest.mark.slow)],
    ids=["K42-int8", "K4-int8", "K42-f32"])
def test_compacted_pass_compiles_at_2000_columns(one_chip, K, dtype):
    """The n/2 bucket of a round pass: the compaction at 504 payload rows
    (sixteen plane groups, 512-row blocks) and the payload kernel over
    column blocks of 8 word rows, the three riding rows beside them."""
    from lightgbm_tpu.ops import hist_pallas as HP
    f, n = EPSILON
    S = 200_704

    def fn(src, key, g, h, lor, leaves, cnt):
        pc = compact_payload_pallas(src, key, g, h, lor, size=S)
        return histogram_payload_pallas(
            pc, leaves, cnt, num_f=f, n_bins=256,
            rows_per_block=H._pallas_blk(dtype, 256),
            compute_dtype=jnp.dtype(dtype).type)

    c = _compile(one_chip, fn, ((f, n), jnp.uint8), ((n,), jnp.int32),
                 ((n,), jnp.float32), ((n,), jnp.float32), ((n,), jnp.int32),
                 ((K,), jnp.int32), ((1,), jnp.int32))
    _assert_kernel(c, "histogram_payload_pallas")
    _assert_kernel(c, "compact_payload_pallas")
    for kernel in ("histogram_payload_pallas", "compact_payload_pallas"):
        stated = _scoped_bytes(c, kernel)
        assert stated and max(stated) <= HP.VMEM_BUDGET_BYTES, stated
    assert HP.col_blocks(2000, 3 * K * 256 * 4, 4)[1] > 1


def test_partition_kernel_compiles_at_2000_columns(one_chip):
    """The block of bins with its casts is 2000 x 2048 x 8 B = 33 MB: over
    Mosaic's default, inside the budget, and said so."""
    from lightgbm_tpu.ops import hist_pallas as HP
    f, n = EPSILON
    K = 42
    c = _compile(one_chip, _partition,
                 ((f, n), jnp.uint8), ((n,), jnp.int32), ((n,), jnp.int32),
                 *[((K,), jnp.int32)] * 10)
    _assert_kernel(c, "partition_select_pallas")
    (stated,) = set(_scoped_bytes(c, "partition_select_pallas"))
    assert stated == HP.vmem_limit(8 * f * 2048) <= HP.VMEM_BUDGET_BYTES
    assert " pad(" not in c.as_text()


def test_the_ranking_cells_flat_pass_states_its_27_mib(one_chip, on_tpu):
    """``istella-rank-train``'s K = 42 flat pass keeps a 27.1 MiB output
    block; it compiled only while XLA's memory-space assignment kept that
    output in VMEM for it, and stopped when PR 44 took two row vectors out
    of the round program (``Scoped allocation with size 27.97M and limit
    16.00M``).  Alone, with whole row blocks and so no row-sized buffer
    beside it, the kernel now states the block and its body's share."""
    from lightgbm_tpu.ops import hist_pallas as HP
    f, n = ISTELLA

    def fn(bins_t, g, h, lor, leaves):
        return H.histogram_for_leaves_masked(
            bins_t, g, h, lor, leaves, n_bins=256, rows_per_block=8192,
            hist_dtype="int8", hist_kernel="onehot")

    c = _compile(one_chip, fn, *_rows(f, n), ((42,), jnp.int32))
    _assert_kernel(c, "_histogram_leaves_impl")
    block = 126 * 220 * 256 * 4
    assert _scoped_bytes(c, "_histogram_leaves_impl") == \
        [block + HP._VMEM_BODY_BYTES]
    assert HP.col_blocks(220, 126 * 256 * 4, 11) == (220, 1)
    assert not [line for line in c.as_text().splitlines()
                if " pad(" in line and str(n) in line]


def test_one_tree_at_the_cell_s_shapes_never_visits_virtual_space(
        one_chip, on_tpu):
    """(From ``tests/test_allstate_cell.py``, PR 48: that file was the
    suite's longest, and compiles for a described chip belong in this one
    file.)  One tree of the job of ``allstate-train`` (13,184,290 rows, 46
    bundle columns over 4,228 features, K=42, int8) compiled for the
    described chip: nothing
    in the program has the virtual ``[*, 4228, 256]`` shape (the
    expansion's tables are dropped as unused arguments), the split search
    sits under its scope, and the partition is the fused kernel."""
    import re
    from lightgbm_tpu.learner.grower import BundleSearch, DeviceBundle
    from lightgbm_tpu.obs.metrics import global_metrics
    from lightgbm_tpu.ops.split import SplitHyper
    n, fb, fv, b = 13_184_290, 46, 4228, 256
    A = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    i32 = jnp.int32
    search = BundleSearch(*([A((fv,), i32)] * 3 + [A((fb, b), i32)] * 6))
    bundle = DeviceBundle(A((fv,), i32), A((fv, b), i32), A((fv, b), jnp.bool_),
                          A((fv,), i32), A((fv, b), i32), search)
    hp = SplitHyper(num_leaves=255, min_data_in_leaf=0,
                    min_sum_hessian_in_leaf=100.0, hist_dtype="int8",
                    n_bins=256, rows_per_block=8192)
    before = global_metrics.counter("bundle_expand_calls")
    c = batch_grower.grow_tree_batched.lower(
        A((n, fb), jnp.uint8), A((n,), jnp.float32), A((n,), jnp.float32),
        None, A((fv,), i32), A((fv,), i32), A((fv,), jnp.bool_), None, hp,
        batch=42, bundle=bundle, hist_scale=A((2,), jnp.float32)).compile()
    assert global_metrics.counter("bundle_expand_calls") == before
    text = c.as_text()
    assert not re.findall(r"\[[\d,]*4228,256[\d,]*\]", text)
    assert "bundle_search" in text and "partition_select_pallas" in text
    # the fused kernel under its scope, reading the resident bins as they
    # lie: no row-sized pad there (the bins: a copy of 606 MB a round pass)
    import chip_smoke
    chip_smoke._require_partition_kernel(text, "the bundled tree")
    m = c.memory_analysis()
    # rehearsal on this tree: 1.40 GB of temporaries, 0.74 GB of arguments
    assert m.temp_size_in_bytes < 2 * 1024 ** 3


def test_partition_at_published_higgs_rows_stays_lane_dense(one_chip):
    """Size guard: at the published 10.5M rows the partition step's
    results are two [1, n] i32 vectors, 84 MB.  A kernel that emitted an
    [n, small] i32 result here (the removed payload variant's [n, 10]) is
    padded 12.8x by the TPU's (8, 128) tiling — 5.0 GB for 0.4 GB of data
    — and the whole-tree program no longer fits 16 GB of HBM."""
    n = HIGGS_ROWS_PADDED
    c = _compile(one_chip, _partition, ((F, n), jnp.uint8),
                 ((n,), jnp.int32), ((n,), jnp.int32), *K_ARGS)
    m = c.memory_analysis()
    out_and_temp = m.output_size_in_bytes + m.temp_size_in_bytes
    # rehearsal on this tree: 0.084 GB of output, no temporaries; the
    # payload variant's output alone took 5.01 GB
    assert out_and_temp < 256 * 1024 * 1024, \
        f"partition step needs {out_and_temp / 1e9:.2f} GB at 10.5M rows"
