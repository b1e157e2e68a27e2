"""chip_smoke.py refuses to report success without a chip, and the
compile-cache helper leaves the cache where the machine placed it."""

import json
import os
import subprocess
import sys

import jax
import pytest

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
                         timeout=900)      # 381 s in the driver's PR 47 run
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
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    assert [ln.get("phase") for ln in lines[:-1]] == ["device", "wide"]
    assert lines[-1] == {"ok": False, "only": "wide",
                         "device": lines[-1]["device"]}


# ----------------------------------------- the per-leaf state's one layout
_STATE = "f32[256,4,2000,256]{3,2,1,0:T(8,128)}"
_OTHER = "f32[256,4,2000,256]{2,3,1,0:T(4,128)}"
_SLAB = "f32[1,4,2000,256]{3,2,1,0:T(8,128)}"
_MOVED = "f32[256,2000,256,4]{2,1,3,0:T(8,128)}"
_BLOCK = "f32[256,4,256,256]{3,2,1,0:T(8,128)}"
_BLOCKS_SIG = ", ".join(["f32[256,4,256,256]"] * 7)
_BLOCKS = ", ".join([_BLOCK] * 5 + ["/*index=5*/" + _BLOCK, _BLOCK])
_BLOCKS_OF = ", ".join(["%mini-gather-slice.1"] * 7)


def _round_program(in_round_body="", in_entry="", in_slot_body=""):
    """A compiled round program cut down to what ``_hist_state_copies``
    reads, in the text form of the chip's compiler: the tree loop's body
    (called from the entry's ``while``), the K slots' loop inside it, whose
    body writes one slab into the state in place through a fusion."""
    import textwrap
    return textwrap.dedent(f"""\
        HloModule jit_run, is_scheduled=true

        %fused_write (param_0.1: f32[256,4,2000,256], param_1.2: f32[1,4,2000,256], param_2.3: s32[]) -> f32[256,4,2000,256] {{
          %param_0.1 = {_STATE} parameter(0)
          %param_1.2 = {_SLAB} parameter(1)
          %param_2.3 = s32[]{{:T(128)}} parameter(2)
          %constant.1 = s32[]{{:T(128)}} constant(0)
          ROOT %dynamic_update_slice.8 = {_STATE} dynamic-update-slice(%param_0.1, %param_1.2, %param_2.3, %constant.1, %constant.1, /*index=5*/%constant.1), metadata={{op_name="jit(run)/round_hist/hist_update/while/body/dynamic_update_slice"}}
        }}

        %fused_relayout (param_0.4: f32[256,4,2000,256]) -> f32[256,4,2000,256] {{
          %param_0.4 = {_STATE} parameter(0)
          ROOT %transpose.1 = {_OTHER} transpose(%param_0.4), dimensions={{0,1,2,3}}
        }}

        %fused_moved (param_0.5: f32[256,4,2000,256]) -> f32[256,2000,256,4] {{
          %param_0.5 = {_STATE} parameter(0)
          ROOT %transpose.2 = {_MOVED} transpose(%param_0.5), dimensions={{0,2,3,1}}
        }}

        %fused_blocks (param_0.6: f32[256,4,2000,256]) -> ({_BLOCKS_SIG}) {{
          %param_0.6 = {_STATE} parameter(0)
          %mini-gather-slice.1 = {_BLOCK} slice(%param_0.6), slice={{[0:256], [0:4], [0:256], [0:256]}}
          ROOT %tuple.9 = ({_BLOCKS}) tuple({_BLOCKS_OF})
        }}

        %fused_write_pair (param_0.7: f32[256,4,2000,256], param_1.7: f32[1,4,2000,256], param_2.7: s32[]) -> (f32[256,4,2000,256], f32[1,4,2000,256]) {{
          %param_0.7 = {_STATE} parameter(0)
          %param_1.7 = {_SLAB} parameter(1)
          %param_2.7 = s32[]{{:T(128)}} parameter(2)
          %constant.7 = s32[]{{:T(128)}} constant(0)
          %negate.7 = {_SLAB} negate(%param_1.7)
          %dynamic_update_slice.7 = {_STATE} dynamic-update-slice(%param_0.7, %negate.7, %param_2.7, %constant.7, %constant.7, /*index=5*/%constant.7)
          ROOT %tuple.7 = ({_STATE}, {_SLAB}) tuple(%dynamic_update_slice.7, %negate.7)
        }}

        %fused_copy_pair (param_0.8: f32[256,4,2000,256], param_1.8: f32[1,4,2000,256]) -> (f32[256,4,2000,256], f32[1,4,2000,256]) {{
          %param_0.8 = {_STATE} parameter(0)
          %param_1.8 = {_SLAB} parameter(1)
          %negate.8 = {_SLAB} negate(%param_1.8)
          %negate.9 = {_STATE} negate(%param_0.8)
          ROOT %tuple.8 = ({_STATE}, {_SLAB}) tuple(%negate.9, %negate.8)
        }}

        %slot_body (arg.1: (s32[], f32[256,4,2000,256], f32[84,4,2000,256])) -> (s32[], f32[256,4,2000,256], f32[84,4,2000,256]) {{
          %arg.1 = (s32[]{{:T(128)}}, {_STATE}, f32[84,4,2000,256]{{3,2,1,0:T(8,128)}}) parameter(0)
          %get-tuple-element.1 = s32[]{{:T(128)}} get-tuple-element(%arg.1), index=0
          %get-tuple-element.2 = {_STATE} get-tuple-element(%arg.1), index=1
          %get-tuple-element.3 = f32[84,4,2000,256]{{3,2,1,0:T(8,128)}} get-tuple-element(%arg.1), index=2
          %dynamic-slice.1 = {_SLAB} dynamic-slice(%get-tuple-element.3, %get-tuple-element.1), dynamic_slice_sizes={{1,4,2000,256}}
          {in_slot_body}
          %dynamic-slice_dynamic-update-slice_fusion.1 = {_STATE} fusion(%get-tuple-element.2, %dynamic-slice.1, %get-tuple-element.1), kind=kLoop, calls=%fused_write
          ROOT %tuple.1 = (s32[]{{:T(128)}}, {_STATE}, f32[84,4,2000,256]{{3,2,1,0:T(8,128)}}) tuple(%get-tuple-element.1, %dynamic-slice_dynamic-update-slice_fusion.1, %get-tuple-element.3)
        }}

        %slot_cond (arg.2: (s32[], f32[256,4,2000,256], f32[84,4,2000,256])) -> pred[] {{
          %arg.2 = (s32[]{{:T(128)}}, {_STATE}, f32[84,4,2000,256]{{3,2,1,0:T(8,128)}}) parameter(0)
          ROOT %constant.2 = pred[]{{:T(512)}} constant(true)
        }}

        %round_body (arg.3: (s32[], f32[256,4,2000,256])) -> (s32[], f32[256,4,2000,256]) {{
          %arg.3 = (s32[]{{:T(128)}}, {_STATE}) parameter(0)
          %get-tuple-element.4 = s32[]{{:T(128)}} get-tuple-element(%arg.3), index=0
          %get-tuple-element.5 = {_STATE} get-tuple-element(%arg.3), index=1
          %copy.7 = f32[84,4,2000,256]{{3,2,1,0:T(8,128)}} copy(%get-tuple-element.5)
          {in_round_body}
          %tuple.2 = (s32[]{{:T(128)}}, {_STATE}, f32[84,4,2000,256]{{3,2,1,0:T(8,128)}}) tuple(%get-tuple-element.4, %get-tuple-element.5, %copy.7)
          %while.1 = (s32[]{{:T(128)}}, {_STATE}, f32[84,4,2000,256]{{3,2,1,0:T(8,128)}}) while(%tuple.2), condition=%slot_cond, body=%slot_body
          %get-tuple-element.6 = {_STATE} get-tuple-element(%while.1), index=1
          ROOT %tuple.3 = (s32[]{{:T(128)}}, {_STATE}) tuple(%get-tuple-element.4, %get-tuple-element.6)
        }}

        %round_cond (arg.4: (s32[], f32[256,4,2000,256])) -> pred[] {{
          %arg.4 = (s32[]{{:T(128)}}, {_STATE}) parameter(0)
          ROOT %constant.3 = pred[]{{:T(512)}} constant(true)
        }}

        ENTRY %main.9 (Arg_0.1: s32[]) -> f32[256,4,2000,256] {{
          %Arg_0.1 = s32[]{{:T(128)}} parameter(0)
          %constant.4 = f32[]{{:T(128)}} constant(0)
          %broadcast.1 = {_STATE} broadcast(%constant.4), dimensions={{}}
          {in_entry}
          %tuple.4 = (s32[]{{:T(128)}}, {_STATE}) tuple(%Arg_0.1, %broadcast.1)
          %while.2 = (s32[]{{:T(128)}}, {_STATE}) while(%tuple.4), condition=%round_cond, body=%round_body
          ROOT %get-tuple-element.7 = {_STATE} get-tuple-element(%while.2), index=1
        }}
        """)


_STATE_CASES = [
    ("nowhere", "", []),
    # the two copies a round pass that the state cost until PR 46
    ("in_round_body",
     f"%copy.465 = {_OTHER} copy(%get-tuple-element.5)", ["%copy.465"]),
    ("in_slot_body",
     f"%copy.466 = {_STATE} copy(%get-tuple-element.2)", ["%copy.466"]),
    ("in_round_body",
     f"%fusion.9 = {_OTHER} fusion(%get-tuple-element.5), kind=kLoop, "
     "calls=%fused_relayout", ["%fusion.9"]),
    # the state moved to another logical shape: a materialised
    # ``_channels_last(st["hist"])``, as a fusion or a bare transpose
    ("in_round_body",
     f"%fusion.10 = {_MOVED} fusion(%get-tuple-element.5), kind=kLoop, "
     "calls=%fused_moved", ["%fusion.10"]),
    ("in_round_body",
     f"%transpose.3 = {_MOVED} transpose(%get-tuple-element.5), "
     "dimensions={0,2,3,1}", ["%transpose.3"]),
    ("in_round_body",
     f"%copy-start.1 = ({_STATE}, {_STATE}, u32[]{{:S(2)}}) "
     "copy-start(%get-tuple-element.5)", ["%copy-start.1"]),
    # a fusion of several results: one that writes the state in place
    # beside a slab does not count, one that gives the state anew does
    ("in_slot_body",
     f"%fusion.11 = ({_STATE}, {_SLAB}) fusion(%get-tuple-element.2, "
     "%dynamic-slice.1, %get-tuple-element.1), kind=kLoop, "
     "calls=%fused_write_pair", []),
    ("in_slot_body",
     f"%fusion.12 = ({_STATE}, {_SLAB}) fusion(%get-tuple-element.2, "
     "%dynamic-slice.1), kind=kLoop, calls=%fused_copy_pair",
     ["%fusion.12"]),
    # the compiler's blocked gather of ``st["hist"][parents]``: seven
    # column blocks of the state copied out a round pass
    ("in_round_body",
     f"%fusion.13 = ({_BLOCKS}) fusion(%get-tuple-element.5), kind=kLoop, "
     "calls=%fused_blocks", ["%fusion.13"]),
    # outside every loop the state may be made and moved
    ("in_entry", f"%copy.1 = {_OTHER} copy(%broadcast.1)", []),
]


@pytest.mark.parametrize("sigil", ["%", ""], ids=["percent_names", "bare_names"])
@pytest.mark.parametrize("where,line,found", _STATE_CASES, ids=[
    "one_layout", "copy_in_the_tree_loop", "copy_in_the_slot_loop",
    "fusion_that_changes_the_layout", "fusion_to_another_shape",
    "transpose_to_another_shape", "async_copy", "in_place_beside_a_slab",
    "state_anew_beside_a_slab", "blocked_gather", "copy_outside_every_loop"])
def test_hist_state_copies_reads_the_compiled_text(where, line, found, sigil):
    """``chip_smoke.py``'s engagement check of the state's one layout, on
    a canned text: a ``copy``, ``transpose`` or ``fusion`` in a ``while``
    body that gives the state's element count anew (under any shape,
    alone or in a tuple), or half of it in blocks, counts; the in-place
    write fusion, a copy of a slab stack and anything outside a loop do
    not.  Both ways the compiler has printed names, with ``%`` and bare."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    text = _round_program(**{where: line}) if line else _round_program()
    counts = {rows * 4 * 2000 * 256 for rows in (255, 256)}
    got = chip_smoke._hist_state_copies(text.replace("%", sigil), counts)
    assert [g.split(" = ")[0] for g in got] == found


# the round body of ``_round_program`` with the split search's instructions
# as the chip's compiler printed them at 2,000 columns, K = 42 (PR 47)
_SEARCH_OP = ('metadata={op_name="jit(run)/while/body/closed_call/'
              'jit(grow_tree_batched)/tree_select/while/body/%s"}')
_KIDS = "f32[84,2000,256]{2,1,0:T(8,128)}"
_NEXT = "\n" + " " * 10      # a second instruction, at ``_round_program``'s margin
_SEARCH_CASES = [
    ("nothing_of_the_search", "in_round_body", "", []),
    # what the search writes since PR 47: the bins' cumulative sums, a
    # plane each, and a feature's best gain and its place
    ("cumulative_sums_and_the_reduction", "in_round_body",
     f"%fusion.520 = {_KIDS} fusion(%copy.7), kind=kOutput, "
     "calls=%fused_write, " + _SEARCH_OP % "find_splits/vmap()/dot_general"
     + _NEXT + "%fusion.534 = (f32[84,2000]{1,0:T(8,128)S(1)}, "
     "s32[84,2000]{1,0:T(8,128)S(1)}) fusion(%fusion.520), kind=kLoop, "
     "calls=%fused_write, " + _SEARCH_OP % "find_splits/vmap()/reduce", []),
    # the parent's: five variants stacked, the variants on the sublanes
    ("five_variants_stacked", "in_round_body",
     "%maximum_maximum_fusion.5 = f32[84,2000,256,5]{0,3,2,1:T(8,128)} "
     "fusion(%copy.7), kind=kLoop, calls=%fused_write, "
     + _SEARCH_OP % "find_splits/vmap()/concatenate",
     ["%maximum_maximum_fusion.5"]),
    ("the_stack_flattened_and_copied", "in_round_body",
     "%reshape.2223 = f32[84,2560000]{0,1:T(8,128)} reshape(%copy.7), "
     + _SEARCH_OP % "find_splits/vmap()/reshape"
     + _NEXT + "%copy.102 = f32[84,2560000]{1,0:T(8,128)} copy(%reshape.2223), "
     + _SEARCH_OP % "find_splits/vmap()/reshape",
     ["%reshape.2223", "%copy.102"]),
    ("two_variants_stacked", "in_round_body",
     "%fusion.77 = f32[84,2000,256,2]{3,2,1,0:T(2,128)} fusion(%copy.7), "
     "kind=kLoop, calls=%fused_write, "
     + _SEARCH_OP % "find_splits/vmap()/concatenate", ["%fusion.77"]),
    # one variant's worth, converted: a layout copy of a plane
    ("a_plane_copied", "in_round_body",
     "%copy.101 = f32[84,2000,256,1]{0,3,2,1:T(1,128)} copy(%copy.7), "
     + _SEARCH_OP % "find_splits/vmap()/broadcast_in_dim", ["%copy.101"]),
    ("a_plane_transposed", "in_round_body",
     "%transpose.9 = f32[2000,84,256]{2,1,0:T(8,128)} transpose(%copy.7), "
     "dimensions={1,0,2}, " + _SEARCH_OP % "find_splits/vmap()/transpose",
     ["%transpose.9"]),
    # the children's slabs joined under the scope are the search's input
    ("the_childrens_slabs", "in_round_body",
     "%concatenate.3 = f32[84,2000,256,4]{2,1,3,0:T(8,128)} "
     "concatenate(%copy.7, %copy.7), dimensions={0}, "
     + _SEARCH_OP % "find_splits/concatenate", []),
    # the kernels' output has the count of two variants and is round_hist's
    ("the_kernels_output", "in_round_body",
     "%reshape.9 = f32[42,2000,256,4]{2,1,3,0:T(8,128)} reshape(%copy.7), "
     + _SEARCH_OP % "round_hist/hist_update/reshape", []),
    # a small copy under the scope: 84 winners' columns
    ("a_copy_of_the_winners", "in_round_body",
     "%copy.5 = f32[84,2000]{0,1:T(8,128)} copy(%copy.7), "
     + _SEARCH_OP % "find_splits/vmap()/gather", []),
    # outside every loop the root's search may do as it likes
    ("outside_every_loop", "in_entry",
     "%reshape.1 = f32[84,2560000]{0,1:T(8,128)} reshape(%broadcast.1), "
     + _SEARCH_OP % "tree_root/find_splits/reshape", []),
]


@pytest.mark.parametrize("name,where,lines,found", _SEARCH_CASES,
                         ids=[c[0] for c in _SEARCH_CASES])
def test_search_candidate_arrays_reads_the_compiled_text(name, where, lines,
                                                         found):
    """``chip_smoke.py``'s engagement check of the split search, on a
    canned text: under the scope ``find_splits`` in a loop, an array of
    2 to 5 variants of ``[2K, F, bins]`` candidates counts under any shape,
    and so does a ``copy``, ``transpose`` or ``reshape`` of one variant's
    worth or more; what the search writes since PR 47, the children's
    slabs, ``round_hist``'s output and anything outside a loop do not."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    text = _round_program(**{where: lines})
    got = chip_smoke._search_candidate_arrays(text, 84, 2000, 256)
    assert [g.split(" = ")[0] for g in got] == found
