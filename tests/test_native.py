"""Native C++ data-plane tests (reference's parser/bin-push are C++:
src/io/parser.cpp, bin.h ValueToBin — parity vs the NumPy fallback)."""

import os

import numpy as np
import pytest

try:
    from lightgbm_tpu.native import apply_bins_numerical, parse_text
    HAVE_NATIVE = True
except ImportError:  # no compiler in this environment
    HAVE_NATIVE = False

pytestmark = pytest.mark.skipif(not HAVE_NATIVE,
                                reason="native toolchain unavailable")

BIN_TRAIN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                         "examples", "binary_classification", "binary.train")


def test_parse_matches_numpy():
    ours = parse_text(BIN_TRAIN, sep="\t", skip_header=0)
    ref = np.loadtxt(BIN_TRAIN)
    assert ours.shape == ref.shape
    np.testing.assert_allclose(ours, ref, rtol=1e-12)


def test_parse_csv_with_missing(tmp_path):
    p = tmp_path / "x.csv"
    p.write_text("1.5,2,3\n4,,6\n7,8,nan\n")
    arr = parse_text(str(p), sep=",")
    assert arr.shape == (3, 3)
    assert np.isnan(arr[1, 1]) and np.isnan(arr[2, 2])
    assert arr[0, 0] == 1.5 and arr[2, 1] == 8


def test_parse_header_skip(tmp_path):
    p = tmp_path / "h.csv"
    p.write_text("a,b\n1,2\n3,4\n")
    arr = parse_text(str(p), sep=",", skip_header=1)
    np.testing.assert_array_equal(arr, [[1, 2], [3, 4]])


def test_apply_bins_matches_python():
    from lightgbm_tpu.io.binning import BinMapper
    rng = np.random.default_rng(0)
    vals = rng.normal(size=200_000)
    vals[rng.random(len(vals)) < 0.05] = np.nan
    m = BinMapper.find_bin(vals, total_sample_cnt=len(vals), max_bin=63,
                           min_data_in_bin=3, use_missing=True,
                           zero_as_missing=False)
    native = apply_bins_numerical(
        vals, np.asarray(m.bin_upper_bound), m.missing_type,
        m.num_bin - 1 if m.missing_type == 2 else -1, m.default_bin)
    # python reference path (force it by slicing under the native threshold)
    py = np.concatenate([m.values_to_bins(vals[i:i + 50_000])
                         for i in range(0, len(vals), 50_000)])
    np.testing.assert_array_equal(native.astype(np.int32), py)


def test_dataset_from_file_uses_native_transparently():
    """End-to-end: Dataset(path) parses + bins identically to before."""
    import lightgbm_tpu as lgb
    ds = lgb.Dataset(BIN_TRAIN, params={"verbose": -1}).construct()
    ref = np.loadtxt(BIN_TRAIN)
    assert ds.num_data() == ref.shape[0] == 500
    assert ds.num_feature() == ref.shape[1] - 1 == 28
