"""What the histogram passes of a wide dense job HAVE to move, and what the
one-hot formulation computes for them, from shapes and counts alone (PR 45,
the cell ``epsilon-train``).  Kept with the benchmark so that every PR
computes ``wide_hist_roofline_share`` the same way.

A pass over ``rows`` selected rows of ``features`` columns into ``hists``
leaves' histograms has to read each selected row's bin bytes once (one
byte a column) with its 12 bytes of gradient, hessian and leaf id, and to
write each histogram's ``[features, bins, 3]`` float32 sums once.  Nothing
else is necessary whatever implements the pass: not the rows a pass
streams past to find the selected ones, not a payload written and read
back, not the per-leaf state's update.  So the share of the HBM roofline
this gives can only read UNDER what the kernels achieve of the memory
system: 100% would be a pass that moves nothing but this at the peak rate.

The one-hot formulation's arithmetic, for the log: each selected row meets
``features x bins`` one-hot columns in each of ``3 K`` value channels of
the split batch (two operations a multiply-add)."""

from __future__ import annotations

#: peaks of one chip by ``device_kind`` (Google Cloud documentation, "TPU
#: v5e": 819 GB/s of HBM, 393 TOP/s in int8, 197 TFLOP/s in bf16).  A
#: device that is not here is an error, not a default.
PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12,
                         "bf16_ops_per_s": 197e12}}

ROW_RIDERS_BYTES = 12       # gradient, hessian, leaf id: 4 bytes each
CHANNELS = 3                # gradient, hessian, count


def pass_bytes(rows: int, features: int, bins: int, hists: int) -> int:
    """Bytes the passes have to move: ``rows`` selected rows in, ``hists``
    histograms out."""
    return (rows * (features + ROW_RIDERS_BYTES)
            + hists * features * bins * CHANNELS * 4)


def onehot_ops(rows: int, features: int, bins: int, batch: int) -> int:
    """MXU operations of the one-hot contraction over ``rows`` selected
    rows with ``batch`` leaf slots a pass."""
    return 2 * rows * features * bins * CHANNELS * batch


def roofline_s(nbytes: int, device_kind: str) -> float:
    """Seconds the device's HBM needs for ``nbytes``."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return nbytes / PEAKS[device_kind]["hbm_bytes_per_s"]
