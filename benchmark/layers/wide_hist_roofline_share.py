"""Share of the HBM roofline the histogram passes of a wide dense job reach
(the cell ``epsilon-train``), in percent: the seconds the chip's memory
needs for what the passes HAVE to move (each selected row's bin bytes and
its 12 riding bytes once, each built leaf's ``[F, bins, 3]`` float32 sums
once: ``harness/wide_bytes.py``) over the device seconds of the compaction
and histogram kernels.  The one-hot formulation is bound by its compares,
not by memory, so this reads low; the run's log (``wide_hist:``) gives the
formulation's MXU operations beside it."""

from harness import wide_trace

read = wide_trace.roofline_share
