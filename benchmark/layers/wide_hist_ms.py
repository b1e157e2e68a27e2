"""Device milliseconds per round of ALL histogram work in a wide dense job
(the cell ``epsilon-train``): everything under the scope ``round_hist``
(the kernels over their column blocks, the compaction, the per-leaf
state's update) plus the root pass's histogram, which sits under
``tree_root`` (``harness/wide_trace.py``)."""

from harness import wide_trace

read = wide_trace.hist_ms_per_round
