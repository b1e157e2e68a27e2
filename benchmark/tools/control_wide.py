"""``tools/control.py`` for a wide dense job (the cell ``epsilon-train``,
PR 45): the same readings (sound jobs, the bfloat16 control, planted
faults, each through the cell's own comparison), with the faults of
``tools/faults_wide.py`` beside the accepted ones.

    python3 benchmark/tools/control_wide.py --workload epsilon-train \\
        --dispatches 1 --bf16 --faults drop_col_block shift_col_block \\
        skip_state_update [--rehearse-cpu]

``control.py`` looks a fault up in ``faults.HISTOGRAM`` and calls it with
``feature=`` the data's heaviest column; this file adds the wide faults
to that table for the life of the process and hands over."""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import control  # noqa: E402  (puts benchmark/ and the checkout on the path)
import faults  # noqa: E402
import faults_wide  # noqa: E402


def main(argv=None) -> int:
    faults.HISTOGRAM.update(faults_wide.WIDE)
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
