"""One run of a sparse-rows cell, exactly as ``run.py`` makes it (driver,
window, trace, comparison, per-layer readers), on a VARIANT of its
configuration: what the cell would read if the source's job differed in
one stated respect.  The variant is given key by key over the
configuration's blocks and is never a cell of the benchmark: its limits
are the cell's own, so ``correct`` may read false and refuses nothing.

    python3 benchmark/tools/variant_csr.py --data pos_rate=0.0073 \\
        --any-leaves -- --workload allstate-train --seed 1 --seconds 40 --trace 1

``--data k=v,...`` / ``--params k=v,...`` / ``--compare k=v,...`` go over
the configuration's blocks of those names; ``--any-leaves`` drops the
driver's requirement that every tree of the window has ``efb.leaves``
leaves (labels at the challenge's own positive rate let
``min_sum_hessian_in_leaf`` stop a tree short).  What follows ``--`` is
``run.py``'s own command line; the lines printed are ``run.py``'s, the
window's dispatches (``train_round_ms``) and the trace's passes among them.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))
sys.path.insert(2, os.path.join(HERE, "tools"))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    cut = argv.index("--") if "--" in argv else len(argv)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    for block in ("data", "params", "compare"):
        ap.add_argument("--" + block, default="")
    ap.add_argument("--any-leaves", action="store_true")
    args = ap.parse_args(argv[:cut])
    import run as bench
    from control_csr import parsed
    real = bench.find_cell

    def variant(name, rehearse_cpu=False):
        manifest, cell, cfg, traffic = real(name, rehearse_cpu)
        cfg = dict(cfg, **{block: {**cfg[block], **parsed(getattr(args, block))}
                           for block in ("data", "params", "compare")})
        if args.any_leaves:
            cfg["efb"] = {k: v for k, v in cfg["efb"].items() if k != "leaves"}
        return manifest, cell, cfg, traffic
    bench.find_cell = variant
    return bench.main(argv[cut + 1:])


if __name__ == "__main__":
    sys.exit(main())
