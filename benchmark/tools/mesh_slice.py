"""Pin what ``harness/mesh_trace.py`` reads in a slice that
``tools/trace_slice.py`` cut from a trace of several chips: adds
``expect_mesh`` (the busiest chip and every chip's numbers) to the
gzipped table, for ``tests/test_mesh_trace.py``.

    python3 benchmark/tools/mesh_slice.py <slice.json.gz> [out.json.gz]
"""

import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import mesh_trace  # noqa: E402


def main(argv) -> None:
    with gzip.open(argv[1], "rt") as fh:
        table = json.load(fh)
    r = mesh_trace.reduce_table(table)
    table["expect_mesh"] = {
        "busiest": r["busiest"],
        "chips": [{k: v for k, v in chip.items() if not isinstance(v, dict)}
                  for chip in r["chips"]]}
    with gzip.open(argv[2] if len(argv) > 2 else argv[1], "wt") as fh:
        json.dump(table, fh, separators=(",", ":"))
    print(json.dumps(table["expect_mesh"], indent=1))


if __name__ == "__main__":
    main(sys.argv)
