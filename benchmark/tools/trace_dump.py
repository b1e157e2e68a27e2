"""Print the structure of an ``.xplane.pb``: planes, lines, and the first
events of each line with their stats: for looking at one real trace by
hand before trusting the reduction.

    python3 benchmark/tools/trace_dump.py <file.xplane.pb> [events-per-line]
"""

import sys

from jax.profiler import ProfileData


def main(path: str, per_line: int = 4) -> None:
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                stats = {k: (str(v)[:160]) for k, v in ev.stats}
                print(f"    {ev.name[:100]!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={stats}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 4)
