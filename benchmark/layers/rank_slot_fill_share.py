"""Share of the ranking plan's padded doc slots that hold a doc:
100 x ``rank_docs`` / ``rank_slot_rows``, the program's own counters of
the job (obs/metrics.py), which the driver reads from the booster's
registry in its path check.  What the per-query sorts and the pair step
read beyond the docs is the rest.  ``None`` against a program without
the counters."""


def read(run):
    counts = run.get("rank_counts") or {}
    if not counts.get("rank_slot_rows"):
        return None
    return 100.0 * counts["rank_docs"] / counts["rank_slot_rows"]
