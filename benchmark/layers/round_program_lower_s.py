"""Seconds of tracing and lowering of the ONE program that runs the
rounds (the fused runner; in ``criteo-dp4-train`` the tree program),
over all its lowerings in the process: the warm-up's dispatches and the
window's job.  The program is found by the span it is called in
(``harness/compile_table.py`` ``round_program``).  ``None`` against a
program without the compile table."""

from harness import compile_table


def read(run):
    return compile_table.round_program_lower_s(compile_table.rows())
