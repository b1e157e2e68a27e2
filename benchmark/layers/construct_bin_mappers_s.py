"""Host seconds of the dense ``Dataset.construct`` in the program's span
``dense_bin_mappers`` (``io/dataset.py`` ``_construct_mappers``: the row
sample and every feature's bin finder), from the program's always-armed
counter of this name: set-up lies outside the profiler session.
``None`` against a program without the counter."""

from harness import scoped


def read(run):
    return scoped.program_counter("construct_bin_mappers_s")
