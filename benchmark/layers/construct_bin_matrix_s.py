"""Host seconds of the dense ``Dataset.construct`` in the program's span
``dense_bin_matrix`` (``io/dataset.py`` ``_bin_all``: every value of the
training rows and of the valid set to its bin, the uint8 matrices), from
the program's always-armed counter of this name.  ``None`` against a
program without the counter."""

from harness import scoped


def read(run):
    return scoped.program_counter("construct_bin_matrix_s")
