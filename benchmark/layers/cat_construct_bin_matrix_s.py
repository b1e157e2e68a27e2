"""``construct_bin_matrix_s`` in a categorical job (the cell
``allstate-cat-train``): host seconds of the dense construct in the span
``dense_bin_matrix`` (every value of the 13,184,290 x 32 training rows
and of the valid set to its bin; the categorical columns' share of it is
``cat_bin_mappers_s``'s). The reader is
``layers/construct_bin_matrix_s.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "construct_bin_matrix_s").read
