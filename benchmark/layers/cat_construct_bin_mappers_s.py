"""``construct_bin_mappers_s`` in a categorical job (the cell
``allstate-cat-train``): host seconds of the dense construct in the span
``dense_bin_mappers`` (the row sample and every column's bin finder, the
categorical columns' level counts among them). The reader is
``layers/construct_bin_mappers_s.py``'s, which says what is read and
from where; an accepted metric's list of cells is not a new cell's to
extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "construct_bin_mappers_s").read
