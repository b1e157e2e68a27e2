"""``construct_bin_mappers_s`` in a wide dense job (the cell
``epsilon-train``): host seconds of the dense construct in the span
``dense_bin_mappers``, the row sample and 2,000 columns' bin finders.  The
reader is ``layers/construct_bin_mappers_s.py``'s; an accepted metric's
list of cells is not a new cell's to extend."""

from harness import load_module

read = load_module("layers", "construct_bin_mappers_s").read
