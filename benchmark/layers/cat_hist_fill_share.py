"""``hist_fill_share`` in a categorical job (the cell ``allstate-cat-
train``): of the rows the passes were handed, the share they had to
read. The reader is ``layers/hist_fill_share.py``'s, which says what is
read and from where; an accepted metric's list of cells is not a new
cell's to extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "hist_fill_share").read
