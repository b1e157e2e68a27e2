"""``construct_s`` in a categorical job (the cell ``allstate-cat-train``):
host seconds in ``lgb.Dataset(X, categorical_feature=...).construct()``
for the training rows and the valid set. The reader is
``layers/construct_s.py``'s, which says what is read and from where; an
accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "construct_s").read
