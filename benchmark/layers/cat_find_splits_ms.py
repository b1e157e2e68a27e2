"""``find_splits_ms`` in a categorical job (the cell ``allstate-cat-
train``): device time under the scope ``find_splits`` (the split search
of the 2K children a pass, its ``cat_subset`` and ``cat_bitset`` scopes
included). The reader is ``layers/find_splits_ms.py``'s, which says what
is read and from where; an accepted metric's list of cells is not a new
cell's to extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "find_splits_ms").read
