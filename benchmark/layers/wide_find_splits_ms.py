"""``find_splits_ms`` in a wide dense job (the cell ``epsilon-train``):
device time under the scope ``find_splits``, the split search over
``[2K, 2000, 256]`` candidates a pass.  The reader is
``layers/find_splits_ms.py``'s; an accepted metric's list of cells is not
a new cell's to extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "find_splits_ms").read
