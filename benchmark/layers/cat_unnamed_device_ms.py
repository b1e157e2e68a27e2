"""``unnamed_device_ms`` in a categorical job (the cell ``allstate-cat-
train``): device time under no named scope at all. The reader is
``layers/unnamed_device_ms.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "unnamed_device_ms").read
