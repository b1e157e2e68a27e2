"""``between_dispatch_ms`` in a categorical job (the cell ``allstate-cat-
train``): the device's idle time between two dispatches. The reader is
``layers/between_dispatch_ms.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "between_dispatch_ms").read
