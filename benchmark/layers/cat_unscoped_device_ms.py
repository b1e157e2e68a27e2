"""``unscoped_device_ms`` in a categorical job (the cell ``allstate-cat-
train``): busy time minus the self time of every scope. The reader is
``layers/efb_unscoped_device_ms.py``'s, which says what is read and from
where; an accepted metric's list of cells is not a new cell's to extend,
so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "efb_unscoped_device_ms").read
