"""``partition_ms`` in a categorical job (the cell ``allstate-cat-train``):
device milliseconds per round under the scope ``partition``, the fused
partition + key kernel routing rows by the bit of the value's bin in
the slot's left set (a numeric slot's set made from its ranges). The
reader is ``layers/efb_partition_ms.py``'s, which says what is read and
from where; an accepted metric's list of cells is not a new cell's to
extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "efb_partition_ms").read
