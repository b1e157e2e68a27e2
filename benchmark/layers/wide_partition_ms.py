"""``partition_ms`` in a wide dense job (the cell ``epsilon-train``):
device milliseconds per round under the scope ``partition``, whose kernel
contracts a ``[K, 2000]`` one-hot with a ``[2000, block]`` block of bins.
The reader is ``layers/partition_ms.py``'s; an accepted metric's list of
cells is not a new cell's to extend."""

from harness import load_module

read = load_module("layers", "partition_ms").read
