"""``hist_kernel_ms`` in a bundled job (the cell ``allstate-train``): the
histogram kernels of every branch of the row ladder over the bundle
columns, the root pass's included. The reader is
``layers/hist_kernel_ms.py``'s, which says what is read and from where;
an accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "hist_kernel_ms").read
