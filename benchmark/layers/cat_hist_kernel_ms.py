"""``hist_kernel_ms`` in a categorical job (the cell ``allstate-cat-
train``): the histogram kernels' device time. The reader is
``layers/hist_kernel_ms.py``'s, which says what is read and from where;
an accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "hist_kernel_ms").read
