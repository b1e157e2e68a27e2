"""``device_idle_share`` in a categorical job (the cell ``allstate-cat-
train``): share of the traced window in which no operation ran on the
device. The reader is ``layers/efb_device_idle_share.py``'s, which says
what is read and from where; an accepted metric's list of cells is not a
new cell's to extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "efb_device_idle_share").read
