"""``device_idle_share`` in a ranking job (the cell ``istella-rank-
train``): the share of the traced window in which no operation ran on
the device. The reader is ``layers/device_idle_share.py``'s, which says
what is read and from where; an accepted metric's list of cells is not a
new cell's to extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "device_idle_share").read
