"""``device_idle_share`` in a wide dense job (the cell ``epsilon-train``):
share of the traced window in which no operation ran on the device.  The
reader is ``layers/device_idle_share.py``'s; an accepted metric's list of
cells is not a new cell's to extend."""

from harness import load_module

read = load_module("layers", "device_idle_share").read
