"""``unscoped_device_ms`` in a ranking job (the cell ``istella-rank-
train``): busy time outside the scopes ``round_hist``, ``partition`` and
``find_splits``: with those three it adds up to the busy time. The
reader is ``layers/unscoped_device_ms.py``'s, which says what is read
and from where; an accepted metric's list of cells is not a new cell's
to extend, so the cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "unscoped_device_ms").read
