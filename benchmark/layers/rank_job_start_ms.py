"""``job_start_ms`` in a ranking job (the cell ``istella-rank-train``):
from the start of an ``lgb.train`` job to the first execution of its
round program: the booster, the objective's and the metric's bucket
plans, 2.3 GB of bins to the device. The reader is
``layers/job_start_ms.py``'s, which says what is read and from where; an
accepted metric's list of cells is not a new cell's to extend, so the
cell reports it under a name of its own."""

from harness import load_module

read = load_module("layers", "job_start_ms").read
