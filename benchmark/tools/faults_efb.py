"""Faults of a bundled (EFB) job, planted as ``faults.py`` plants its own
(each takes ``setattr(object, name, value)``).  The program's compiled
runners hold what was traced: clear them
(``harness.program.free_everything``) before a planted job and after."""

from __future__ import annotations


def shift_member_segments(setattr_, by: int = 1) -> None:
    """The split search takes every member of a shared bundle column to
    lie ``by`` bins off its place: it reads a neighbour's sums as the
    member's own (an offset table built one off).  The partition and the
    valid scorer keep the true ranges, so the splits stated are routed
    truthfully and only the search is at fault."""
    import numpy as np
    from lightgbm_tpu.io import dataset

    real = dataset.Dataset.device_bundle_ranges

    def shifted(self):
        r = real(self)
        if r is None:
            return None
        shared = np.array([len(m) > 1 for m in self.bundle_plan.bundles])
        moved = {k: np.where(shared[:, None], np.roll(getattr(r, k), by, axis=1),
                             getattr(r, k))
                 for k in ("feat_of", "vbin_of", "off", "roff")}
        return r._replace(**moved)
    setattr_(dataset.Dataset, "device_bundle_ranges", shifted)


def skip_odd_features(setattr_) -> None:
    """The split search never looks at a feature of odd index, numeric
    column or bundle member alike (their positions read as no member's):
    it states the best split among the other half.  Every sum, the
    partition and the valid scorer are sound, so every stated split is a
    true and allowed one and only the gain it gives away shows: a fault
    that ``split_regret_mean`` reads as a finite number."""
    import numpy as np
    from lightgbm_tpu.io import dataset

    real = dataset.Dataset.device_bundle_ranges

    def halved(self):
        r = real(self)
        return r and r._replace(
            feat_of=np.where(r.feat_of % 2 == 1, -1, r.feat_of))
    setattr_(dataset.Dataset, "device_bundle_ranges", halved)
