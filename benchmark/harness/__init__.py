"""The benchmark's yardstick: data generation, the driver of the system
under test, the trace reduction and the comparison that decides
``correct``.  Later PRs add files beside these and edit none."""

import importlib.util
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` of the benchmark's directory, found by the
    name a manifest entry or a configuration gives: ``layers``,
    ``reference``, ``datagen``."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
