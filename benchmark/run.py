"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It finds the cell in ``BENCHMARK.json``, the cell's
configuration under ``configs/`` and its traffic under ``traffic/``; the
traffic file names its driver (``drivers/``), the configuration its data
generator (``datagen/``), its plain reference (``reference/``) and the
comparison that decides ``correct`` (``comparisons/``); the manifest
names one reader per per-layer metric (``layers/``).  A later PR brings a
cell, a kind of traffic, an objective or a metric as new files and new
entries and edits nothing here.

A run: device; the driver's set-up (for ``train_jobs``: data,
``Dataset.construct``, a warm-up job of two dispatches, which compiles or
loads from the cache); the driver's window of ``--seconds``; the peak
memory; the driver's answers, with the program's state freed; the
comparison with the reference; the result line.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits
with code 2.  ``--rehearse-cpu`` drives the same code at a tiny
size on the CPU backend and can never print a result line.
"""

from __future__ import annotations

import time

T_START = time.time()       # process start, as near as Python can say

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def at_size(block: dict, rehearse_cpu: bool) -> dict:
    """A configuration or a traffic file at the size of the run: its
    ``rehearsal`` block laid over it (one level into nested groups) for
    ``--rehearse-cpu``, else as it stands."""
    out = {k: v for k, v in block.items() if k != "rehearsal"}
    if rehearse_cpu:
        for k, v in block.get("rehearsal", {}).items():
            out[k] = {**out[k], **v} if isinstance(v, dict) and k in out else v
    return out


def find_cell(name: str, rehearse_cpu: bool = False):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as fh:
        cfg = json.load(fh)
    traffic = load_json("traffic", cell["traffic"] + ".json")
    return (manifest, cell, at_size(cfg, rehearse_cpu),
            at_size(traffic, rehearse_cpu))


def metrics_of(manifest: dict, group: str, cell: str) -> list:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


class Context:
    """What a driver is given: the cell's files at the run's size, the
    arguments, and ``phases`` to put its set-up's seconds into."""

    def __init__(self, args, cell: dict, cfg: dict, traffic: dict, on_tpu: bool):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds = int(args.seed), float(args.seconds)
        self.rehearse_cpu, self.on_tpu = bool(args.rehearse_cpu), on_tpu
        self.phases = {}


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from harness.program import Refused
    try:
        result = run_cell(args)
    except Refused as e:
        print(f"benchmark: refused: {e.why}", file=sys.stderr)
        return 2
    if args.rehearse_cpu:
        emit({"rehearsal": "cpu: the control flow ran; nothing was shown "
              "about the chip", "correct": result["correct"],
              "compared": result["compared"]})
        return 0
    emit(result)
    return 0


def run_cell(args) -> dict:
    """One run of one cell: the cell's traffic names its driver
    (drivers/), its configuration the reference (reference/) and the
    comparison (comparisons/), the manifest the per-layer readers
    (layers/)."""
    from harness import compare, load_module, program
    from harness.program import Refused

    manifest, cell, cfg, traffic = find_cell(args.workload, args.rehearse_cpu)
    try:
        import jax
        import lightgbm_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}")
    cache_dir = program.place_compile_cache(ROOT)
    compiles = program.CompileLog()
    device = program.open_device(int(cell["chips"]), args.rehearse_cpu)
    ctx = Context(args, cell, cfg, traffic, device["platform"] == "tpu")
    ctx.phases["device_init_s"] = time.time() - T_START

    driver = load_module("drivers", traffic["driver"])
    state = driver.prepare(ctx)
    warm = compiles.snapshot()
    setup_s = time.time() - T_START

    # ------------------------------------------------------------ window
    trace_dir = os.path.join(ROOT, ".bench_cache", "trace", cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    misses0 = program.global_counter("round_compile_misses")
    events0 = program.global_counter("xla_compile_events")
    host0 = program.host_usage()
    with jax.profiler.TraceAnnotation("bench.window"):
        out = driver.measure(ctx, state)
    host = {k: round(v - host0[k], 3) for k, v in program.host_usage().items()}
    if args.trace:
        jax.profiler.stop_trace()
    memory = program.memory_peaks()
    compiled = {"round_compile_misses":
                program.global_counter("round_compile_misses") - misses0,
                "xla_compile_events":
                program.global_counter("xla_compile_events") - events0}
    emit({"window": {"window_s": round(out["window_s"], 3), **out["log"],
                     "host": host, "compiled_in_window": compiled,
                     "compile_log": compiles.snapshot(), **memory,
                     "memory_stats": program.memory_stats()}})
    program.require(not any(compiled.values()),
                    f"something compiled inside the window: {compiled}")

    # --------------------------------------------------- answers, then free
    answers, inputs, collected = driver.collect(ctx, state)
    del state

    t = time.time()
    ref = load_module("reference", cfg["reference"])
    comparison = load_module("comparisons", cfg["comparison"])
    numbers = comparison.gaps(ref, cfg, answers, inputs, ctx.seed)
    correct, compared = compare.judge(numbers, cfg["limits"])
    compare_s = time.time() - t

    emit({"setup_phases_s": {k: round(v, 3) for k, v in ctx.phases.items()},
          "setup_s": round(setup_s, 3), "compare_s": round(compare_s, 3),
          "compile_in_setup": warm, "compile_cache_dir": cache_dir,
          **collected,
          "not_compared": {k: numbers[k] for k in comparison.NOT_COMPARED}})

    values = {**out["end_to_end"], "setup_s": setup_s}
    run = {"phases": ctx.phases, "window_s": out["window_s"], "trace": None,
           **out["run"]}
    breakdown = None
    if args.trace:
        from harness import tracered
        run["trace"] = tracered.reduce_dir(trace_dir)
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        breakdown = tracered.breakdown(run["trace"])
        wanted = metrics_of(manifest, "per_layer", cell["name"])
        # each per-layer metric's own reader, layers/<name>.py: a number,
        # or None where it finds nothing to read
        values = {m["name"]: load_module("layers", m["name"]).read(run)
                  for m in wanted}
    else:
        wanted = metrics_of(manifest, "end_to_end", cell["name"])
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}
    device.update(memory)

    for name, c in compared.items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct = {correct}", file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


if __name__ == "__main__":
    sys.exit(main())
