"""Traffic driver ``train_jobs_dp`` (a traffic file names it: ``"driver":
"train_jobs_dp"``): ``lgb.train`` jobs with ``tree_learner=data`` on one
constructed ``Dataset`` whose rows the program splits over the host's
chips.  That learner keeps the per-iteration loop (``GBDT.train_one_iter``:
several programs a round, the tree to the host every round), so a job
offers a boundary after every round and a callback of the harness stops
it at the first one after ``--seconds``.

The protocol is ``train_jobs``'s (``prepare``, ``measure``, ``collect``;
drivers/train_jobs.py says what each returns) and so are the data, the
``Dataset`` and the answers.  What differs is the path check: the mesh,
the placement of the rows, ``sharded_rounds`` for every round and no
round in the fused scan (``check_path`` below, as ``chip_smoke.py``'s
four-chip phase does), and the warm-up, which is a job of
``warmup_rounds`` rounds: a job's second round still compiles (its
scores come back from the first with another placement), its third does
not.

A program that does not count ``sharded_rounds`` and ``collective_bytes``
cannot be checked here: the run is refused at once, before any data is
made.
"""

from __future__ import annotations

import time

from harness import load_module, program

NEEDS = ("sharded_rounds", "collective_bytes")


def prepare(ctx) -> dict:
    from lightgbm_tpu.obs.metrics import COUNTERS
    missing = [c for c in NEEDS if c not in COUNTERS]
    if missing:
        raise program.Refused(
            f"the program does not count {missing}: a tree_learner=data job "
            "over a mesh cannot be checked against this cell's path")
    import lightgbm_tpu as lgb
    params = {**ctx.cfg["params"], **ctx.traffic.get("params", {})}
    rounds = int(ctx.traffic["num_boost_round"])
    warm = int(ctx.traffic["warmup_rounds"])

    t = time.time()
    (xt32, xt64, y), (xv32, xv64, yv) = \
        load_module("drivers", "train_jobs").make_data(ctx)
    ctx.phases["data_s"] = time.time() - t

    t = time.time()
    ds, dv = program.construct(lgb, params, (xt64, y), (xv64, yv))
    del xt64, xv64
    ctx.phases["construct_s"] = time.time() - t

    t = time.time()
    marks = []
    bst, _, n = run_job(lgb, params, ds, dv, rounds, 0.0,
                        lambda: marks.append(time.time()), at_least=warm)
    took = check_path(bst, ctx.cfg, n, ctx.on_tpu)
    del bst
    ctx.phases["warmup_s"] = time.time() - t
    ctx.phases["warmup_first_round_s"] = marks[0] - t
    return {"lgb": lgb, "params": params, "rounds": rounds,
            "ds": ds, "dv": dv, "path": took,
            "inputs": {"train": (xt32, y), "valid": (xv32, yv)}}


def run_job(lgb, params: dict, ds, dv, rounds: int, deadline: float,
            on_round=None, at_least: int = 1):
    """One ``lgb.train`` job of up to ``rounds`` rounds, stopped at the
    first round boundary at or after ``deadline`` (host clock) once
    ``at_least`` rounds are done.  ``on_round`` is called after every
    round, when its tree and its metric are on the host.  Returns the
    booster, the valid metric per round, the rounds done."""
    from lightgbm_tpu.callback import EarlyStopException
    evals: dict = {}
    done = [0]

    def boundary(env):
        done[0] = env.iteration + 1
        if on_round is not None:
            on_round()
        if at_least <= done[0] < rounds and time.time() >= deadline:
            raise EarlyStopException(env.iteration, env.evaluation_result_list)
    boundary.order = 90

    callbacks = [boundary]
    if dv is not None:
        callbacks.insert(0, lgb.record_evaluation(evals))
    bst = lgb.train(params, ds, num_boost_round=rounds,
                    valid_sets=[dv] if dv is not None else None,
                    callbacks=callbacks)
    metric = params.get("metric", "auc")
    aucs = list(evals["valid_0"][metric]) if dv is not None else []
    program.require(len(bst._gbdt.models) == done[0],
                    f"{len(bst._gbdt.models)} trees after {done[0]} rounds")
    return bst, aucs, done[0]


def check_path(bst, cfg: dict, rounds: int, on_tpu: bool) -> dict:
    """The job went the way the cell says: the learner, split batch and
    histogram type the configuration expects; a mesh over every chip,
    the bins and the scores split evenly over them; every round in the
    per-iteration loop over that mesh and none in the fused scan; the
    booster's state on the TPU."""
    gb = bst._gbdt
    mesh = gb.mesh
    got = {"tpu_split_batch": int(gb.config.tpu_split_batch),
           "hist_dtype": gb.hp.hist_dtype,
           "packed_mirror": gb.bins_words is not None,
           "device_n_bins": int(gb.hp.n_bins),
           "parallel_mode": gb.parallel_mode,
           "mesh_devices": 0 if mesh is None else int(mesh.devices.size)}
    for key, want in cfg.get("expects", {}).items():
        program.require(got.get(key) == want,
                        f"{key}: expected {want}, got {got.get(key)}")
    chips = got["mesh_devices"]
    for name in ("bins", "scores"):
        a = getattr(gb, name)
        shards = {s.device: s.data.shape[0] for s in a.addressable_shards}
        program.require(len(shards) == chips,
                        f"{name} lives on {len(shards)} device(s) of {chips}")
        program.require(set(shards.values()) == {a.shape[0] // chips},
                        f"{name} is not split evenly: {shards}")
    program.require(not gb.supports_fused(),
                    "this learner now supports the fused scan: the cell "
                    "is about the per-iteration loop")
    counted = {c: gb.metrics.counter(c)
               for c in ("sharded_rounds", "strict_rounds", "fused_rounds")}
    program.require(counted == {"sharded_rounds": rounds,
                                "strict_rounds": rounds, "fused_rounds": 0},
                    f"{rounds} rounds, counted {counted}")
    program.require(gb.metrics.counter("collective_bytes") > 0,
                    "no collective payload was counted")
    if on_tpu:
        off = [name for name, v in vars(gb).items()
               for a in program._arrays(v)
               if {d.platform for d in a.devices()} != {"tpu"}]
        program.require(not off,
                        f"booster state not on the TPU: {sorted(set(off))}")
    return got


def measure(ctx, state: dict) -> dict:
    import jax
    lgb, params, rounds = state["lgb"], state["params"], state["rounds"]
    job_s, done, last, aucs, marks = [], 0, None, None, []
    payload0 = program.global_counter("collective_bytes")
    t0 = time.time()
    while True:
        last = None                 # the booster before goes, as a user's would
        tj = time.time()
        with jax.profiler.TraceAnnotation("bench.job"):
            last, aucs, n = run_job(lgb, params, state["ds"], state["dv"],
                                    rounds, t0 + ctx.seconds,
                                    lambda: marks.append(time.time()))
        now = time.time()
        job_s.append(now - tj)
        done += n
        if now - t0 >= ctx.seconds:
            break
    window_s = now - t0
    state["last"], state["last_rounds"], state["aucs"] = last, n, aucs
    round_s = [b - a for a, b in zip([t0] + marks, marks)]
    return {"window_s": window_s, "attempted": done, "failed": 0,
            "end_to_end": {"train_round_ms": 1000.0 * window_s / done},
            "run": {"rounds": done, "round_s": round_s,
                    "collective_bytes":
                    program.global_counter("collective_bytes") - payload0},
            "log": {"rounds": done, "job_s": [round(x, 3) for x in job_s],
                    "round_s": [round(x, 3) for x in round_s]}}


def collect(ctx, state: dict):
    """The window's last job: its trees, the AUC it recorded each round
    and the training scores it holds; then everything of the program's
    goes from the devices."""
    last = state.pop("last")
    check_path(last, ctx.cfg, state["last_rounds"], ctx.on_tpu)
    answers = {"trees": program.plain_trees(last._gbdt.models),
               "valid_auc": state["aucs"],
               "train_scores": program.train_scores(last)}
    del last
    state.pop("ds"), state.pop("dv")
    return answers, state["inputs"], {"path": state["path"],
                                      "valid_auc": answers["valid_auc"],
                                      "bytes_in_use_after_free":
                                      program.free_everything()}
