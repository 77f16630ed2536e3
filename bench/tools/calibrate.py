#!/usr/bin/env python3
"""Readings that a training cell's correctness limits are set from.

    python3 bench/tools/calibrate.py --workload vgg16.rr100 \
        --seeds 11,12,... --control-seeds 21,22,23 --fault-seeds 31,32,33 \
        --faults half_batch,server_unchanged,half_clients

Training cells: for each of `--seeds`, the program against the plain
reference over its first round and at least three turns (the lower
readings); for each of `--control-seeds`, the reference itself computed
in the precision below the configured one, put in the program's place
(the control, as the model's file defines it: it must fail); for
each of `--fault-seeds` and each of `--faults`, the program with that
fault of `bench/lib/faults.py` planted.  Training needs
no measured window, so none is run.

Serving cells: for each of `--seeds` and `--control-seeds`, a session
from that seed serves the cell's traffic for its warm-up and
`--serve-seconds`; once every session is freed, the reference reads the
served tokens' logit gaps (program seeds) and the gaps of the tokens
that a float8 rendition of the reference puts first (control seeds).

One JSON line per reading; runs on the chip, in one process.
"""
import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.lib import faults, harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="half_batch",
                    help="comma-separated names from bench/lib/faults.py")
    ap.add_argument("--serve-seconds", type=float, default=15.0)
    args = ap.parse_args()
    seeds = lambda s: [int(x) for x in s.split(",") if x]

    spec = harness.load_module(ROOT / "bench" / "run.py", "run")
    cell, cfg, traffic, limits, _ = spec.find_cell(
        harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    drv = harness.load_module(ROOT / "bench" / "drivers"
                              / f"{cfg['driver']}.py", cfg["driver"])
    model = harness.load_module(ROOT / "bench" / "models"
                                / f"{cfg['model']}.py", cfg["model"])
    if cfg["driver"] == "split_serve":
        return serve_readings(args, drv, model, cfg, traffic, limits,
                              seeds(args.seeds), seeds(args.control_seeds))

    def prepare(seed):
        key_w, key_d = jax.random.split(harness.seed_key(seed))
        return key_w, drv.make_pool(model, cfg, traffic, key_d)

    def reference(key_w, pool, control=False):
        return drv.reference_capture(model, cfg, key_w, pool,
                                     traffic["n_clients"], control)

    def program(key_w, pool, fault=None):
        sess = drv.build_session(model, cfg, traffic)
        sess.init(key_w)
        if fault:
            fault(sess)
        got = drv.program_capture(sess, pool)
        del sess
        gc.collect()
        return got

    runs = [("program", s) for s in seeds(args.seeds)]
    runs += [("control", s) for s in seeds(args.control_seeds)]
    runs += [("fault_" + f, s) for f in args.faults.split(",") if f
             for s in seeds(args.fault_seeds)]
    for kind, seed in runs:
        t0 = time.perf_counter()
        key_w, pool = prepare(seed)
        if kind == "control":
            got = reference(key_w, pool, control=True)
        else:
            got = program(key_w, pool,
                          faults.TRAINING.get(kind.removeprefix("fault_")))
        gc.collect()
        want = reference(key_w, pool)
        gc.collect()
        turns = drv.per_turn(got, want)
        step = np.asarray(turns.pop("step_gap"))
        print(json.dumps({"kind": kind, "seed": seed,
                          **drv.readings(got, want), "per_turn": turns,
                          "step_gap_worst_client": int(step.argmax()),
                          "step_gap_median": float(np.median(step)),
                          "loss0": float(want["losses"][0]),
                          "seconds": time.perf_counter() - t0}), flush=True)


def serve_readings(args, drv, model, cfg, traffic, limits, seeds,
                   control_seeds):
    import collections
    picked = {}
    for seed in seeds + control_seeds:
        t0 = time.perf_counter()
        key_w = jax.random.split(harness.seed_key(seed))[0]
        sess, bat = drv.build(model, cfg, key_w)
        drv.warm(bat, traffic, cfg["vocab"])
        horizon = traffic["warm_s"] + args.serve_seconds
        reqs = drv.make_requests(traffic, horizon)
        state = {"queue": collections.deque(), "seated": {}, "next": 0}
        drv.serve(bat, reqs, seed, cfg["vocab"], time.perf_counter(), 0.0,
                  horizon, state, drv.Record())
        picked[seed] = (key_w, drv.sample(reqs, seed,
                                          limits["sample_requests"]))
        del sess, bat, state
        gc.collect()
        print(json.dumps({"seed": seed, "served_s": time.perf_counter() - t0,
                          "finished_sampled": len(picked[seed][1])}),
              flush=True)
    for seed in seeds + control_seeds:
        key_w, reqs = picked[seed]
        t0 = time.perf_counter()
        kind = "program" if seed in seeds else "control_fp8"
        gaps = drv.reference_gaps(model, cfg, traffic, key_w, reqs, seed,
                                  limits["sample_requests"],
                                  control=kind != "program")
        print(json.dumps({"kind": kind, "seed": seed,
                          "served_logit_gap": float(gaps.max()),
                          "tokens": int(gaps.size),
                          "at_best": int((gaps == 0).sum()),
                          "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
