#!/usr/bin/env python3
"""Sweep a serving cell's arrival rate once, to find the knee.

    python3 bench/tools/knee.py --workload phi4mini.serve_poisson \
        --rates 1,2,3,4 --seconds 30 --seed 7

One process, one session: for each rate the cell's open loop runs
`seconds` after the traffic's warm-up, then drains.  One JSON line per
rate: requests completed per second, TTFT p90 and ITL p95 of the
window, and the requests queued for a slot and seated at its end.  The
knee is the highest rate whose queue does not grow and whose TTFT p90
stays under the latency limit; the cell's traffic file then states
0.8 x that rate as a number.
"""
import argparse
import collections
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench.lib import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    run = harness.load_module(ROOT / "bench" / "run.py", "run")
    cell, cfg, traffic, _, _ = run.find_cell(
        harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    harness.require_chips(cell["chips"])
    harness.enable_compile_cache()
    drv = harness.load_module(ROOT / "bench" / "drivers"
                              / f"{cfg['driver']}.py", cfg["driver"])
    model = harness.load_module(ROOT / "bench" / "models"
                                / f"{cfg['model']}.py", cfg["model"])
    key_w = jax.random.split(harness.seed_key(args.seed))[0]
    sess, bat = drv.build(model, cfg, key_w)
    drv.warm(bat, traffic, cfg["vocab"])
    for rate in [float(r) for r in args.rates.split(",")]:
        tr = dict(traffic, rate_per_s=rate)
        w0 = tr["warm_s"]
        reqs = drv.make_requests(tr, w0 + args.seconds)
        state = {"queue": collections.deque(), "seated": {}, "next": 0}
        rec = drv.Record()
        t0 = time.perf_counter()
        drv.serve(bat, reqs, args.seed, cfg["vocab"], t0, 0.0,
                  w0 + args.seconds, state, rec)
        w1 = time.perf_counter() - t0
        st = drv.window_stats(reqs, w0, w1)
        print(json.dumps({
            "rate_per_s": rate, "window_s": w1 - w0,
            "completed_per_s": st["completed"] / (w1 - w0),
            "ttft_ms": {q: 1e3 * drv.percentile(st["ttft"], q)
                        for q in (50, 90)},
            "itl_ms": {q: 1e3 * drv.percentile(st["itl"], q)
                       for q in (90, 95)},
            "due": len(st["ttft"]), "queued_at_end": len(state["queue"]),
            "seated_at_end": len(state["seated"]),
            "step_ms_median": 1e3 * drv.percentile(rec.step_s, 50),
            "join_ms_median": 1e3 * drv.percentile(rec.join_s, 50),
            "live_mean": (sum(rec.step_live) / len(rec.step_live)
                          if rec.step_live else 0)}), flush=True)
        bat.run()                       # drain the seated tenants
        bat.finished.clear()


if __name__ == "__main__":
    main()
