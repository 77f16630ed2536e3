#!/usr/bin/env python3
"""Measure a cell's spread: two sets of runs on the same seeds, then
traced runs, one process at a time.

    python3 bench/tools/sets.py <workload> <seed,...> <trace seed,...> \
        [seconds]

Each run's last line and its `[...]` diagnostic lines go, one JSON
record per run, to `chiprun_out/sets_<workload>.jsonl`; a summary line
per run goes to standard output.  The bounds in BENCHMARK.json come
from the two sets' quartile spreads (`statistics.quantiles(n=4)`).
"""
import json
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main():
    cell, seeds, trace_seeds = sys.argv[1], sys.argv[2], sys.argv[3]
    secs = sys.argv[4] if len(sys.argv) > 4 else "51"
    seeds = [s for s in seeds.split(",") if s]
    plan = ([("A", s, 0) for s in seeds] + [("B", s, 0) for s in seeds]
            + [("T", s, 1) for s in trace_seeds.split(",") if s])
    out = ROOT / "chiprun_out" / f"sets_{cell}.jsonl"
    out.parent.mkdir(exist_ok=True)
    with out.open("a") as f:
        for set_, seed, trace in plan:
            t0 = time.time()
            p = subprocess.run(
                [sys.executable, str(ROOT / "bench" / "run.py"),
                 "--workload", cell, "--seed", seed, "--seconds", secs,
                 "--trace", str(trace)],
                capture_output=True, text=True, cwd=ROOT)
            lines = p.stdout.strip().splitlines()
            ok = p.returncode == 0 and lines
            rec = {"set": set_, "seed": int(seed), "trace": trace,
                   "rc": p.returncode, "wall_s": time.time() - t0,
                   "notes": [ln for ln in lines if ln.startswith("[")],
                   "result": json.loads(lines[-1]) if ok else None,
                   "stderr_tail": "" if ok else p.stderr[-1500:]}
            f.write(json.dumps(rec) + "\n")
            f.flush()
            r = rec["result"] or {}
            print(set_, seed, trace, p.returncode, round(rec["wall_s"], 1),
                  r.get("correct"),
                  {k: v["value"] for k, v in r.get("metrics", {}).items()},
                  {k: v["value"] for k, v in r.get("checks", {}).items()},
                  flush=True)


if __name__ == "__main__":
    main()
