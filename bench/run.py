#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout that holds `BENCHMARK.json`.  The cell
names its configuration (`BENCHMARK.json` "configs" -> a file under
`bench/configs/`) and its traffic mix (`bench/traffic/<traffic>.json`);
the configuration names its driver (`bench/drivers/<driver>.py`) and
model (`bench/models/<model>.py`), the cell's correctness limits are in
`bench/limits/<workload>.json`, and each per-layer metric is read by
`bench/metrics/<name>.py`.  Adding a cell adds files and entries; it
edits none.

With `--trace 0` the result's metrics are the cell's end-to-end metrics,
with `--trace 1` its per-layer metrics, read from a profiler trace of
the window's first `harness.TRACE_SECONDS`.  The last line on standard
output is one JSON object (`correct`, `attempted`, `failed`, `metrics`,
`device`, optionally `breakdown`, then `checks`: each compared number
with its limit).  The
run refuses, with a non-zero exit and no result, without a TPU, with
fewer chips than the cell asks for, in a kernel mode other than pallas,
or on a device kind missing from `bench/peaks.json`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402


def find_cell(bench: dict, workload: str) -> tuple:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise harness.Refused(f"no workload {workload!r} in BENCHMARK.json "
                              f"(have {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = harness.load_json(ROOT / cfg_entry["file"])
    traffic = harness.load_json(harness.BENCH_DIR / "traffic"
                                / f"{cell['traffic']}.json")
    limits = harness.load_json(harness.BENCH_DIR / "limits"
                               / f"{workload}.json")
    return cell, cfg, traffic, limits, bench["per_layer"]


def main(argv=None, *, hooks=None, require_chip=True, cell=None):
    """`cell`, `hooks` and `require_chip=False` are for the benchmark's
    own tests: a (cell, cfg, traffic, limits, metrics) tuple in place of
    the one BENCHMARK.json names, a fault planted in the program, and
    the CPU in place of the chip."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if cell is None:
        cell = find_cell(harness.load_json(ROOT / "BENCHMARK.json"),
                         args.workload)
    cell, cfg, traffic, limits, metrics = cell
    if require_chip:
        devices, peak = harness.require_chips(cell["chips"])
    else:                    # the benchmark's own tests, on the CPU
        import jax
        devices = jax.devices()[:cell["chips"]]
        peak = harness.counts.peaks("TPU v5 lite")
    cache = harness.enable_compile_cache() if require_chip else None
    compiles = harness.CompileMeter()
    harness.note("device", platform=devices[0].platform,
                 device_kind=devices[0].device_kind, count=len(devices),
                 compile_cache=cache, seed=args.seed,
                 workload=args.workload)
    driver = harness.load_module(
        harness.BENCH_DIR / "drivers" / f"{cfg['driver']}.py", cfg["driver"])
    result, checks = driver.run(
        cell, cfg, traffic, limits, metrics, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), devices=devices,
        peak=peak, compiles=compiles, t_start=T_START, hooks=hooks)
    harness.emit(result, checks)
    return result, checks


if __name__ == "__main__":
    main()
