"""Client-axis scaling bench: steps/s and bytes-at-cut vs device count.

Runs the SAME Plan (vanilla split, parallel SplitFed schedule by
default) at several client-mesh sizes and measures client-turn
throughput.  Each device count runs in a fresh subprocess so
`XLA_FLAGS=--xla_force_host_platform_device_count=<d>` can split the
host CPU into `d` virtual devices before jax initialises — the exact
recipe CI uses to exercise real 8-way sharding on one machine.

Usage:  PYTHONPATH=src python benchmarks/fleet_bench.py \
            [--n-clients 32] [--rounds 20] [--per-client-batch 4] \
            [--devices 1,2,4,8] [--schedule parallel] \
            [--out BENCH_fleet.json]

Writes a machine-readable `BENCH_fleet.json` (per-device-count steps/s,
wall time, per-turn cut traffic, plus the max-vs-1 speedup) at the repo
root; CI uploads it as an artifact and `check_regression.py` gates PRs
against the committed copy.

Interpreting the numbers: the parallel schedule's client-axis compute is
embarrassingly parallel, so steps/s should scale ~linearly with device
count UNTIL the mesh outstrips the physical cores backing the virtual
devices (the payload records `cores`; a 2-core runner caps the
achievable speedup near 2x no matter how many virtual devices the mesh
has).  bytes-at-cut per turn is schedule/mesh-invariant — sharding moves
computation, not protocol traffic.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def refuse_on_accelerator():
    """The fleet and wire benches time virtual CPU devices in child
    processes pinned to `JAX_PLATFORMS=cpu`; on a chip host their JSON
    would hold chip numbers beside CPU numbers with nothing to tell
    them apart, so they refuse to run there."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise SystemExit(
            f"{pathlib.Path(sys.argv[0]).name} is a CPU bench (virtual CPU "
            f"devices); this host's default backend is {backend!r}. "
            "Run chip_smoke.py on the chip instead.")


def worker(args) -> None:
    """One device count, fresh backend (env set by the parent)."""
    import jax
    import jax.numpy as jnp

    from repro import optim
    from repro.api import FleetSpec, Plan
    from repro.core import split as sp
    from repro.data import synthetic as syn
    from repro.engine import stack_batches
    from repro.nn import convnets as C

    cfg = C.CNNConfig(name="bench", width_mult=0.25,
                      plan=(16, 16, "M", 32, "M"), n_classes=4)
    layers = C.vgg_plan(cfg)
    model = sp.list_segmodel(
        n_segments=len(layers),
        init=lambda k: C.vgg_init(k, cfg),
        layer_apply=lambda p, i, x: C.vgg_layer_apply(p, layers[i], x))

    n, per, rounds = args.n_clients, args.per_client_batch, args.rounds
    key = jax.random.PRNGKey(0)
    data = []
    for r in range(rounds + 1):                     # +1 warmup round
        key, k = jax.random.split(key)
        b = syn.image_batch(k, per * n, 4)
        data.append(stack_batches(
            [{"x": b["images"][i * per:(i + 1) * per],
              "labels": b["labels"][i * per:(i + 1) * per]}
             for i in range(n)]))
    jax.block_until_ready(data[-1]["x"])

    sess = Plan(mode="vanilla", model=model, cut=2, n_clients=n,
                schedule=args.schedule, optimizer=optim.sgd(0.05, 0.9),
                fleet=FleetSpec(n_devices=args.n_devices)).compile()
    sess.init(jax.random.PRNGKey(1))
    sess.run_round(data[0])                         # warmup / compile
    jax.block_until_ready(sess.state["server"])

    import time
    t0 = time.perf_counter()
    for stacked in data[1:]:
        losses = sess.run_round(stacked)
    jax.block_until_ready((sess.state["server"], losses))
    dt = time.perf_counter() - t0

    wires = sess.wire_report(data[0])
    print(json.dumps({
        "n_devices": args.n_devices,
        "jax_devices": jax.device_count(),
        "steps_per_sec": round(n * rounds / dt, 2),
        "wall_s": round(dt, 3),
        "bytes_at_cut_per_turn": sum(w["bytes"] for w in wires),
        "final_loss": round(float(jnp.mean(losses)), 4),
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-clients", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--per-client-batch", type=int, default=4)
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--schedule", choices=["parallel", "round_robin"],
                    default="parallel")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_fleet.json"))
    ap.add_argument("--worker", action="store_true",
                    help="internal: run one device count in-process")
    ap.add_argument("--n-devices", type=int, default=1)
    args = ap.parse_args()

    if args.worker:
        worker(args)
        return

    refuse_on_accelerator()
    counts = [int(d) for d in args.devices.split(",")]
    results: dict = {}
    for d in counts:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={d}").strip()
        cmd = [sys.executable, __file__, "--worker",
               "--n-devices", str(d),
               "--n-clients", str(args.n_clients),
               "--rounds", str(args.rounds),
               "--per-client-batch", str(args.per_client_batch),
               "--schedule", args.schedule]
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stdout + proc.stderr)
            raise SystemExit(f"fleet bench worker (d={d}) failed")
        results[str(d)] = json.loads(proc.stdout.strip().splitlines()[-1])
        r = results[str(d)]
        print(f"devices={d:2d}  {r['steps_per_sec']:8.1f} steps/s  "
              f"{r['wall_s']:7.3f}s  "
              f"{r['bytes_at_cut_per_turn']:9d} B/turn at the cut")

    base = results[str(counts[0])]["steps_per_sec"]
    top = results[str(counts[-1])]["steps_per_sec"]
    payload = {
        "bench": "fleet", "schedule": args.schedule,
        "n_clients": args.n_clients, "rounds": args.rounds,
        "per_client_batch": args.per_client_batch,
        "cores": os.cpu_count(),
        "devices": results,
        f"speedup_{counts[-1]}_vs_{counts[0]}": round(top / base, 2),
    }
    print(f"speedup {counts[-1]} vs {counts[0]} devices: "
          f"{top / base:.2f}x on {os.cpu_count()} cores "
          f"(linear scaling needs >= {counts[-1]} physical cores)")
    pathlib.Path(args.out).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
