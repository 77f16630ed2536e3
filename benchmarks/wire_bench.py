"""Wire bench: fake vs PHYSICAL int8 cut payloads, engine + fleet paths.

Measures what ISSUE 4 is about — making the metered wire the physical
wire.  Three variants of the same vanilla split Plan:

    fp32     — no wire middleware (dense fp32 payloads)
    fake     — quantize_int8(): in-graph fake-quant, fp32 tensors with
               int8 information content (bytes are a bytes_fn claim)
    physical — quantize_int8(physical=True): the in-graph wire value IS
               the packed (int8, fp32 row scales) pytree emitted by the
               fused Pallas kernels; metered bytes are derived from the
               actual payload dtypes

and two paths:

    engine — single-device compiled round-robin rounds (lax.scan),
             steps/s + bytes-at-cut per turn (cut_act + cut_grad) +
             p2p handoff bytes per sync;
    fleet  — the round-robin ppermute ring over virtual devices
             (subprocess with XLA_FLAGS=--xla_force_host_platform_
             device_count, same recipe as fleet_bench.py): the ring's
             handoff payload rides PACKED under the physical wire —
             ~4x fewer bytes per device hop.

The cut activation is (B, 32, 32, 64): at K=64 lanes the packed payload
is n + n/64*4 bytes vs 4n dense = a 3.76x physical reduction (the >=3.5x
acceptance floor).  Writes `BENCH_wire.json` at the repo root; CI runs a
reduced version, uploads the artifact, and `check_regression.py` gates
both `steps_per_sec` (direction=higher) and `bytes_at_cut`
(direction=lower) against the committed baseline.

Usage:  PYTHONPATH=src python benchmarks/wire_bench.py \
            [--n-clients 4] [--rounds 20] [--per-client-batch 8] \
            [--fleet-devices 2] [--fleet-rounds 6] [--skip-fleet] \
            [--out BENCH_wire.json]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

WIRE_SPECS = {"fp32": "", "fake": "quantize_int8",
              "physical": "quantize_int8:physical"}


def _build(n_clients, wire_spec, fleet_devices=0):
    from repro import optim
    from repro.api import FleetSpec, Plan
    from repro.core import split as sp
    from repro.launch.train import parse_wire
    from repro.nn import convnets as C

    cfg = C.CNNConfig(name="wire_bench", width_mult=1.0,
                      plan=(64, "M", 32, "M"), n_classes=4)
    layers = C.vgg_plan(cfg)
    model = sp.list_segmodel(
        n_segments=len(layers),
        init=lambda k: C.vgg_init(k, cfg),
        layer_apply=lambda p, i, x: C.vgg_layer_apply(p, layers[i], x))
    return Plan(mode="vanilla", model=model, cut=1, n_clients=n_clients,
                schedule="round_robin", sync="p2p",
                optimizer=optim.sgd(0.05, 0.9),
                wire=parse_wire(wire_spec),
                fleet=(FleetSpec(n_devices=fleet_devices)
                       if fleet_devices else None)).compile()


def _data(n, per, rounds):
    import jax
    from repro.data import synthetic as syn
    from repro.engine import stack_batches

    key = jax.random.PRNGKey(0)
    out = []
    for _ in range(rounds + 1):                     # +1 warmup round
        key, k = jax.random.split(key)
        b = syn.image_batch(k, per * n, 4)
        out.append(stack_batches(
            [{"x": b["images"][i * per:(i + 1) * per],
              "labels": b["labels"][i * per:(i + 1) * per]}
             for i in range(n)]))
    jax.block_until_ready(out[-1]["x"])
    return out


def run_variant(variant, args, fleet_devices=0):
    """One (variant, path) measurement; returns the result dict."""
    import jax
    from repro.core.accounting import bytes_of_tree
    from repro.engine.engine import tree_index

    n, per = args.n_clients, args.per_client_batch
    rounds = args.fleet_rounds if fleet_devices else args.rounds
    sess = _build(n, WIRE_SPECS[variant], fleet_devices)
    data = _data(n, per, rounds)
    sess.init(jax.random.PRNGKey(1))
    sess.run_round(data[0])                         # warmup / compile
    jax.block_until_ready(sess.state["server"])

    t0 = time.perf_counter()
    for stacked in data[1:]:
        losses = sess.run_round(stacked)
    jax.block_until_ready((sess.state["server"], losses))
    dt = time.perf_counter() - t0

    wires = sess.wire_report(data[0])
    pc = tree_index(sess.state["clients"], 0)
    dense_handoff = bytes_of_tree(pc)
    stack = sess.wire_stack
    handoff = (stack.handoff_bytes(pc)
               if stack and stack.has_handoff else dense_handoff)
    return {
        "steps_per_sec": round(n * rounds / dt, 2),
        "wall_s": round(dt, 3),
        "bytes_at_cut": sum(w["bytes"] for w in wires),
        "physical_payload": bool(wires and wires[0].get("physical")),
        "handoff_bytes_per_sync": handoff,
        "final_loss": round(float(losses.mean()), 4),
    }


def fleet_worker(args):
    """One fleet variant in a fresh backend (env set by the parent)."""
    res = run_variant(args.variant, args, fleet_devices=args.n_devices)
    import jax
    res["jax_devices"] = jax.device_count()
    print(json.dumps(res))


def run_fleet(variant, args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.fleet_devices}"
    ).strip()
    cmd = [sys.executable, __file__, "--fleet-worker",
           "--variant", variant,
           "--n-devices", str(args.fleet_devices),
           "--n-clients", str(args.n_clients),
           "--rounds", str(args.rounds),
           "--fleet-rounds", str(args.fleet_rounds),
           "--per-client-batch", str(args.per_client_batch)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        sys.stderr.write(proc.stdout + proc.stderr)
        raise SystemExit(f"wire bench fleet worker ({variant}) failed")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--per-client-batch", type=int, default=8)
    ap.add_argument("--fleet-devices", type=int, default=2)
    ap.add_argument("--fleet-rounds", type=int, default=6)
    ap.add_argument("--skip-fleet", action="store_true")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_wire.json"))
    ap.add_argument("--fleet-worker", action="store_true",
                    help="internal: run one fleet variant in-process")
    ap.add_argument("--variant", choices=list(WIRE_SPECS), default="fp32")
    ap.add_argument("--n-devices", type=int, default=1)
    args = ap.parse_args()

    if args.fleet_worker:
        fleet_worker(args)
        return

    from fleet_bench import refuse_on_accelerator   # benchmarks/ is on sys.path
    refuse_on_accelerator()
    engine = {}
    for variant in WIRE_SPECS:
        engine[variant] = run_variant(variant, args)
        r = engine[variant]
        print(f"engine/{variant:8s} {r['steps_per_sec']:8.1f} steps/s  "
              f"{r['bytes_at_cut']:9d} B at cut/turn  "
              f"{r['handoff_bytes_per_sync']:9d} B handoff")

    fleet = {}
    if not args.skip_fleet:
        for variant in ("fp32", "physical"):
            fleet[variant] = run_fleet(variant, args)
            r = fleet[variant]
            print(f"fleet/{variant:9s} {r['steps_per_sec']:8.1f} steps/s  "
                  f"{r['handoff_bytes_per_sync']:9d} B/ring hop")

    payload = {
        "bench": "wire", "n_clients": args.n_clients,
        "rounds": args.rounds, "per_client_batch": args.per_client_batch,
        "cores": os.cpu_count(),
        "engine": engine,
        "bytes_reduction_physical_vs_fp32": round(
            engine["fp32"]["bytes_at_cut"]
            / engine["physical"]["bytes_at_cut"], 2),
        "steps_ratio_physical_vs_fp32": round(
            engine["physical"]["steps_per_sec"]
            / engine["fp32"]["steps_per_sec"], 3),
        "steps_ratio_physical_vs_fake": round(
            engine["physical"]["steps_per_sec"]
            / engine["fake"]["steps_per_sec"], 3),
    }
    if fleet:
        payload["fleet"] = {"n_devices": args.fleet_devices,
                            "rounds": args.fleet_rounds, **fleet}
        payload["ring_hop_bytes_reduction"] = round(
            fleet["fp32"]["handoff_bytes_per_sync"]
            / fleet["physical"]["handoff_bytes_per_sync"], 2)
    print(f"bytes-at-cut reduction (physical vs fp32): "
          f"{payload['bytes_reduction_physical_vs_fp32']:.2f}x "
          f"(target >= 3.5x)")
    print(f"steps/s physical vs fp32: "
          f"{payload['steps_ratio_physical_vs_fp32']:.3f} "
          f"(target >= 0.95)")
    pathlib.Path(args.out).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
