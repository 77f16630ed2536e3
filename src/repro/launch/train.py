"""End-to-end training driver: argparse -> `repro.api.Plan`.

Every mode compiles through the one Plan/Session path:

  * monolithic   — Plan(mode="large_batch", n_clients=1): standard
    full-model training as the degenerate one-client sync-SGD round;
  * split        — Plan(mode="vanilla"): the paper's protocol, client
    segment + server segment, only the cut activation crossing the
    tiers.  --n-clients > 1 runs the compiled round-robin (or
    SplitFed-parallel) round; --n-clients 1 is a one-turn scan;
  * fedavg / large_batch — the paper's comparison baselines, compiled
    (vmap over clients).

--wire stacks cut middleware, e.g. `--wire quantize_int8,dp_noise:0.05`.

On this CPU container run reduced configs (--reduced); on a real pod the
same driver takes the full configs (the dry-run proves they lower).

Examples:
    PYTHONPATH=src python -m repro.launch.train \
        --arch phi4_mini_3_8b --reduced --steps 100 --mode split --cut 1
    PYTHONPATH=src python -m repro.launch.train \
        --arch phi4_mini_3_8b --reduced --steps 20 --mode split \
        --n-clients 4 --schedule round_robin --wire quantize_int8
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro import checkpoint as ckpt
from repro import optim
from repro.api import FleetSpec, FullFns, Plan, lm_split_fns
from repro.configs import get_config
from repro.data import synthetic as syn
from repro.engine import tree_index
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model


def make_batch_fn(cfg, batch, seq):
    def fn(key):
        b = syn.lm_batch(key, batch, seq, cfg.vocab)
        if cfg.family == "vlm":
            b["patch_embeds"] = 0.02 * jax.random.normal(
                key, (batch, cfg.n_patches, cfg.vision_dim), cfg.dtype)
        if cfg.encdec:
            b["audio_feats"] = 0.02 * jax.random.normal(
                key, (batch, cfg.n_audio_frames, cfg.d_model), cfg.dtype)
        return b
    return fn


# parse_wire moved to the api layer so the serving engine shares the one
# wire grammar; re-exported here for back-compat (benchmarks import it).
from repro.api.wire import parse_wire  # noqa: E402,F401


def build_plan(model, args) -> Plan:
    opt = optim.adamw(args.lr, weight_decay=0.01)
    fleet = (FleetSpec(n_devices=args.fleet_devices or None)
             if args.fleet else None)
    if args.mode == "monolithic":
        if fleet is not None:
            raise SystemExit("--fleet: monolithic training has no client "
                             "axis to shard (n_clients=1); use --mode "
                             "split/fedavg/large_batch with --n-clients")
        return Plan(mode="large_batch",
                    model=FullFns(init=model.init, apply=model.forward),
                    n_clients=1, optimizer=opt, clip_norm=1.0,
                    schedule=(args.schedule if args.schedule == "pipelined"
                              else None),
                    microbatches=args.microbatches)
    if args.mode in ("fedavg", "large_batch"):
        # schedule="pipelined" + microbatches stream each client's local
        # gradient in M accumulated chunks; other schedules are a no-op
        # for the baselines, so only pipelined is forwarded (and Plan
        # still validates the microbatches/schedule pairing)
        return Plan(mode=args.mode,
                    model=FullFns(init=model.init, apply=model.forward),
                    n_clients=args.n_clients, optimizer=opt,
                    schedule=(args.schedule if args.schedule == "pipelined"
                              else None),
                    microbatches=args.microbatches,
                    local_steps=args.local_steps, fleet=fleet)
    # split
    if args.topology != "vanilla":
        raise SystemExit(
            f"--topology {args.topology}: the LM launch path exposes the "
            "vanilla cut only (apply_client/apply_server).  Other "
            "topologies build a repro.api.Plan over a SegModel or Branch "
            "directly — see README and tests/test_api.py.")
    try:
        wire = parse_wire(args.wire)
    except ValueError as e:
        raise SystemExit(str(e))
    return Plan(mode="vanilla", model=lm_split_fns(model, args.cut),
                cut=args.cut, n_clients=args.n_clients,
                schedule=args.schedule, microbatches=args.microbatches,
                optimizer=opt,
                wire=wire, fleet=fleet,
                clip_norm=1.0 if args.n_clients == 1 else None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mode",
                    choices=["monolithic", "split", "fedavg", "large_batch"],
                    default="monolithic")
    ap.add_argument("--cut", type=int, default=-1)
    ap.add_argument("--n-clients", type=int, default=1)
    ap.add_argument("--schedule",
                    choices=["round_robin", "parallel", "pipelined"],
                    default="round_robin")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="pipelined schedule: split each client batch "
                         "into M chunks double-buffered across the cut")
    ap.add_argument("--topology",
                    choices=["vanilla", "u_shaped", "vertical", "multihop"],
                    default="vanilla")
    ap.add_argument("--wire", default="",
                    help="comma list: quantize_int8[:physical],"
                         "dp_noise:SIGMA,leakage_probe")
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--fleet", action="store_true",
                    help="shard the client axis over a device mesh "
                         "(repro.engine.fleet); on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first")
    ap.add_argument("--fleet-devices", type=int, default=0,
                    help="client-axis mesh size (0 = all visible devices)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    if args.n_clients < 1:
        ap.error("--n-clients must be >= 1")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(vocab=256)
    if args.cut < 0:
        args.cut = min(cfg.default_cut, max(1, cfg.n_layers // 2))
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    batch_fn = make_batch_fn(cfg, args.batch, args.seq)

    sess = build_plan(model, args).compile()
    sess.init(key)

    def round_batches(r):
        ks = jax.random.split(jax.random.fold_in(key, r),
                              args.n_clients)
        return [batch_fn(k) for k in ks]

    t0 = time.time()
    losses = sess.fit(round_batches, rounds=args.steps,
                      log_every=args.log_every)
    dt = time.time() - t0

    # eval over the WHOLE client fleet (vmapped over the stacked client
    # axis) — a single stack slice hides the spread once clients diverge
    eval_accs = sess.evaluate_all(
        batch_fn(jax.random.fold_in(key, args.steps + 1)))
    eval_accs = [round(float(a), 4) for a in eval_accs]
    print(f"eval acc/client: {eval_accs} (mean "
          f"{sum(eval_accs) / len(eval_accs):.4f})", flush=True)

    extra: dict = {}
    if sess.plan.mode in ("vanilla",):
        extra = {"n_clients": args.n_clients, "schedule": args.schedule,
                 "microbatches": args.microbatches,
                 "topology": args.topology,
                 "client_gb": [round(g, 6) for g in
                               sess.meter()["client_gb"]]}
        if args.wire:
            extra["wire"] = args.wire
            extra["wire_report"] = sess.wire_report(round_batches(0))
        if args.ckpt:
            if args.n_clients > 1:
                # parallel clients diverge — persist ALL stacked trees
                ckpt.save(args.ckpt + ".clients", sess.state["clients"],
                          step=args.steps)
            else:
                ckpt.save(args.ckpt + ".client",
                          tree_index(sess.state["clients"], 0),
                          step=args.steps)
            ckpt.save(args.ckpt + ".server", sess.state["server"],
                      step=args.steps)
    elif args.ckpt:
        ckpt.save(args.ckpt, sess.state["global"], step=args.steps)

    summary = {"arch": cfg.name, "mode": args.mode,
               "steps": args.steps, "wall_s": round(dt, 1),
               "first_loss": losses[0], "final_loss": losses[-1],
               "eval_acc_per_client": eval_accs}
    summary.update(extra)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
