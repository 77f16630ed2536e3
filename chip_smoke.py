#!/usr/bin/env python3
"""Bring-up smoke: split training and split serving end to end on a TPU.

    python chip_smoke.py                # phases paper, lm_train, serve (1 chip)
    python chip_smoke.py --four-chips   # the fleet over 4 chips vs 1 device

Every phase drives the entry points a user calls (`Plan` -> `Session`,
`ServePlan` -> `ServeSession` -> `Batcher`) with data and weights made
from `--seed`, and checks its own outputs; a failed check raises and the
script exits non-zero.  The lines before the last are bring-up
diagnostics (compile time, fenced step time, peak device memory, kernel
mode, exact wire bytes), not benchmark results.  The last line is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.

Phases (one chip):
  paper    — the paper's VGG-16 at published widths, 4 clients,
             round-robin vanilla split over the physical int8 wire;
  lm_train — phi4-mini at published widths, depth and vocabulary cut to
             fit one chip (each cut printed), 2 clients, adamw;
  serve    — split-vs-monolithic prefill logits at 4 layers, then the
             complete 32-layer phi4-mini served to five tenants through
             a `Batcher`, one tenant checked against solo `generate`.

The script refuses to run without a TPU: it never measures the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import optim  # noqa: E402
from repro.api import (FleetSpec, Plan, lm_split_fns,  # noqa: E402
                       quantize_int8)
from repro.configs import get_config  # noqa: E402
from repro.configs import vgg_cifar10  # noqa: E402
from repro.core import split as sp  # noqa: E402
from repro.data import synthetic as syn  # noqa: E402
from repro.engine import stack_batches, tree_index  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.nn import convnets as C  # noqa: E402
from repro.serve import Batcher, ServePlan  # noqa: E402

PHI4 = "phi4_mini_3_8b"
# split-vs-monolithic logits at 4 full-width layers: bf16 keeps 8
# significant bits, so the bound is 2% of the largest logit
LOGIT_TOL = 0.02


class CompileMeter:
    """Backend compiles and their seconds (a persistent-cache load counts
    as one, with its load time) and persistent-cache hits/misses, from
    jax.monitoring."""

    def __init__(self):
        self.count, self.secs, self.hits, self.misses = 0, 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return self.count, self.secs, self.hits, self.misses

    def since(self, mark) -> dict:
        n, s, h, m = mark
        return {"compiles": self.count - n,
                "compile_s": round(self.secs - s, 3),
                "cache_hits": self.hits - h, "cache_misses": self.misses - m}


COMPILES: CompileMeter | None = None


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def report(phase, **fields):
    print(f"[{phase}] " + json.dumps(fields, default=str), flush=True)


def memory(devices=None) -> dict:
    """Device memory as the backend reports it; `peak_bytes_in_use` is
    the process-wide high-water mark so far."""
    out = {}
    for d in devices or jax.devices()[:1]:
        st = d.memory_stats() or {}
        out[str(d.id)] = {k: st.get(k) for k in ("bytes_in_use",
                                                 "peak_bytes_in_use")}
    return out


def vgg_segmodel(cnn):
    layers = C.vgg_plan(cnn)
    return sp.list_segmodel(
        n_segments=len(layers),
        init=lambda k: C.vgg_init(k, cnn),
        layer_apply=lambda p, i, x: C.vgg_layer_apply(p, layers[i], x))


def image_round(key, cnn, n_clients, per_client):
    b = syn.image_batch(key, per_client * n_clients, cnn.n_classes)
    return stack_batches([
        {"x": b["images"][i * per_client:(i + 1) * per_client],
         "labels": b["labels"][i * per_client:(i + 1) * per_client]}
        for i in range(n_clients)])


def train_rounds(sess, batches, rounds):
    """`rounds` rounds on ONE repeated batch, each fenced.  The engine
    donates its state, so only `sess.state` is read afterwards."""
    losses, secs = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        ls = sess.run_round(batches)
        jax.block_until_ready((ls, sess.state))
        secs.append(time.perf_counter() - t0)
        losses.append(np.asarray(ls, np.float64))
    return np.stack(losses), secs


def check_metered_bytes(sess, batches, rounds) -> dict:
    """The meter's per-client totals must be exactly `rounds` times the
    per-turn `wire_report` bytes."""
    wires = sess.wire_report(batches)
    up = sum(w["bytes"] for w in wires if w["direction"] == "up")
    down = sum(w["bytes"] for w in wires if w["direction"] == "down")
    meter = sess.engine.meter
    for ci in range(sess.plan.n_clients):
        check(meter.bytes_up[ci] == rounds * up,
              f"client {ci}: metered up {meter.bytes_up[ci]} != "
              f"{rounds} x {up} reported")
        check(meter.bytes_down[ci] == rounds * down,
              f"client {ci}: metered down {meter.bytes_down[ci]} != "
              f"{rounds} x {down} reported")
    check(all(w["physical"] for w in wires), "wire records not physical")
    return {"wires": [(w["name"], w["direction"], w["shape"], w["bytes"])
                      for w in wires],
            "bytes_up_per_turn": up, "bytes_down_per_turn": down,
            "sync_bytes_per_client": list(meter.sync_bytes)}


def train_report(phase, mark, losses, secs):
    check(np.all(np.isfinite(losses)), f"non-finite losses {losses}")
    first, last = losses[0].mean(), losses[-1].mean()
    check(last < first, f"loss on the repeated batch did not fall: "
                        f"{first} -> {last}")
    report(phase, kernel_mode=ops.kernel_mode(), **COMPILES.since(mark),
           first_round_s=secs[0],
           round_s=statistics.median(secs[1:]) if len(secs) > 1 else None,
           loss_first=float(first), loss_last=float(last),
           memory=memory())


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_paper(seed, *, cnn=vgg_cifar10.CONFIG, cut=2, n_clients=4,
                per_client=64, rounds=4):
    """VGG-16 vanilla split, round-robin, physical int8 wire."""
    mark = COMPILES.mark()
    sess = Plan(mode="vanilla", model=vgg_segmodel(cnn), cut=cut,
                n_clients=n_clients, schedule="round_robin",
                optimizer=optim.adamw(1e-3),
                wire=[quantize_int8(physical=True)]).compile()
    sess.init(jax.random.PRNGKey(seed))
    batches = image_round(jax.random.PRNGKey(seed + 1), cnn, n_clients,
                          per_client)
    losses, secs = train_rounds(sess, batches, rounds)
    wire = check_metered_bytes(sess, batches, rounds)
    report("paper", model=cnn.name, width_mult=cnn.width_mult, cut=cut,
           n_clients=n_clients, per_client_batch=per_client, **wire)
    train_report("paper", mark, losses, secs)

    # the chip's quantize kernel against the jnp oracle, on a real cut
    # activation of the trained client
    pc = tree_index(sess.state["clients"], 0)
    act = jax.jit(sess.engine.topology.client_fwd)(
        pc, {k: v[0] for k, v in batches.items()})
    q, s = jax.jit(ops.wire_quantize)(act)
    q_ref, s_ref = jax.jit(ref.wire_quant_ref)(act)
    dq = np.abs(np.asarray(q, np.int32) - np.asarray(q_ref, np.int32))
    ds = np.abs(np.asarray(s) - np.asarray(s_ref))
    report("paper", kernel_check="wire_quantize vs kernels.ref.wire_quant_ref",
           act_shape=tuple(act.shape), q_max_abs_diff=int(dq.max()),
           q_elements_differing=int((dq > 0).sum()),
           scale_max_abs_diff=float(ds.max()),
           bitwise=bool(dq.max() == 0 and ds.max() == 0))
    # a divide that rounds differently may move a value across a .5
    # rounding boundary: one int8 step, never more
    check(dq.max() <= 1, f"wire_quantize q differs by {dq.max()} steps")
    check(ds.max() == 0, f"wire_quantize scales differ by {ds.max()}")


def phase_lm_train(seed, *, base=None, n_layers=4, cut=2, vocab=25_088,
                   n_clients=2, batch=4, seq=512, rounds=4):
    """phi4-mini split training at published widths, cut to one chip."""
    base = base or get_config(PHI4)
    cfg = dataclasses.replace(base, n_layers=n_layers, vocab=vocab)
    for name, was, now in (("n_layers", base.n_layers, n_layers),
                           ("vocab", base.vocab, vocab)):
        if was != now:
            report("lm_train", reduction=name, published=was, run=now,
                   why="one chip's 16 GB holds params + fp32 adam state "
                       "for both clients and the server")
    mark = COMPILES.mark()
    model = build_model(cfg)
    sess = Plan(mode="vanilla", model=lm_split_fns(model, cut), cut=cut,
                n_clients=n_clients, schedule="round_robin",
                optimizer=optim.adamw(1e-3),
                wire=[quantize_int8(physical=True)]).compile()
    sess.init(jax.random.PRNGKey(seed))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), n_clients)
    batches = stack_batches([syn.lm_batch(k, batch, seq, cfg.vocab)
                             for k in keys])
    losses, secs = train_rounds(sess, batches, rounds)
    wire = check_metered_bytes(sess, batches, rounds)
    report("lm_train", model=cfg.name, d_model=cfg.d_model,
           heads=(cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim),
           d_ff=cfg.d_ff, dtype=jnp.dtype(cfg.dtype).name, cut=cut,
           n_clients=n_clients, batch=batch, seq=seq,
           tokens_per_round=n_clients * batch * seq, **wire)
    train_report("lm_train", mark, losses, secs)


def _serve_parity(seed, cfg, n_layers, prompt_len):
    """fp32-wire split prefill logits vs the monolithic `model.prefill`."""
    cfg = dataclasses.replace(cfg, n_layers=n_layers)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    sess = ServePlan(arch=cfg, cut=n_layers // 2, wire="", max_batch=1,
                     max_len=prompt_len).session(params)
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 2),
                                (1, prompt_len), 0, cfg.vocab)
    batch = {"tokens": prompt}

    @jax.jit
    def mono(p, b):
        return model.prefill(p, b, model.init_cache(1, prompt_len))[0]

    @jax.jit
    def split(cp, sp_, b):
        cc, sc = model.init_cache_split(1, prompt_len, sess.cut)
        act, _ = model.prefill_client(cp, b, sess.cut, cc)
        return model.prefill_server(sp_, act, sess.cut, sc)[0]

    want = np.asarray(mono(params, batch), np.float32)
    got = np.asarray(split(sess.client_params, sess.server_params, batch),
                     np.float32)
    err, top = float(np.abs(got - want).max()), float(np.abs(want).max())
    report("serve", parity="split fp32-wire prefill vs model.prefill",
           layers=n_layers, cut=sess.cut, logits_shape=want.shape,
           max_abs_diff=err, max_abs_logit=top,
           tolerance=f"{LOGIT_TOL} x max|logit|",
           argmax_last_equal=bool(want[0, -1].argmax() == got[0, -1].argmax()))
    check(np.all(np.isfinite(got)), "non-finite split logits")
    check(err <= LOGIT_TOL * top,
          f"split logits differ by {err} (> {LOGIT_TOL} x {top})")


def phase_serve(seed, *, arch=PHI4, parity_layers=4,
                prompt_lens=(17, 64, 128, 200), late_len=40, new=16,
                max_batch=4, max_len=512):
    """Split serving of the complete model through `Batcher`."""
    mark = COMPILES.mark()
    cfg = arch if not isinstance(arch, str) else get_config(arch)
    _serve_parity(seed, cfg, parity_layers, max(prompt_lens))
    gc.collect()
    report("serve", step="parity done", **COMPILES.since(mark),
           memory=memory())

    mark = COMPILES.mark()
    plan = ServePlan(arch=cfg, wire="quantize_int8:physical",
                     max_batch=max_batch, max_len=max_len)
    t0 = time.perf_counter()
    sess = plan.session(jax.random.PRNGKey(seed))
    jax.block_until_ready((sess.client_params, sess.server_params))
    init_s = time.perf_counter() - t0
    leaves = jax.tree_util.tree_leaves((sess.client_params,
                                        sess.server_params))
    weights = sum({id(a): a.nbytes for a in leaves}.values())  # tied once
    report("serve", step="session", model=cfg.name, n_layers=cfg.n_layers,
           vocab=cfg.vocab, cut=sess.cut, init_s=init_s,
           weight_bytes=weights, memory=memory())

    bat = Batcher(sess)
    pkeys = jax.random.split(jax.random.PRNGKey(seed + 3),
                             len(prompt_lens) + 1)
    prompts = [jax.random.randint(k, (n,), 0, cfg.vocab)
               for k, n in zip(pkeys, tuple(prompt_lens) + (late_len,))]
    seated, join_s, step_s = [], [], []

    def join(i):
        t0 = time.perf_counter()
        seated.append(bat.tenants[bat.join(prompts[i], new)])
        join_s.append(time.perf_counter() - t0)

    def step():
        t0 = time.perf_counter()
        bat.step()                  # one host read of the step's tokens
        step_s.append(time.perf_counter() - t0)

    join(0)
    join(1)
    for _ in range(3):              # two tenants decode before the rest
        step()
    for i in range(2, len(prompt_lens)):
        join(i)
    check(not bat.free_slots(), "batch should be full")
    while not bat.free_slots():
        step()
    join(len(prompt_lens))          # the late tenant takes a freed slot
    window = COMPILES.mark()        # every shape is warm from here on
    while bat.tenants:
        step()
    window = COMPILES.since(window)["compiles"]
    check(all(t.done for t in seated) and len(bat.finished) == len(prompts),
          f"{len(bat.finished)} of {len(prompts)} tenants finished")
    check(all(len(t.tokens) == new for t in seated),
          f"token counts {[len(t.tokens) for t in seated]} != {new}")

    # one tenant's batched stream against the session's solo generate
    t0_tokens = seated[0].tokens
    t0 = time.perf_counter()
    solo = jax.block_until_ready(sess.generate(prompts[0][None], new))
    solo_s = time.perf_counter() - t0
    solo = [int(x) for x in np.asarray(solo)[0]]
    report("serve", step="batcher", kernel_mode=ops.kernel_mode(),
           **COMPILES.since(mark), tenants=len(prompts),
           prompt_lens=[int(p.shape[0]) for p in prompts], new_tokens=new,
           join_s=join_s, step_s_median=statistics.median(step_s),
           steps=len(step_s), compiles_in_last_steps=window,
           solo_generate_s=solo_s,
           wire_bytes_up=bat.bytes_up, wire_bytes_down=bat.bytes_down,
           tokens_generated=bat.tokens_generated,
           decode_bytes_per_token=sess.bytes_per_token(),
           tenant0_batched=t0_tokens, tenant0_solo=solo,
           memory=memory())
    check(window == 0, f"{window} compiles while decoding warm shapes")
    check(bat.tokens_generated == new * len(prompts),
          f"{bat.tokens_generated} tokens generated")
    check(t0_tokens == solo,
          f"batched tokens {t0_tokens} != solo generate {solo}")


def phase_four_chips(seed, *, cnn=vgg_cifar10.CONFIG, cut=2, n_clients=8,
                     per_client=64, rounds=2, n_devices=4):
    """The fleet engine over `n_devices` chips vs the single-device
    engine, on the same data and seed."""
    devices = jax.devices()[:n_devices]
    check(len(devices) == n_devices,
          f"--four-chips needs {n_devices} devices, jax sees "
          f"{len(jax.devices())}")
    batches = image_round(jax.random.PRNGKey(seed + 1), cnn, n_clients,
                          per_client)
    for schedule in ("parallel", "round_robin"):
        mark = COMPILES.mark()
        out = {}
        for fleet in (None, FleetSpec(n_devices=n_devices)):
            sess = Plan(mode="vanilla", model=vgg_segmodel(cnn), cut=cut,
                        n_clients=n_clients, schedule=schedule,
                        optimizer=optim.adamw(1e-3),
                        wire=[quantize_int8(physical=True)],
                        fleet=fleet).compile()
            sess.init(jax.random.PRNGKey(seed))
            losses, secs = train_rounds(sess, batches, rounds)
            out["fleet" if fleet else "single"] = (sess, losses, secs)
        (single, l1, s1), (fleet, l4, s4) = out["single"], out["fleet"]
        placed = {d for leaf in jax.tree_util.tree_leaves(
            fleet.state["clients"]) for d in leaf.sharding.device_set}
        mem = memory(devices)
        report("four_chips", schedule=schedule, n_clients=n_clients,
               n_devices=n_devices, **COMPILES.since(mark),
               kernel_mode=ops.kernel_mode(),
               losses_single=l1.tolist(), losses_fleet=l4.tolist(),
               max_abs_loss_diff=float(np.abs(l1 - l4).max()),
               round_s_single=s1, round_s_fleet=s4,
               client_state_devices=sorted(d.id for d in placed),
               meter_single=single.meter(), meter_fleet=fleet.meter(),
               memory=mem)
        check(np.all(np.isfinite(l4)), "non-finite fleet losses")
        np.testing.assert_allclose(l4, l1, rtol=1e-3, atol=1e-3)
        check(single.meter() == fleet.meter(), "meters differ")
        check(placed == set(devices),
              f"client state on {sorted(d.id for d in placed)}")
        check(all((m["bytes_in_use"] or 0) > 0 for m in mem.values()),
              f"a device holds nothing: {mem}")
        del single, fleet, out
        gc.collect()


# ---------------------------------------------------------------------------

def main():
    global COMPILES
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip fleet phase and its "
                         "single-device comparison")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU present (jax sees platform "
                 f"{dev.platform!r}); this smoke runs on a TPU only")
    mode = ops.kernel_mode()
    if mode != "pallas":
        sys.exit(f"chip_smoke: kernel mode is {mode!r}, not 'pallas' "
                 "(unset REPRO_KERNELS)")
    cache = enable_compile_cache()
    COMPILES = CompileMeter()
    report("setup", platform=dev.platform, kind=dev.device_kind,
           devices=len(jax.devices()), kernel_mode=mode, jax=jax.__version__,
           compile_cache=cache, seed=args.seed)

    if args.four_chips:
        phase_four_chips(args.seed)
        count = 4
    else:
        for phase in (phase_paper, phase_lm_train, phase_serve):
            t0 = time.perf_counter()
            phase(args.seed)
            gc.collect()
            report(phase.__name__[len("phase_"):], phase_s=round(
                time.perf_counter() - t0, 3), passed=True)
        count = 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
