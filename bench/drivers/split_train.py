"""Split training cells: a fleet of clients trains one model with a
server through `repro.api.Plan` -> `Session.run_round`.

Set-up makes the weights and a pool of distinct rounds of data on the
device from the seed, builds one compiled session and drives it through
its first round or rounds (which compile and warm every shape of the
window), keeping what the correctness check compares.  The window then
runs the same session's `run_round` back to back, each round fenced,
until `seconds` have passed.  Once the window has closed and device
memory has been read, the program's state is freed and the plain
reference (`bench/lib/split_ref.py`) retraces the first round, and at
least three turns, at f32 `highest` precision.

What is compared: the first turns' losses, client gradients and
weights, where the program and the reference still agree to rounding;
every first-round client's own step, whose size Adam's first step
fixes whatever the trajectory; and the server's first moment and change
after the first round.  Later rounds carry no comparison: two runs that
differ by rounding alone (the program's bf16 matmul passes against the
reference's f32, then a different int8 rounding of the handed-on
weights) part ways within a few rounds.
"""
from __future__ import annotations

import functools
import gc
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import harness, split_ref, trace as tr

CHECK_TURNS = 3
WINDOW_SPAN = "bench.window"


# ---------------------------------------------------------------------------
# inputs and the program
# ---------------------------------------------------------------------------

def make_pool(model, cfg, traffic, key, dtype=jnp.float32) -> list:
    """`pool_rounds` distinct rounds of (n_clients, per_client, ...)
    inputs, made on the device in one jitted call and cycled through."""
    lead = (traffic["pool_rounds"], traffic["n_clients"],
            traffic["per_client"])
    data = jax.jit(lambda k: model.make_inputs(k, cfg, traffic, lead,
                                               dtype))(key)
    return [{k: v[r] for k, v in data.items()}
            for r in range(traffic["pool_rounds"])]


def build_session(model, cfg, traffic):
    from repro import optim
    from repro.api import Plan
    from repro.api.wire import parse_wire
    o = cfg["optimizer"]
    init = jax.jit(lambda k: model.init_params(k, cfg))
    return Plan(mode="vanilla", model=model.program_model(cfg, init),
                cut=cfg["cut"], n_clients=traffic["n_clients"],
                schedule=traffic["schedule"], sync=cfg["sync"],
                optimizer=optim.adamw(o["lr"], b1=o["b1"], b2=o["b2"],
                                      eps=o["eps"],
                                      weight_decay=o["weight_decay"]),
                wire=parse_wire(cfg["wire"])).compile()


def _norms(tree):
    """Norm of each leaf, in f32, as one vector."""
    return jnp.stack([jnp.linalg.norm(a.astype(jnp.float32).ravel())
                      for a in jax.tree_util.tree_leaves(tree)])


def _gap_norms(a, b):
    """Norm of each leaf of a - b, in f32, fused: no tree of differences
    is kept."""
    return _norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


norms = jax.jit(_norms)
gap_norms = jax.jit(_gap_norms)


@functools.partial(jax.jit, static_argnums=1)
def head_norms(stacked, k: int):
    """(k, leaves): leaf norms of the first `k` stacked clients."""
    return jax.vmap(_norms)(jax.tree_util.tree_map(lambda a: a[:k], stacked))


@functools.partial(jax.jit, static_argnums=2)
def client_change(clients, init, k: int):
    """(k, leaves): the first `k` stacked clients' change from the one
    initial client `init`."""
    head = jax.tree_util.tree_map(lambda a: a[:k], clients)
    return jax.vmap(lambda c: _gap_norms(c, init))(head)


@jax.jit
def client_step_norms(clients, init):
    """(n_clients, leaves) after the first round of a round-robin with
    the p2p handoff: each client's own step, its weights less what it
    adopted, the previous client's weights through the int8 wire
    (client 0 adopted nothing: its weights less the initial ones)."""
    first = _gap_norms(jax.tree_util.tree_map(lambda a: a[0], clients), init)
    cur = jax.tree_util.tree_map(lambda a: a[1:], clients)
    prev = jax.tree_util.tree_map(lambda a: jax.vmap(split_ref.q8)(a[:-1]),
                                  clients)
    return jnp.concatenate([first[None],
                            jax.vmap(_gap_norms)(cur, prev)])


def capture(run_round, state_of, pool, n_clients) -> dict:
    """Drive the first rounds through `run_round` (which returns a
    round's per-turn losses) until the first round and CHECK_TURNS turns
    have run, and keep, leaf by leaf: for each of the first CHECK_TURNS
    turns its loss and its client's change from the initial weights,
    read after the round that holds the turn (the turns before it,
    handed on through the int8 wire, and its own step); for each of the
    first round's first clients the gradient as Adam got it (its first
    moment after its first step, (1 - b1) x the gradient); for every
    client of the first round its own step; and the server's first
    moment, change from its initial weights and optimizer step count
    after the first round, and every client's step count."""
    k = min(n_clients, CHECK_TURNS)
    st = state_of()
    # every client starts from the same weights: one copy of each side
    init_c = jax.tree_util.tree_map(lambda a: jnp.copy(a[0]), st["clients"])
    init_s = jax.tree_util.tree_map(jnp.copy, st["server"])
    losses, change, out, turn, r = [], [], {}, 0, 0
    while turn < max(n_clients, CHECK_TURNS):
        ls = np.asarray(run_round(pool[r]), np.float64)
        st = state_of()
        if r == 0:
            out["grad"] = np.asarray(head_norms(st["opt_c"]["m"], k),
                                     np.float64).ravel()
            out["steps"] = np.asarray(
                client_step_norms(st["clients"], init_c), np.float64)
            out["server_m"] = np.asarray(norms(st["opt_s"]["m"]), np.float64)
            out["server_change"] = np.asarray(
                gap_norms(st["server"], init_s), np.float64)
            out["server_steps"] = int(st["opt_s"]["step"])
            out["client_steps"] = np.asarray(st["opt_c"]["step"])
            del init_s
        rows = np.asarray(client_change(st["clients"], init_c, k), np.float64)
        for t in range(turn, min(turn + n_clients, CHECK_TURNS)):
            losses.append(ls[t - turn])
            change.append(rows[(t - turn) % k])
        turn, r = turn + n_clients, r + 1
    return dict(out, losses=np.asarray(losses),
                change=np.concatenate(change), clients=k, rounds=r,
                treedef=jax.tree_util.tree_structure(init_c))


def program_capture(sess, pool) -> dict:
    def run_round(batch):
        ls = sess.run_round(batch)
        jax.block_until_ready((ls, sess.state))
        return ls
    return capture(run_round, lambda: sess.state, pool, sess.plan.n_clients)


def reference_capture(model, cfg, key_w, pool, n_clients,
                      control: bool = False) -> dict:
    """What `capture` keeps, from the plain reference over the same
    weights and rows; with `control`, from the model's control (the
    reference in the precision below the configured one).  Round-robin
    with the p2p handoff is one chain of client weights, each turn's
    client adopting the last one's; each client keeps its own optimizer
    state.  The reference follows the first round and at least
    CHECK_TURNS turns."""
    k = min(n_clients, CHECK_TURNS)
    fwd, loss, dtype = model.reference(cfg, control)
    params = jax.jit(lambda key: model.init_params(key, cfg))(key_w)
    pc, ps = model.split(params, cfg)
    init_c = jax.tree_util.tree_map(jnp.copy, pc)   # the configured dtype
    init_s = jax.tree_util.tree_map(jnp.copy, ps)
    own = lambda t: jax.tree_util.tree_map(lambda a: jnp.array(a, dtype), t)
    pc, ps = own(pc), own(ps)                     # a tied table: two copies
    del params
    opt_c, os_ = {}, split_ref.adam_init(ps)
    turn = split_ref.make_turn(fwd, loss, cfg["optimizer"])
    losses, grad, change, counts, out = [], [], [], [], {}
    for t in range(max(n_clients, CHECK_TURNS)):
        r, ci = divmod(t, n_clients)
        batch = {name: (v[ci].astype(dtype) if v.dtype == jnp.float32
                        else v[ci]) for name, v in pool[r].items()}
        if ci not in opt_c:
            opt_c[ci] = split_ref.adam_init(pc)
        pc, opt_c[ci], ps, os_, ls = turn(pc, opt_c[ci], ps, os_, batch,
                                          t > 0 and n_clients > 1)
        if t < k:
            grad.append(np.asarray(norms(opt_c[ci]["m"])))
        if t < n_clients:
            counts.append(int(opt_c[ci]["step"]))
        if ci >= k:
            del opt_c[ci]        # a later turn of this client is not followed
        if t < CHECK_TURNS:
            losses.append(float(ls))
            change.append(np.asarray(gap_norms(pc, init_c)))
        if t == n_clients - 1:
            out["server_m"] = np.asarray(norms(os_["m"]), np.float64)
            out["server_change"] = np.asarray(
                gap_norms(ps, init_s), np.float64)
            out["server_steps"] = int(os_["step"])
    return dict(out, losses=np.asarray(losses, np.float64),
                grad=np.concatenate(grad).astype(np.float64),
                change=np.concatenate(change).astype(np.float64),
                steps=change[0].astype(np.float64)[None],
                client_steps=np.asarray(counts),
                clients=k, rounds=-(-max(n_clients, CHECK_TURNS)
                                     // n_clients),
                treedef=jax.tree_util.tree_structure(init_c))


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def leaf_gaps(got, want, want_grad) -> np.ndarray:
    """Each leaf's gap between two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger.  Leaves
    whose reference gradient is under a thousandth of the median leaf's
    (nought to rounding) are left out."""
    keep = want_grad >= 1e-3 * np.median(want_grad)
    floor = np.median(want[keep])
    return (np.abs(got - want) / np.maximum(want, floor))[keep]


def leaf_gap(got, want, want_grad) -> float:
    """The worst leaf's gap (`leaf_gaps`)."""
    return float(leaf_gaps(got, want, want_grad).max())


def per_turn(got: dict, want: dict) -> dict:
    """Every turn's loss gap and worst-leaf gradient and change gaps,
    every first-round client's step gap against the reference's first
    step, and the server's gaps after the first round."""
    if got["treedef"] != want["treedef"]:
        raise ValueError(f"program and reference trees differ: "
                         f"{got['treedef']} vs {want['treedef']}")
    gl, wl = got["losses"], want["losses"]
    loss = (np.abs(gl - wl) / np.abs(wl) if np.all(np.isfinite(gl))
            else np.full(wl.shape, np.inf))
    k = want["clients"]
    wg = np.split(want["grad"], k)
    grad = [leaf_gap(g, w, w) for g, w in zip(np.split(got["grad"], k), wg)]
    change = [leaf_gap(g, w, wg[t % k]) for t, (g, w) in enumerate(zip(
        np.split(got["change"], CHECK_TURNS),
        np.split(want["change"], CHECK_TURNS)))]
    step = [leaf_gap(g, want["steps"][0], wg[0]) for g in got["steps"]]
    wm = want["server_m"]
    return {"loss_gap": loss.tolist(), "grad_gap": grad,
            "change_gap": change, "step_gap": step,
            "server_grad_gap": leaf_gap(got["server_m"], wm, wm),
            "server_change_gap": leaf_gap(got["server_change"],
                                          want["server_change"], wm),
            "server_change_median_gap": float(np.median(leaf_gaps(
                got["server_change"], want["server_change"], wm))),
            "server_step_gap": abs(got["server_steps"]
                                   - want["server_steps"]),
            "client_step_gap": int(np.abs(got["client_steps"]
                                          - want["client_steps"]).max())}


def readings(got: dict, want: dict) -> dict:
    """The numbers compared: the first turn's loss gap and first client
    gradient's worst-leaf gap; the worst leaf's change gap over the
    first three turns' clients; the worst step gap over every client of
    the first round; the server's first-round moment and change gaps
    (the change's by the worst and by the median leaf);
    and, exact (limit 0), how many optimizer steps the server's first
    round took beyond or short of one a turn, and the same of each
    client's one step."""
    t = per_turn(got, want)
    return {"loss_gap": float(t["loss_gap"][0]),
            "grad_gap": float(t["grad_gap"][0]),
            "change_gap": float(max(t["change_gap"])),
            "step_gap": float(max(t["step_gap"])),
            "server_grad_gap": float(t["server_grad_gap"]),
            "server_change_gap": float(t["server_change_gap"]),
            "server_change_median_gap": float(t["server_change_median_gap"]),
            "server_step_gap": float(t["server_step_gap"]),
            "client_step_gap": float(t["client_step_gap"])}


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def window(sess, pool, start: int, seconds: float) -> dict:
    """Rounds back to back, each fenced, cycling through the pool from
    round `start`, until `seconds` have passed.  The window ends when
    the round that crossed the deadline ends: every round counts,
    whole."""
    n_pool, rounds, losses, longest = len(pool), 0, [], 0.0
    meter = sess.engine.meter
    bytes0 = sum(meter.bytes_up) + sum(meter.bytes_down) + sum(
        meter.sync_bytes)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        t0 = t1 = time.perf_counter()
        while True:
            batch = pool[(start + rounds) % n_pool]
            with jax.profiler.TraceAnnotation("bench.run_round"):
                ls = sess.run_round(batch)
            with jax.profiler.TraceAnnotation("bench.fence"):
                jax.block_until_ready((ls, sess.state))
            losses.append(ls)
            rounds += 1
            t_prev, t1 = t1, time.perf_counter()
            longest = max(longest, t1 - t_prev)
            if t1 - t0 >= seconds:
                break
    wire = (sum(meter.bytes_up) + sum(meter.bytes_down)
            + sum(meter.sync_bytes) - bytes0)
    finite = [bool(np.all(np.isfinite(np.asarray(ls)))) for ls in losses]
    return {"rounds": rounds, "window_s": t1 - t0, "wire_bytes": wire,
            "failed": finite.count(False), "longest_round_s": longest}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell, cfg, traffic, limits, per_layer, *, seed, seconds, trace,
        devices, peak, compiles, t_start, hooks=None) -> tuple:
    """One run of a training cell -> (result, checks).  `hooks` lets a
    test plant a fault in the session ("session": sess -> None)."""
    hooks = hooks or {}
    model = harness.load_module(
        harness.BENCH_DIR / "models" / f"{cfg['model']}.py", cfg["model"])
    key_w, key_d = jax.random.split(harness.seed_key(seed))
    pool = make_pool(model, cfg, traffic, key_d)
    sess = build_session(model, cfg, traffic)
    sess.init(key_w)
    if "session" in hooks:
        hooks["session"](sess)
    mark = compiles.mark()
    got = program_capture(sess, pool)
    setup_s = time.perf_counter() - t_start
    harness.note("setup", setup_s=setup_s, **compiles.since(mark),
                 memory=harness.memory(devices))

    mark = compiles.mark()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        seconds = min(seconds, harness.TRACE_SECONDS)
    if trace:
        with jax.profiler.trace(trace_dir,
                                profiler_options=harness.trace_options()):
            win = window(sess, pool, got["rounds"], seconds)
    else:
        win = window(sess, pool, got["rounds"], seconds)
    in_window = compiles.since(mark)
    mem = harness.memory(devices)
    n, b = traffic["n_clients"], traffic["per_client"]
    samples = win["rounds"] * n * b
    rate = samples / win["window_s"]
    harness.note("window", rounds=win["rounds"], samples=samples,
                 window_s=win["window_s"], samples_per_s=rate,
                 longest_round_s=win["longest_round_s"],
                 failed_rounds=win["failed"], **in_window, memory=mem)
    if in_window["compiles"]:
        harness.note("warning", msg="the window compiled",
                     compiles=in_window["compiles"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(m["peak_bytes_in_use"] or 0
                                       for m in mem.values())}
    del sess
    gc.collect()

    want = reference_capture(model, cfg, key_w, pool, traffic["n_clients"])
    checks = harness.checks_of(readings(got, want), limits)

    result = {"correct": all(c["ok"] for c in checks.values())
              and win["failed"] == 0,
              "attempted": win["rounds"], "failed": win["failed"],
              "device": device}
    if not trace:
        result["metrics"] = {
            "train_samples_per_s": {"value": rate, "unit": "samples/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return result, checks

    ctx, busy, window_s, breakdown = tr.read_window(
        trace_dir, [d.id for d in devices], WINDOW_SPAN)
    ctx.update(peak=peak, chips=len(devices), samples_per_s=rate,
               samples=samples,
               wire_bytes=win["wire_bytes"], cfg=cfg, traffic=traffic,
               model=model)
    result["metrics"] = harness.read_per_layer(per_layer, cell["name"], ctx)
    device.update(busy_s=busy, window_s=window_s)
    result["breakdown"] = breakdown
    return result, checks
