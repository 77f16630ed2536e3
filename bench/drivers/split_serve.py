"""Split serving cells: an open loop of tenants served by
`repro.serve.Batcher` over one `ServeSession`.

Requests arrive on a schedule (Poisson at the traffic file's fixed
rate) whether or not earlier ones have finished.  Each is admitted into
a free slot as soon as one is free (`Batcher.join`: prefill through both
halves, first token on the host), and every live tenant advances one
token per `Batcher.step`.  All of it runs in this one thread, which is
what a single-host deployment of the batcher does.

Set-up builds the session from the seed, compiles every prompt bucket's
prefill and every slot's scatter, and then runs the loop for the
traffic's `warm_s` untimed seconds, so that the window opens on a busy
batcher.  The window is the next `seconds` of the same loop.  After it,
device memory is read, the program is freed, and the plain reference
(`bench/models/<model>.py: served_logits`) recomputes the logits of a
sample of finished requests, teacher-forced over prompt and served
tokens.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import harness, trace as tr

WINDOW_SPAN = "bench.window"


# ---------------------------------------------------------------------------
# the traffic
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    index: int
    due: float               # seconds after the loop's start
    prompt_len: int
    answer_len: int
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)


def _lognormal(rng, spec, n):
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def make_requests(traffic, horizon_s: float) -> list:
    """The schedule: arrival times and lengths, drawn from the traffic's
    `population_seed`, the same in every run.  A run's seed draws the
    prompt tokens and the weights; it does not reorder the schedule,
    because near the knee the order of a few long answers moves the TTFT
    tail by a factor of ten between seeds."""
    n = int(traffic["rate_per_s"] * horizon_s * 1.25) + 20
    pop = np.random.default_rng(traffic["population_seed"])
    prompt = _lognormal(pop, traffic["prompt"], n)
    buckets = np.asarray(traffic["prompt"]["buckets"])
    prompt = buckets[np.searchsorted(buckets, prompt)]
    answer = _lognormal(pop, traffic["answer"], n)
    due = np.cumsum(pop.exponential(1.0 / traffic["rate_per_s"], n))
    return [Request(i, float(due[i]), int(prompt[i]), int(answer[i]))
            for i in range(n)]


def prompt_tokens(seed: int, r: Request, vocab: int) -> np.ndarray:
    return np.random.default_rng([seed, r.index]).integers(
        0, vocab, r.prompt_len, dtype=np.int32)


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    join_s: list = dataclasses.field(default_factory=list)
    step_s: list = dataclasses.field(default_factory=list)
    step_live: list = dataclasses.field(default_factory=list)
    step_pos: list = dataclasses.field(default_factory=list)
    lag_s: list = dataclasses.field(default_factory=list)


def serve(bat, reqs, seed, vocab, t0, start, stop, state, rec: Record):
    """Run the open loop from `start` to `stop` (seconds after t0).
    `state` carries the queue and the seated requests across calls."""
    queue, seated = state["queue"], state["seated"]
    while True:
        now = time.perf_counter() - t0
        if now >= stop:
            return
        while state["next"] < len(reqs) and reqs[state["next"]].due <= now:
            queue.append(reqs[state["next"]])
            state["next"] += 1
        while queue and bat.free_slots():
            r = queue.popleft()
            toks = jnp.asarray(prompt_tokens(seed, r, vocab))
            tj = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.join"):
                slot = bat.join(toks, r.answer_len)
            t = time.perf_counter()
            rec.join_s.append(t - tj)
            r.times.append(t - t0)
            seated[slot] = r
            _collect(bat, seated)
        if bat.tenants:
            live = list(bat.tenants.values())
            rec.step_live.append(len(live))
            rec.step_pos.append(sum(len(seated[tn.slot].tokens)
                                    + seated[tn.slot].prompt_len
                                    for tn in live))
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                bat.step()
            t = time.perf_counter()
            rec.step_s.append(t - ts)
            for tn in live:
                seated[tn.slot].times.append(t - t0)
            _collect(bat, seated)
        else:
            nxt = (reqs[state["next"]].due if state["next"] < len(reqs)
                   else stop)
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, stop) - now))
            if nxt <= stop:
                rec.lag_s.append(time.perf_counter() - t0 - nxt)


def _collect(bat, seated):
    """Take finished tenants' tokens and drop the tenants (with their
    client caches), as a client does once its answer is complete."""
    for tn in bat.finished:
        r = seated.pop(tn.slot)
        r.tokens = list(tn.tokens)
    bat.finished.clear()
    for slot, tn in bat.tenants.items():
        seated[slot].tokens = tn.tokens


def warm(bat, traffic, vocab):
    """Compile what the window uses: every bucket's prefill, every slot's
    scatter, the client and server steps."""
    rng = np.random.default_rng(0)
    for length in traffic["prompt"]["buckets"]:
        for _ in range(bat.max_batch):
            bat.join(jnp.asarray(rng.integers(0, vocab, length,
                                              dtype=np.int32)), 2)
        bat.run()
    bat.finished.clear()


def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else float("nan")


def window_stats(reqs, w0, w1) -> dict:
    """TTFT of every request due in [w0, w1) (one still without a first
    token enters at w1 - due) and every inter-token gap that ends in
    the window."""
    ttft, itl = [], []
    for r in reqs:
        if w0 <= r.due < w1:
            first = r.times[0] if r.times else w1
            ttft.append(min(first, w1) - r.due)
        for a, b in zip(r.times, r.times[1:]):
            if w0 <= b < w1:
                itl.append(b - a)
    done = sum(1 for r in reqs if r.times and len(r.times) == r.answer_len
               and w0 <= r.times[-1] < w1)
    return {"ttft": ttft, "itl": itl, "completed": done}


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def sample(reqs, seed, n: int) -> list:
    """`n` finished requests drawn from the seed, the longest among
    them."""
    done = [r for r in reqs if r.tokens and len(r.tokens) == r.answer_len]
    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt_len + r.answer_len)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(model, cfg, traffic, key_w, picked, seed, n: int,
                   control: bool = False) -> np.ndarray:
    """Per served token, the gap by which the reference's logit of the
    served token (with `control`, of the token the model's control puts
    first) lies below the reference's best.  The `n` sequences (picked
    requests, then empty rows) are padded to `max_len` tokens and to the
    longest answer, so that one program serves every run."""
    max_len, most = cfg["max_len"], traffic["answer"]["max"]
    seq = np.zeros((n, max_len), np.int32)
    pos = np.zeros((n, most), np.int32)
    chosen = np.zeros((n, most), np.int32)
    valid = np.zeros((n, most), bool)
    for i, r in enumerate(picked):
        full = np.concatenate([prompt_tokens(seed, r, cfg["vocab"]),
                               np.asarray(r.tokens[:-1], np.int32)])
        seq[i, :len(full)] = full
        k = len(r.tokens)
        pos[i, :k] = r.prompt_len - 1 + np.arange(k)
        chosen[i, :k] = r.tokens
        valid[i, :k] = True
    hi = jax.lax.Precision.HIGHEST
    ref = model.served_logits(key_w, cfg, jnp.asarray(seq),
                              jnp.asarray(pos), hi)
    if control:
        ctl = model.served_logits(key_w, cfg, jnp.asarray(seq),
                                  jnp.asarray(pos), hi, model.fp8)
        pick = jnp.argmax(ctl, -1)
        del ctl
    else:
        pick = jnp.asarray(chosen)
    gaps = ref.max(-1) - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return np.asarray(gaps, np.float64)[valid]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def build(model, cfg, key_w):
    from repro.serve import Batcher, ServePlan
    plan = ServePlan(arch=model.program_arch(cfg), cut=cfg["cut"],
                     wire=cfg["wire"], max_batch=cfg["max_batch"],
                     max_len=cfg["max_len"])
    sess = plan.session(key_w)
    return sess, Batcher(sess)


def run(cell, cfg, traffic, limits, per_layer, *, seed, seconds, trace,
        devices, peak, compiles, t_start, hooks=None) -> tuple:
    hooks = hooks or {}
    model = harness.load_module(
        harness.BENCH_DIR / "models" / f"{cfg['model']}.py", cfg["model"])
    key_w = jax.random.split(harness.seed_key(seed))[0]
    vocab = cfg["vocab"]
    sess, bat = build(model, cfg, key_w)
    if "batcher" in hooks:
        hooks["batcher"](bat)
    mark = compiles.mark()
    warm(bat, traffic, vocab)
    warm_s = traffic["warm_s"]
    reqs = make_requests(traffic, warm_s + seconds)
    state = {"queue": collections.deque(), "seated": {}, "next": 0}
    rec_warm, rec = Record(), Record()
    t0 = time.perf_counter()
    serve(bat, reqs, seed, vocab, t0, 0.0, warm_s, state, rec_warm)
    setup_s = time.perf_counter() - t_start
    harness.note("setup", setup_s=setup_s, **compiles.since(mark),
                 memory=harness.memory(devices))

    mark = compiles.mark()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        seconds = min(seconds, harness.TRACE_SECONDS)
    w0 = time.perf_counter() - t0

    def window():
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            serve(bat, reqs, seed, vocab, t0, w0, w0 + seconds, state, rec)
        return time.perf_counter() - t0
    if trace:
        with jax.profiler.trace(trace_dir,
                                profiler_options=harness.trace_options()):
            w1 = window()
    else:
        w1 = window()
    in_window = compiles.since(mark)
    mem = harness.memory(devices)
    st = window_stats(reqs, w0, w1)
    harness.note("window", window_s=w1 - w0, due=len(st["ttft"]),
                 completed=st["completed"], gaps=len(st["itl"]),
                 steps=len(rec.step_s), joins=len(rec.join_s),
                 queued_at_end=len(state["queue"]),
                 seated_at_end=len(state["seated"]),
                 generator_lag_p99_s=percentile(rec.lag_s, 99),
                 ttft_ms={q: 1e3 * percentile(st["ttft"], q)
                          for q in (50, 90, 99)},
                 itl_ms={q: 1e3 * percentile(st["itl"], q)
                         for q in (50, 90, 95, 99)},
                 **in_window, memory=mem)
    if in_window["compiles"]:
        harness.note("warning", msg="the window compiled",
                     compiles=in_window["compiles"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(m["peak_bytes_in_use"] or 0
                                       for m in mem.values())}
    del sess, bat, state
    gc.collect()

    picked = sample(reqs, seed, limits["sample_requests"])
    gaps = reference_gaps(model, cfg, traffic, key_w, picked, seed,
                          limits["sample_requests"])
    served = int(sum(len(r.tokens) for r in picked))
    read = {"served_logit_gap": float(gaps.max()) if served else math.inf}
    checks = harness.checks_of(read, limits)
    harness.note("check", requests_compared=len(picked),
                 tokens_compared=served,
                 tokens_at_reference_best=int((gaps == 0).sum()))
    result = {"correct": all(c["ok"] for c in checks.values())
              and served >= limits["min_tokens"],
              "attempted": len(st["ttft"]), "failed": 0, "device": device}
    if not trace:
        result["metrics"] = {
            "ttft_p50_ms": {"value": 1e3 * percentile(st["ttft"], 50),
                            "unit": "ms"},
            "itl_p90_ms": {"value": 1e3 * percentile(st["itl"], 90),
                           "unit": "ms"},
            "itl_p99_ms": {"value": 1e3 * percentile(st["itl"], 99),
                           "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return result, checks

    ctx, busy, window_s, breakdown = tr.read_window(
        trace_dir, [d.id for d in devices], WINDOW_SPAN)
    ctx.update(peak=peak, chips=len(devices), record=rec, cfg=cfg,
               traffic=traffic, model=model)
    result["metrics"] = harness.read_per_layer(per_layer, cell["name"], ctx)
    device.update(busy_s=busy, window_s=window_s)
    result["breakdown"] = breakdown
    return result, checks
