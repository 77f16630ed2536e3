"""Serving driver: compiled prefill-then-decode, monolithic or split.

Monolithic: ONE teacher-forced `model.prefill` populates the caches
(replacing the old O(prompt_len) decode_step loop), then greedy decode
runs as ONE `lax.scan` dispatch (`serve.greedy_decode_scan`).

Split (`--split`): the paper's client/server cut at inference time via
`serve.ServeSession` — `--wire quantize_int8:physical` ships the packed
int8 payload on the client->server hop and the quantized logits back,
and the summary reports the metered wire bytes per generated token.

Timings exclude compilation: every phase runs once for warmup and is
`block_until_ready`-fenced before the timestamps.  `--loop` times the
per-token Python-loop decode instead of the scan (the benchmark
baseline the scan is gated against).

Example:
    PYTHONPATH=src python -m repro.launch.serve \
        --arch mamba2_130m --reduced --batch 4 --prompt-len 32 --gen 32
    PYTHONPATH=src python -m repro.launch.serve \
        --arch phi4_mini_3_8b --reduced --split --cut 1 \
        --wire quantize_int8:physical
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.models.registry import supports_split_serving
from repro.serve import ServePlan, ServeSession, greedy_decode_scan


def greedy_decode_loop(model, params, cache, first_token, steps: int):
    """Per-token Python loop (one jitted dispatch per token) — kept as
    the benchmark baseline for the scan-based decode."""
    @jax.jit
    def step(tok, cache):
        logits, cache = model.decode_step(params, tok, cache)
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        return nxt, cache

    toks = []
    tok = first_token
    for _ in range(steps):
        tok, cache = step(tok, cache)
        toks.append(tok)
    return jnp.concatenate(toks, axis=1), cache


def serve_monolithic(model, cfg, params, prompt, gen: int, max_len: int,
                     key, *, loop: bool = False) -> dict:
    """Compiled prefill (ONE teacher-forced forward, cache init fused
    in) + greedy decode; every phase warmed up and fenced so the
    timings exclude compilation."""
    audio = (0.02 * jax.random.normal(
        key, (prompt.shape[0], cfg.n_audio_frames, cfg.d_model), cfg.dtype)
        if cfg.encdec else None)

    @jax.jit
    def prefill_jit(params, prompt, audio):
        if cfg.encdec:
            cache = model.init_cache(params, audio, max_len)
            logits, cache = model.prefill(params, prompt, cache)
        else:
            cache = model.init_cache(prompt.shape[0], max_len)
            logits, cache = model.prefill(params, {"tokens": prompt}, cache)
        return jnp.argmax(logits[:, -1], axis=-1)[:, None], cache

    if loop:
        def decode(params, cache, tok0):
            return greedy_decode_loop(model, params, cache, tok0, gen - 1)
    else:
        decode = jax.jit(lambda params, cache, tok0: greedy_decode_scan(
            model, params, cache, tok0, gen - 1))

    # warmup: compile prefill + decode off the clock
    tok0, cache = prefill_jit(params, prompt, audio)
    out, _ = decode(params, cache, tok0)
    jax.block_until_ready(out)

    t0 = time.time()
    tok0, cache = prefill_jit(params, prompt, audio)
    jax.block_until_ready(tok0)
    t_prefill = time.time() - t0

    t0 = time.time()
    rest, _ = decode(params, cache, tok0)
    jax.block_until_ready(rest)
    t_decode = time.time() - t0

    out = jnp.concatenate([tok0, rest], axis=1)
    B = prompt.shape[0]
    return {
        "mode": "monolithic" + ("_loop" if loop else ""),
        "prefill_s": round(t_prefill, 4), "decode_s": round(t_decode, 4),
        "decode_tok_per_s": round(B * gen / max(t_decode, 1e-9), 1),
        "sample_tokens": out[0, :10].tolist(),
    }


def serve_split(sess: ServeSession, prompt, gen: int) -> dict:
    # warmup: compile prefill + scan decode off the clock
    jax.block_until_ready(sess.generate(prompt, gen))

    t0 = time.time()
    tok0 = sess.prefill(prompt)
    jax.block_until_ready(tok0)
    t_prefill = time.time() - t0

    t0 = time.time()
    rest = sess.decode(tok0, gen - 1)
    jax.block_until_ready(rest)
    t_decode = time.time() - t0

    out = jnp.concatenate([tok0, rest], axis=1)
    B = prompt.shape[0]
    cost = sess.decode_cost(batch=B)
    return {
        "mode": "split", "cut": sess.cut,
        "wire": sess.plan.wire or "fp32",
        "prefill_s": round(t_prefill, 4), "decode_s": round(t_decode, 4),
        "decode_tok_per_s": round(B * gen / max(t_decode, 1e-9), 1),
        "wire_bytes_per_token": round((cost.bytes_up + cost.bytes_down) / B),
        "sample_tokens": out[0, :10].tolist(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--split", action="store_true",
                    help="serve across the client/server cut")
    ap.add_argument("--cut", type=int, default=-1)
    ap.add_argument("--wire", default="",
                    help="cut middleware (split mode), e.g. "
                         "quantize_int8:physical")
    ap.add_argument("--loop", action="store_true",
                    help="per-token Python-loop decode (bench baseline)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(vocab=256)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    B = args.batch
    max_len = args.prompt_len + args.gen + 1
    prompt = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab)

    if args.split:
        ok, why = supports_split_serving(cfg)
        if not ok:
            raise SystemExit(f"--split: {cfg.name}: {why}")
        plan = ServePlan(arch=cfg, cut=args.cut if args.cut >= 0 else None,
                         wire=args.wire, max_batch=B, max_len=max_len)
        try:
            sess = ServeSession(plan, model.init(key))
        except ValueError as e:
            raise SystemExit(str(e))
        summary = serve_split(sess, prompt, args.gen)
    else:
        params = model.init(key)
        summary = serve_monolithic(model, cfg, params, prompt, args.gen,
                                   max_len, key, loop=args.loop)

    summary = {"arch": cfg.name, "batch": B, "prompt_len": args.prompt_len,
               "generated": args.gen, **summary}
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
