"""A plain float32 reference of DeepSeek-V2 (MLA with YaRN rope, a dense
first layer, then MoE layers with shared experts), for the tests.

Written from the published description (arXiv:2405.04434 and the model's
`config.json`), in straightforward `jax.numpy` at `highest` matmul
precision, with no kernel, cache, sort, capacity or batching: keys and
values are decompressed (not absorbed), each MoE layer is a loop over its
held experts (mask x gate x SwiGLU of every token), RMSNorm at eps 1e-6,
an untied head.  It imports nothing from the program; it reads the
program's parameter layout, so that both run on the same seeded weights.

One departure from the published code, shared with the program: the
rotary embedding rotates the halves of a head, (i, i + d/2), where
DeepSeek's code rotates interleaved pairs (2i, 2i + 1) -- a fixed
permutation of the rope columns of `wq` and `wkv_a`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def yarn_inv_freq(d: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """(d/2,) YaRN inverse frequencies in float64, from the formula:
    f_i = theta^(-2i/d); corr(b) = d ln(L0 / (2 pi b)) / (2 ln theta);
    low = max(floor(corr(beta_fast)), 0), high = min(ceil(corr(beta_slow)),
    d - 1); ramp_i = clip((i - low) / (high - low), 0, 1);
    inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i."""
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    corr = lambda b: d * math.log(original_max / (2 * math.pi * b)) \
        / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), d - 1)
    if high == low:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + f / factor * ramp


def yarn_m(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale


def swiglu(p, x):
    return (jax.nn.silu(x @ p["gate"]["w"]) * (x @ p["up"]["w"])) \
        @ p["down"]["w"]


def rope(x, positions, inv_freq, mscale):
    """x: (B, S, heads, d), halves rotated; positions (S,)."""
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos = (jnp.cos(ang) * mscale)[None, :, None, :]
    sin = (jnp.sin(ang) * mscale)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla(p, cfg, x):
    """Causal MLA over a whole sequence, keys and values decompressed."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    y = cfg.yarn
    if y is None:
        inv = 1.0 / cfg.rope_theta ** (np.arange(0, dr, 2) / dr)
        rope_m, scale = 1.0, 1.0 / math.sqrt(dn + dr)
    else:
        inv = yarn_inv_freq(dr, cfg.rope_theta, y.factor, y.original_max,
                            y.beta_fast, y.beta_slow)
        rope_m = yarn_m(y.factor, y.mscale) / yarn_m(y.factor,
                                                     y.mscale_all_dim)
        scale = yarn_m(y.factor, y.mscale_all_dim) ** 2 / math.sqrt(dn + dr)
    inv = jnp.asarray(inv, jnp.float32)
    pos = jnp.arange(S)
    q = (x @ p["wq"]["w"]).reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], pos, inv, rope_m)
    kv = x @ p["wkv_a"]["w"]
    c = rmsnorm(kv[..., :r], p["kv_norm"]["scale"])
    k_pe = rope(kv[..., None, r:], pos, inv, rope_m)      # (B, S, 1, dr)
    k_nope = (c @ p["wk_b"]["w"]).reshape(B, S, H, dn)
    v = (c @ p["wv_b"]["w"]).reshape(B, S, H, dv)
    s = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
         + jnp.einsum("bshd,btd->bhst", q_pe, k_pe[:, :, 0])) * scale
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", w, v).reshape(B, S, H * dv)
    return o @ p["wo"]["w"]


def routed(p, cfg, x, offset: int):
    """The held experts' part of an MoE layer: for each held expert, the
    SwiGLU of every token times that token's router weight for it (zero
    where the expert is not among the token's top-k)."""
    probs = jax.nn.softmax(x @ p["router"]["w"], axis=-1)
    w, eid = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / w.sum(-1, keepdims=True)
    w = w * cfg.routed_scaling_factor
    out = jnp.zeros_like(x)
    for e in range(p["gate"].shape[0]):
        gate = jnp.where(eid == offset + e, w, 0.0).sum(-1)   # (B, S)
        h = jax.nn.silu(x @ p["gate"][e]) * (x @ p["up"][e])
        out = out + gate[..., None] * (h @ p["down"][e])
    return out


def moe(p, cfg, x, offset: int):
    out = routed(p, cfg, x, offset)
    if cfg.n_shared:
        out = out + swiglu(p["shared"], x)
    return out


def layer(p, cfg, x, offset: int):
    x = x + mla(p["mixer"], cfg, rmsnorm(x, p["norm1"]["scale"]))
    h = rmsnorm(x, p["norm2"]["scale"])
    if "router" in p["mlp"]:
        return x + moe(p["mlp"], cfg, h, offset)
    return x + swiglu(p["mlp"], h)


def f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  tree)


def forward(params, cfg, tokens, wire=None):
    """Logits (B, S, V) in f32 of the whole model over `tokens` (B, S);
    `wire`, if given, is applied to the activation after the first
    `cfg.default_cut` layers (the cut)."""
    params = f32(params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][tokens]
        i = 0
        for group in params["groups"]:
            stacked = group["0"]
            for j in range(jax.tree_util.tree_leaves(stacked)[0].shape[0]):
                if wire is not None and i == cfg.default_cut:
                    x = wire(x)
                p = jax.tree_util.tree_map(lambda a: a[j], stacked)
                x = layer(p, cfg, x, cfg.expert_offset)
                i += 1
        x = rmsnorm(x, params["final_norm"]["scale"])
        return x @ params["head"]["w"]
