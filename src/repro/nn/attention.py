"""Attention: GQA and MLA, RoPE variants, sliding windows, KV caches.

Shapes: x is (B, S, D).  Heads layout is (B, S, H, head_dim).
KV caches are (B, max_len, n_kv, head_dim) with a scalar `pos` cursor.

Grouped attention never materializes repeated KV heads — queries are viewed
as (B, S, K, G, hd) and contracted against (B, T, K, hd) directly, which is
the memory-sane layout for 500k-token decode.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.nn import layers as L
from repro.nn import module as nn

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_fraction: float = 1.0        # chatglm: 0.5 ("2d" rope)
    rope_theta: float = 10000.0
    window: int | None = None         # sliding-window size, None = full
    kind: str = "gqa"                 # "gqa" | "mla" | "bidir" | "cross"
    # --- MLA (deepseek-v2) ---
    q_lora_rank: int = 0              # 0 = full-rank q projection
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # split-KV decode: mesh axis the cache length is sharded over (None =
    # single-program GSPMD path).  See gqa_decode_sharded.
    decode_kv_shard: str | None = None
    # KV cache storage: "native" | "int8" (per-(slot,head) symmetric
    # quantization — halves the decode memory floor vs bf16)
    kv_cache_dtype: str = "native"
    dtype: Any = jnp.float32


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, *, theta: float,
               fraction: float = 1.0) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    freqs = rope_freqs(rot, theta)                        # (rot/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------

def causal_mask(q_len: int, kv_len: int, *, window: int | None = None,
                q_offset: int | jnp.ndarray = 0) -> jnp.ndarray:
    """(q_len, kv_len) boolean: True = attend."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    m = k_pos <= q_pos
    if window is not None:
        m = m & (k_pos > q_pos - window)
    return m


# ---------------------------------------------------------------------------
# Core grouped attention
# ---------------------------------------------------------------------------

def grouped_attention(q, k, v, mask, *, scale: float) -> jnp.ndarray:
    """q: (B,S,H,hd) k/v: (B,T,K,hd_k/ hd_v), mask: broadcastable (B,1,1,S,T)
    or (S,T).  Returns (B,S,H,hd_v)."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if mask.ndim == 2:
        mask = mask[None, None, None, :, :]
    else:  # (B, S, T) -> (B,1,1,S,T)
        mask = mask[:, None, None, :, :]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: AttnConfig):
    ks = nn.split_keys(key, 4)
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": L.dense_init(ks[0], D, H * hd, bias=cfg.qkv_bias, dtype=cfg.dtype),
        "wk": L.dense_init(ks[1], D, K * hd, bias=cfg.qkv_bias, dtype=cfg.dtype),
        "wv": L.dense_init(ks[2], D, K * hd, bias=cfg.qkv_bias, dtype=cfg.dtype),
        "wo": L.dense_init(ks[3], H * hd, D, bias=False, dtype=cfg.dtype),
    }


def _qkv(params, cfg: AttnConfig, x):
    B, S, _ = x.shape
    q = L.dense_apply(params["wq"], x).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = L.dense_apply(params["wk"], x).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = L.dense_apply(params["wv"], x).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def gqa_apply(params, cfg: AttnConfig, x, *, positions=None,
              mask=None) -> jnp.ndarray:
    """Full-sequence forward (train / prefill)."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, cfg, x)
    if positions is None:
        positions = jnp.arange(S)
    if cfg.kind != "bidir":
        q = apply_rope(q, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
        k = apply_rope(k, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
    if mask is None:
        if cfg.kind == "bidir":
            mask = jnp.ones((S, S), dtype=bool)
        else:
            mask = causal_mask(S, S, window=cfg.window)
    out = grouped_attention(q, k, v, mask, scale=1.0 / math.sqrt(cfg.head_dim))
    return L.dense_apply(params["wo"], out.reshape(B, S, -1))


def gqa_init_cache(cfg: AttnConfig, batch: int, max_len: int):
    K, hd = cfg.n_kv_heads, cfg.head_dim
    cache_len = min(max_len, cfg.window) if cfg.window else max_len
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, cache_len, K, hd), jnp.int8),
            "v": jnp.zeros((batch, cache_len, K, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, cache_len, K, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, cache_len, K, 1), jnp.float32),
            "pos": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, cache_len, K, hd), cfg.dtype),
        "v": jnp.zeros((batch, cache_len, K, hd), cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def _quant_kv(t):
    """(B, 1, K, hd) -> int8 payload + fp32 per-(slot,head) scale."""
    tf = t.astype(jnp.float32)
    scale = jnp.max(jnp.abs(tf), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(tf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _ring_put(buf, val, slot, per_row: bool):
    """Write the one-token row `val` (B, 1, ...) at ring slot(s) `slot` —
    scalar slot for a uniform batch, (B,) slots when each row sits at its
    own position (multi-tenant decode)."""
    if per_row:
        return buf.at[jnp.arange(buf.shape[0]), slot].set(val[:, 0])
    return jax.lax.dynamic_update_slice(
        buf, val, (0, slot) + (0,) * (buf.ndim - 2))


def _valid_mask(pos, cache_len: int, batch: int, per_row: bool):
    """(B, 1, T) attend-mask over the ring: index < min(pos+1, len)."""
    idx = jnp.arange(cache_len)
    limit = jnp.minimum(pos + 1, cache_len)
    if per_row:
        valid = idx[None, :] < limit[:, None]            # (B, T)
    else:
        valid = (idx < limit)[None, :]                   # (1, T)
    return jnp.broadcast_to(valid[:, None, :], (batch, 1, cache_len))


def gqa_decode(params, cfg: AttnConfig, x, cache, *, qkv=None):
    """One-token decode.  x: (B, 1, D).  Sliding-window caches are ring
    buffers indexed mod window.

    `cache["pos"]` is either the scalar cursor (every row at the same
    position) or a per-row (B,) vector — the serving batcher keeps
    independent tenants at independent positions inside one stacked
    batch; padded slots simply keep advancing their own cursor.

    `qkv` optionally supplies the precomputed flat (q, k, v) projections
    (pre-rope, shapes (B, 1, H*hd)/(B, 1, K*hd)) — the serving engine's
    fused packed-wire entry computes them straight from the int8 payload
    and skips the dense projections here."""
    if cfg.decode_kv_shard is not None:
        return gqa_decode_sharded(params, cfg, x, cache,
                                  seq_axis=cfg.decode_kv_shard)
    B = x.shape[0]
    if qkv is None:
        q, k, v = _qkv(params, cfg, x)
    else:
        q, k, v = qkv
        q = q.reshape(B, 1, cfg.n_heads, cfg.head_dim)
        k = k.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim)
    pos = cache["pos"]
    per_row = jnp.ndim(pos) == 1
    positions = pos[:, None] if per_row else jnp.full((B, 1), pos,
                                                      dtype=jnp.int32)
    q = apply_rope(q, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    k = apply_rope(k, positions, theta=cfg.rope_theta, fraction=cfg.rope_fraction)
    cache_len = cache["k"].shape[1]
    slot = jnp.mod(pos, cache_len)
    int8 = cfg.kv_cache_dtype == "int8"
    if int8:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        new_cache = {
            "k": _ring_put(cache["k"], kq, slot, per_row),
            "v": _ring_put(cache["v"], vq, slot, per_row),
            "k_scale": _ring_put(cache["k_scale"], ks, slot, per_row),
            "v_scale": _ring_put(cache["v_scale"], vs, slot, per_row),
        }
        new_k = _dequant_kv(new_cache["k"], new_cache["k_scale"], k.dtype)
        new_v = _dequant_kv(new_cache["v"], new_cache["v_scale"], v.dtype)
    else:
        new_k = _ring_put(cache["k"], k, slot, per_row)
        new_v = _ring_put(cache["v"], v, slot, per_row)
        new_cache = {"k": new_k, "v": new_v}
    # valid slots: index < min(pos+1, cache_len); ring order is irrelevant to
    # softmax since rope already encoded absolute positions.
    mask = _valid_mask(pos, cache_len, B, per_row)
    out = grouped_attention(q, new_k, new_v, mask,
                            scale=1.0 / math.sqrt(cfg.head_dim))
    y = L.dense_apply(params["wo"], out.reshape(B, 1, -1))
    new_cache["pos"] = pos + 1
    return y, new_cache


def gqa_prefill(params, cfg: AttnConfig, x, cache):
    """Teacher-forced full-sequence forward that POPULATES a fresh decode
    cache in ONE compiled pass — the same attention math as `gqa_apply`,
    plus a scatter of the rope'd K/V rows into the ring slots and
    `pos = S`.  Replaces the O(S) per-token decode_step prefill loop.

    x: (B, S, D); assumes the cache is fresh (pos == 0).  For S beyond a
    sliding-window ring only the last `cache_len` rows are kept (their
    ring slots `p % cache_len` are distinct, so the scatter is exact)."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, cfg, x)
    positions = jnp.arange(S)
    if cfg.kind != "bidir":
        q = apply_rope(q, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
        k = apply_rope(k, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
    mask = causal_mask(S, S, window=cfg.window)
    out = grouped_attention(q, k, v, mask, scale=1.0 / math.sqrt(cfg.head_dim))
    y = L.dense_apply(params["wo"], out.reshape(B, S, -1))

    cache_len = cache["k"].shape[1]
    keep = min(S, cache_len)
    slots = jnp.arange(S - keep, S) % cache_len
    k_keep, v_keep = k[:, S - keep:], v[:, S - keep:]
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quant_kv(k_keep)
        vq, vs = _quant_kv(v_keep)
        new_cache = {
            "k": cache["k"].at[:, slots].set(kq),
            "v": cache["v"].at[:, slots].set(vq),
            "k_scale": cache["k_scale"].at[:, slots].set(ks),
            "v_scale": cache["v_scale"].at[:, slots].set(vs),
        }
    else:
        new_cache = {"k": cache["k"].at[:, slots].set(k_keep),
                     "v": cache["v"].at[:, slots].set(v_keep)}
    new_cache["pos"] = jnp.full_like(cache["pos"], S)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2) — compressed KV cache
# ---------------------------------------------------------------------------

def mla_init(key, cfg: AttnConfig):
    ks = nn.split_keys(key, 8)
    D, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = L.dense_init(ks[0], D, cfg.q_lora_rank, dtype=cfg.dtype)
        p["q_norm"] = L.rmsnorm_init(None, cfg.q_lora_rank, dtype=cfg.dtype)
        p["wq_b"] = L.dense_init(ks[1], cfg.q_lora_rank, H * (dn + dr),
                                 dtype=cfg.dtype)
    else:
        p["wq"] = L.dense_init(ks[0], D, H * (dn + dr), dtype=cfg.dtype)
    p["wkv_a"] = L.dense_init(ks[2], D, r + dr, dtype=cfg.dtype)
    p["kv_norm"] = L.rmsnorm_init(None, r, dtype=cfg.dtype)
    p["wk_b"] = L.dense_init(ks[3], r, H * dn, dtype=cfg.dtype)
    p["wv_b"] = L.dense_init(ks[4], r, H * dv, dtype=cfg.dtype)
    p["wo"] = L.dense_init(ks[5], H * dv, D, dtype=cfg.dtype)
    return p


def _mla_q(params, cfg: AttnConfig, x):
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        q = L.dense_apply(params["wq_a"], x)
        q = L.rmsnorm_apply(params["q_norm"], q)
        q = L.dense_apply(params["wq_b"], q)
    else:
        q = L.dense_apply(params["wq"], x)
    q = q.reshape(B, S, H, dn + dr)
    return q[..., :dn], q[..., dn:]                      # nope, rope parts


def mla_apply(params, cfg: AttnConfig, x, *, positions=None, mask=None):
    """Prefill/train: decompress k,v and run standard MHA."""
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    if positions is None:
        positions = jnp.arange(S)
    q_nope, q_pe = _mla_q(params, cfg, x)
    q_pe = apply_rope(q_pe, positions, theta=cfg.rope_theta)
    kv = L.dense_apply(params["wkv_a"], x)               # (B,S,r+dr)
    c_kv, k_pe = kv[..., :r], kv[..., r:]
    c_kv = L.rmsnorm_apply(params["kv_norm"], c_kv)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, theta=cfg.rope_theta)
    k_nope = L.dense_apply(params["wk_b"], c_kv).reshape(B, S, H, dn)
    v = L.dense_apply(params["wv_b"], c_kv).reshape(B, S, H, dv)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, dr))], axis=-1)
    if mask is None:
        mask = causal_mask(S, S, window=cfg.window)
    out = grouped_attention(q, k, v, mask, scale=1.0 / math.sqrt(dn + dr))
    return L.dense_apply(params["wo"], out.reshape(B, S, -1))


def mla_init_cache(cfg: AttnConfig, batch: int, max_len: int):
    cache_len = min(max_len, cfg.window) if cfg.window else max_len
    return {
        "c_kv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), cfg.dtype),
        "k_pe": jnp.zeros((batch, cache_len, cfg.qk_rope_head_dim), cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def mla_decode(params, cfg: AttnConfig, x, cache):
    """Absorbed-weight decode: scores computed against the *compressed*
    cache c_kv directly — O(len * kv_lora_rank) per head, never
    materializing per-token k/v.  This is the TPU-native MLA decode.

    As in `gqa_decode`, `cache["pos"]` may be scalar or per-row (B,)."""
    B = x.shape[0]
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    pos = cache["pos"]
    per_row = jnp.ndim(pos) == 1
    positions = pos[:, None] if per_row else jnp.full((B, 1), pos,
                                                      dtype=jnp.int32)

    q_nope, q_pe = _mla_q(params, cfg, x)                # (B,1,H,dn),(B,1,H,dr)
    q_pe = apply_rope(q_pe, positions, theta=cfg.rope_theta)

    kv = L.dense_apply(params["wkv_a"], x)
    c_new, kpe_new = kv[..., :r], kv[..., r:]
    c_new = L.rmsnorm_apply(params["kv_norm"], c_new)
    kpe_new = apply_rope(kpe_new[:, :, None, :], positions,
                         theta=cfg.rope_theta)[:, :, 0, :]

    cache_len = cache["c_kv"].shape[1]
    slot = jnp.mod(pos, cache_len)
    c_kv = _ring_put(cache["c_kv"], c_new, slot, per_row)
    k_pe = _ring_put(cache["k_pe"], kpe_new, slot, per_row)

    # absorb wk_b into q: q_eff[b,h,r'] = sum_dn q_nope * wk_b[r', h, dn]
    wk_b = params["wk_b"]["w"].reshape(r, H, dn)
    q_eff = jnp.einsum("bshd,rhd->bshr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))         # (B,1,H,r)
    scores = jnp.einsum("bshr,btr->bhst", q_eff,
                        c_kv.astype(jnp.float32))
    scores = scores + jnp.einsum("bshd,btd->bhst", q_pe.astype(jnp.float32),
                                 k_pe.astype(jnp.float32))
    scores = scores / math.sqrt(dn + dr)
    valid = _valid_mask(pos, cache_len, B, per_row)      # (B,1,T)
    scores = jnp.where(valid[:, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)                  # (B,H,1,T)
    ctx = jnp.einsum("bhst,btr->bshr", w, c_kv.astype(jnp.float32))  # (B,1,H,r)
    wv_b = params["wv_b"]["w"].reshape(r, H, dv)
    out = jnp.einsum("bshr,rhd->bshd", ctx, wv_b.astype(jnp.float32))
    y = L.dense_apply(params["wo"], out.reshape(B, 1, H * dv).astype(x.dtype))
    return y, {"c_kv": c_kv, "k_pe": k_pe, "pos": pos + 1}


def mla_prefill(params, cfg: AttnConfig, x, cache):
    """Full-sequence MLA forward (same math as `mla_apply`) that also
    scatters the COMPRESSED rows — post-norm c_kv and rope'd k_pe, exactly
    what `mla_decode` stores — into a fresh cache, leaving pos = S."""
    B, S, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    positions = jnp.arange(S)
    q_nope, q_pe = _mla_q(params, cfg, x)
    q_pe = apply_rope(q_pe, positions, theta=cfg.rope_theta)
    kv = L.dense_apply(params["wkv_a"], x)               # (B,S,r+dr)
    c_kv, k_pe = kv[..., :r], kv[..., r:]
    c_kv = L.rmsnorm_apply(params["kv_norm"], c_kv)
    k_pe = apply_rope(k_pe[:, :, None, :], positions,
                      theta=cfg.rope_theta)[:, :, 0, :]  # (B,S,dr)
    k_nope = L.dense_apply(params["wk_b"], c_kv).reshape(B, S, H, dn)
    v = L.dense_apply(params["wv_b"], c_kv).reshape(B, S, H, dv)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, dr))],
        axis=-1)
    mask = causal_mask(S, S, window=cfg.window)
    out = grouped_attention(q, k, v, mask, scale=1.0 / math.sqrt(dn + dr))
    y = L.dense_apply(params["wo"], out.reshape(B, S, -1))

    cache_len = cache["c_kv"].shape[1]
    keep = min(S, cache_len)
    slots = jnp.arange(S - keep, S) % cache_len
    new_cache = {
        "c_kv": cache["c_kv"].at[:, slots].set(c_kv[:, S - keep:]),
        "k_pe": cache["k_pe"].at[:, slots].set(k_pe[:, S - keep:]),
        "pos": jnp.full_like(cache["pos"], S),
    }
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder -> encoder states)
# ---------------------------------------------------------------------------

def cross_attn_apply(params, cfg: AttnConfig, x, enc_kv):
    """enc_kv: dict with precomputed k, v from encoder output (B,T,K,hd)."""
    B, S, _ = x.shape
    q = L.dense_apply(params["wq"], x).reshape(B, S, cfg.n_heads, cfg.head_dim)
    T = enc_kv["k"].shape[1]
    mask = jnp.ones((S, T), dtype=bool)
    out = grouped_attention(q, enc_kv["k"], enc_kv["v"], mask,
                            scale=1.0 / math.sqrt(cfg.head_dim))
    return L.dense_apply(params["wo"], out.reshape(B, S, -1))


def cross_attn_kv(params, cfg: AttnConfig, enc_out):
    B, T, _ = enc_out.shape
    k = L.dense_apply(params["wk"], enc_out).reshape(B, T, cfg.n_kv_heads,
                                                     cfg.head_dim)
    v = L.dense_apply(params["wv"], enc_out).reshape(B, T, cfg.n_kv_heads,
                                                     cfg.head_dim)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Split-KV sharded decode (§Perf optimization, beyond-GSPMD)
# ---------------------------------------------------------------------------

def gqa_decode_sharded(params, cfg: AttnConfig, x, cache, *, seq_axis):
    """One-token decode with the KV cache SEQUENCE-sharded over `seq_axis`
    (flash-decode / split-KV, expressed with shard_map).

    GSPMD's lowering of `dynamic_update_slice` + attention over a
    length-sharded ring cache all-gathers the whole cache every step
    (measured 5.4 GB/layer/step for qwen1.5-32B decode_32k).  Here each
    shard keeps its length chunk resident, updates the one slot it owns,
    computes a partial online-softmax, and the shards combine with three
    tiny psums (running-max, normalizer, weighted values) — O(B·H·hd)
    bytes instead of O(B·L·K·hd).

    Head-count divisibility is NOT required: projections are gathered on
    the flat feature dim and reshaped to heads afterwards.
    """
    from jax.sharding import PartitionSpec as P
    from repro.nn import dist as _dist

    mesh = _dist.get_mesh()
    dp = _dist.batch_axes(mesh) or None
    ax = seq_axis
    n_shards = mesh.shape[ax]
    B = x.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    cache_len = cache["k"].shape[1]
    assert cache_len % n_shards == 0
    L_l = cache_len // n_shards
    has_bias = "b" in params["wq"]

    def body(xl, wq, wk, wv, wo, kc, vc, pos):
        # xl (B_l,1,D); wq (D, Hhd/n); kc/vc (B_l, L_l, K, hd); pos ()
        s = jax.lax.axis_index(ax)
        q_l = xl @ wq["w"] + (wq["b"] if has_bias else 0.0)
        k_l = xl @ wk["w"] + (wk["b"] if has_bias else 0.0)
        v_l = xl @ wv["w"] + (wv["b"] if has_bias else 0.0)
        # gather flat feature dims -> full heads (tiny: B*H*hd bytes)
        q = jax.lax.all_gather(q_l, ax, axis=2, tiled=True)
        k = jax.lax.all_gather(k_l, ax, axis=2, tiled=True)
        v = jax.lax.all_gather(v_l, ax, axis=2, tiled=True)
        Bl = q.shape[0]
        q = q.reshape(Bl, 1, H, hd)
        k = k.reshape(Bl, 1, K, hd)
        v = v.reshape(Bl, 1, K, hd)
        positions = jnp.full((Bl, 1), pos, dtype=jnp.int32)
        q = apply_rope(q, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
        k = apply_rope(k, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)

        # ring slot -> local update only on the owning shard
        slot = jnp.mod(pos, cache_len)
        local_slot = slot - s * L_l
        in_range = (local_slot >= 0) & (local_slot < L_l)
        safe = jnp.clip(local_slot, 0, L_l - 1)
        kc_new = jax.lax.dynamic_update_slice(kc, k, (0, safe, 0, 0))
        vc_new = jax.lax.dynamic_update_slice(vc, v, (0, safe, 0, 0))
        kc = jnp.where(in_range, kc_new, kc)
        vc = jnp.where(in_range, vc_new, vc)

        # local partial attention over my length chunk
        qg = q.reshape(Bl, 1, K, G, hd)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                            kc.astype(jnp.float32)) / (hd ** 0.5)
        gidx = s * L_l + jnp.arange(L_l)
        valid = gidx < jnp.minimum(pos + 1, cache_len)
        scores = jnp.where(valid[None, None, None, None, :], scores,
                           NEG_INF)
        m_l = scores.max(axis=-1, keepdims=True)          # (B,K,G,1,1)
        m = jax.lax.pmax(m_l, ax)
        p = jnp.exp(scores - m)
        l_l = p.sum(axis=-1, keepdims=True)
        o_l = jnp.einsum("bkgst,btkd->bskgd", p, vc.astype(jnp.float32))
        lsum = jax.lax.psum(l_l, ax)                      # tiny
        osum = jax.lax.psum(o_l, ax)                      # B*H*hd fp32
        out = osum / jnp.maximum(
            lsum.reshape(Bl, 1, K, G, 1), 1e-30)
        out = out.reshape(Bl, 1, H * hd).astype(xl.dtype)

        # row-parallel output projection: my slice of heads x my wo rows
        width = H * hd // n_shards
        my = jax.lax.dynamic_slice_in_dim(out, s * width, width, axis=2)
        y_l = my @ wo["w"]                                 # (B_l,1,D)
        y = jax.lax.psum(y_l, ax)
        return y, kc, vc

    wspec = {"w": P(None, ax)}
    if has_bias:
        wspec = {"w": P(None, ax), "b": P(ax)}
    y, new_k, new_v = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), wspec, wspec, wspec,
                  {"w": P(ax, None)},
                  P(dp, ax, None, None), P(dp, ax, None, None), P()),
        out_specs=(P(dp, None, None), P(dp, ax, None, None),
                   P(dp, ax, None, None)))(
        x, params["wq"], params["wk"], params["wv"],
        {"w": params["wo"]["w"]}, cache["k"], cache["v"], cache["pos"])
    return y, {"k": new_k, "v": new_v, "pos": cache["pos"] + 1}
