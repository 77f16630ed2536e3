"""DeepSeek-V2-Lite (arXiv:2405.04434; the catalog's `config.json`) as
the program configures it on one chip of an expert-parallel group: MLA
without q compression over a latent cache, YaRN rope, a dense SwiGLU
first layer, then MoE layers that route over all `n_routed_experts` and
hold `n_experts` of them (ids `expert_offset` on), plus the shared
experts.  The weight generator, the program model, the plain reference
and the decode step's counts.

The weights are the program's own initialization scheme
(`repro.models.lm.LM.init`), regenerated here from the seed by the
benchmark's own code, one layer at a time: embedding N(0, 0.02^2); every
projection N(0, 1/fan_in) drawn in f32 and cast to the configured dtype,
the router kept in f32; norm gains 1.  Key derivation: the model key
yields (embedding, dense-group, MoE-group, final norm, head) keys by
successive splits; a group key splits into one key per layer; a layer
key into 4 (norm1, attention, norm2, mlp); the attention key into 8
(wq, -, wkv_a, wk_b, wv_b, wo, -, -), the dense mlp key into 3 (gate,
up, down), the MoE key into 5 (router, gate, up, down, shared; the
shared key into 3 as a dense mlp); a projection draws from the first
half of a split of its key, a stacked expert tensor from its key itself.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import split_ref

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
EPS = 1e-6          # rms_norm_eps
# query rows per block of the reference's attention: 8 sequences of
# 8,192 keys over 16 heads are 1.1 GB of f32 scores a block
Q_BLOCK = 256


def dtype(cfg):
    return DTYPES[cfg["dtype"]]


def sizes(cfg) -> dict:
    """The sizes the code below uses, by the catalog's keys."""
    return dict(D=cfg["hidden_size"], H=cfg["num_attention_heads"],
                dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
                dv=cfg["v_head_dim"], r=cfg["kv_lora_rank"],
                F=cfg["moe_intermediate_size"],
                F_dense=cfg["intermediate_size"],
                E=cfg["n_routed_experts"], G=cfg["n_experts"],
                k=cfg["num_experts_per_tok"], S=cfg["n_shared_experts"],
                L=cfg["num_hidden_layers"],
                L_dense=cfg["first_k_dense_replace"], V=cfg["vocab_size"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def model_keys(key) -> tuple:
    """(embedding, dense group, MoE group, final norm, head) keys in the
    order the model draws them: each a fresh subkey off the running key."""
    out = []
    for _ in range(5):
        key, sub = jax.random.split(key)
        out.append(sub)
    return tuple(out)


def layer_keys(key, cfg) -> list:
    """One key per layer, the dense layers first."""
    s = sizes(cfg)
    _, k_dense, k_moe, _, _ = model_keys(key)
    return (list(jax.random.split(k_dense, s["L_dense"]))
            + list(jax.random.split(k_moe, s["L"] - s["L_dense"])))


def _dense(key, fan_in, fan_out, dt):
    kw, _ = jax.random.split(key)
    return ((1.0 / math.sqrt(fan_in))
            * jax.random.normal(kw, (fan_in, fan_out))).astype(dt)


def _swiglu(key, D, F, dt):
    k = jax.random.split(key, 3)
    return {"gate": {"w": _dense(k[0], D, F, dt)},
            "up": {"w": _dense(k[1], D, F, dt)},
            "down": {"w": _dense(k[2], F, D, dt)}}


def layer(key, cfg, moe: bool) -> dict:
    """One layer's parameters in the program's layout."""
    s = sizes(cfg)
    D, H, r = s["D"], s["H"], s["r"]
    dt = dtype(cfg)
    k = jax.random.split(key, 4)
    ka = jax.random.split(k[1], 8)
    p = {"norm1": {"scale": jnp.ones((D,), dt)},
         "mixer": {"wq": {"w": _dense(ka[0], D, H * (s["dn"] + s["dr"]), dt)},
                   "wkv_a": {"w": _dense(ka[2], D, r + s["dr"], dt)},
                   "kv_norm": {"scale": jnp.ones((r,), dt)},
                   "wk_b": {"w": _dense(ka[3], r, H * s["dn"], dt)},
                   "wv_b": {"w": _dense(ka[4], r, H * s["dv"], dt)},
                   "wo": {"w": _dense(ka[5], H * s["dv"], D, dt)}},
         "norm2": {"scale": jnp.ones((D,), dt)}}
    if not moe:
        p["mlp"] = _swiglu(k[3], D, s["F_dense"], dt)
        return p
    km = jax.random.split(k[3], 5)
    G, F = s["G"], s["F"]
    expert = lambda kk, shape, fan_in: (
        (1.0 / math.sqrt(fan_in)) * jax.random.normal(kk, shape)).astype(dt)
    p["mlp"] = {"router": {"w": _dense(km[0], D, s["E"], jnp.float32)},
                "gate": expert(km[1], (G, D, F), D),
                "up": expert(km[2], (G, D, F), D),
                "down": expert(km[3], (G, F, D), F),
                "shared": _swiglu(km[4], D, F * s["S"], dt)}
    return p


def embedding(key, cfg):
    s = sizes(cfg)
    return (0.02 * jax.random.normal(model_keys(key)[0],
                                     (s["V"], s["D"]))).astype(dtype(cfg))


def head(key, cfg):
    s = sizes(cfg)
    return _dense(model_keys(key)[4], s["D"], s["V"], dtype(cfg))


def init_params(key, cfg) -> dict:
    """The whole model in the program's layout (call under jit)."""
    s = sizes(cfg)
    keys = jnp.stack(layer_keys(key, cfg))
    nd = s["L_dense"]
    dense = jax.vmap(lambda k: layer(k, cfg, False))(keys[:nd])
    moe = jax.vmap(lambda k: layer(k, cfg, True))(keys[nd:])
    return {"embed": {"table": embedding(key, cfg)},
            "groups": [{"0": dense}, {"0": moe}],
            "final_norm": {"scale": jnp.ones((s["D"],), dtype(cfg))},
            "head": {"w": head(key, cfg)}}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def program_arch(cfg):
    """The program's ArchConfig for this configuration: its registered
    architecture (`cfg["arch"]`) with the file's sizes."""
    from repro.configs import get_config
    from repro.nn.attention import Yarn
    s = sizes(cfg)
    rs = cfg["rope_scaling"]
    return dataclasses.replace(
        get_config(cfg["arch"]), dtype=dtype(cfg),
        n_layers=s["L"], d_model=s["D"], n_heads=s["H"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=s["dn"] + s["dr"],
        d_ff=s["F"], dense_d_ff=s["F_dense"], vocab=s["V"],
        n_experts=s["E"], top_k=s["k"], n_shared=s["S"],
        first_dense=s["L_dense"], experts_held=s["G"],
        expert_offset=cfg["expert_offset"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        q_lora_rank=cfg["q_lora_rank"] or 0, kv_lora_rank=s["r"],
        qk_nope_head_dim=s["dn"], qk_rope_head_dim=s["dr"],
        v_head_dim=s["dv"], rope_theta=float(cfg["rope_theta"]),
        yarn=Yarn(factor=float(rs["factor"]),
                  original_max=rs["original_max_position_embeddings"],
                  beta_fast=float(rs["beta_fast"]),
                  beta_slow=float(rs["beta_slow"]),
                  mscale=float(rs["mscale"]),
                  mscale_all_dim=float(rs["mscale_all_dim"])),
        tie_embeddings=cfg["tie_word_embeddings"], default_cut=cfg["cut"])


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def identity(a):
    return a


def fp8(a):
    """The control's precision: values rounded to float8 e4m3."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale


def yarn(cfg):
    """(inverse frequencies (dr/2,), cos/sin scale, softmax scale) from the
    published YaRN formula."""
    s, rs = sizes(cfg), cfg["rope_scaling"]
    d, theta, factor = s["dr"], float(cfg["rope_theta"]), rs["factor"]
    L0 = rs["original_max_position_embeddings"]
    m = lambda a: 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0
    corr = lambda b: d * math.log(L0 / (2 * math.pi * b)) \
        / (2 * math.log(theta))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    high = high + 0.001 if high == low else high
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    inv = f * (1.0 - ramp) + f / factor * ramp
    return (inv.astype(np.float32), m(rs["mscale"]) / m(rs["mscale_all_dim"]),
            m(rs["mscale_all_dim"]) ** 2 / math.sqrt(s["dn"] + d))


def rope(x, inv, mscale):
    """Halves rotated as pairs (i, i + d/2) at positions 0..S-1.
    x: (B, S, heads, d)."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * mscale)[None, :, None, :]
    sin = (jnp.sin(ang) * mscale)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mla(p, x, cfg, mm, cast, precision):
    """Causal MLA over the whole sequence with keys and values
    decompressed, queries in blocks of `Q_BLOCK`."""
    s = sizes(cfg)
    B, S, _ = x.shape
    H, dn, dv, r = s["H"], s["dn"], s["dv"], s["r"]
    inv, rope_m, scale = yarn(cfg)
    inv = jnp.asarray(inv)
    q = mm(x, p["wq"]["w"]).reshape(B, S, H, -1)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], inv, rope_m)
    kv = mm(x, p["wkv_a"]["w"])
    c = rmsnorm(kv[..., :r], p["kv_norm"]["scale"])
    k_pe = rope(kv[..., None, r:], inv, rope_m)[:, :, 0]   # (B, S, dr)
    k_nope = mm(c, p["wk_b"]["w"]).reshape(B, S, H, dn)
    v = mm(c, p["wv_b"]["w"]).reshape(B, S, H, dv)
    n = -(-S // Q_BLOCK)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, n * Q_BLOCK - S))
                            + ((0, 0),) * (a.ndim - 2))
    blocks = lambda a: jnp.swapaxes(
        pad(a).reshape(B, n, Q_BLOCK, *a.shape[2:]), 0, 1)

    def one(args):
        i, qn, qp = args
        sc = (jnp.einsum("bshd,bthd->bhst", cast(qn), cast(k_nope),
                         precision=precision)
              + jnp.einsum("bshd,btd->bhst", cast(qp), cast(k_pe),
                           precision=precision)) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        causal = jnp.arange(S)[None, :] <= rows[:, None]
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhst,bthd->bshd", cast(w), cast(v),
                          precision=precision)

    o = jax.lax.map(one, (jnp.arange(n), blocks(q_nope), blocks(q_pe)))
    o = jnp.swapaxes(o, 0, 1).reshape(B, n * Q_BLOCK, H * dv)[:, :S]
    return mm(o, p["wo"]["w"])


def swiglu(p, x, mm):
    return mm(jax.nn.silu(mm(x, p["gate"]["w"])) * mm(x, p["up"]["w"]),
              p["down"]["w"])


def moe(p, x, cfg, mm):
    """The held experts' part of the layer plus the shared experts: for
    each held expert, the SwiGLU of every token times that token's router
    weight for it (zero where it is not among the token's top-k)."""
    s = sizes(cfg)
    probs = jax.nn.softmax(mm(x, p["router"]["w"]), axis=-1)
    w, eid = jax.lax.top_k(probs, s["k"])
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    out = swiglu(p["shared"], x, mm)
    for e in range(s["G"]):
        gate = jnp.where(eid == cfg["expert_offset"] + e, w, 0.0).sum(-1)
        h = jax.nn.silu(mm(x, p["gate"][e])) * mm(x, p["up"][e])
        out = out + gate[..., None] * mm(h, p["down"][e])
    return out


def block(p, x, cfg, precision, cast=identity):
    mm = lambda a, b: jnp.matmul(cast(a), cast(b), precision=precision)
    x = x + mla(p["mixer"], rmsnorm(x, p["norm1"]["scale"]), cfg, mm, cast,
                precision)
    h = rmsnorm(x, p["norm2"]["scale"])
    if "router" in p["mlp"]:
        return x + moe(p["mlp"], h, cfg, mm)
    return x + swiglu(p["mlp"], h, mm)


def served_logits(key, cfg, tokens, positions, precision, cast=identity):
    """Teacher-forced logits (f32) of the served model over the sequences
    `tokens` (B, S), at `positions` (B, A) of each: the client's layers,
    the int8 wire at the cut, the server's layers, the final norm and
    the untied head.  Only the positions up to the last one asked for
    are computed (rounded up to 1,024).  Weights are regenerated layer by
    layer from `key` in f32, so the reference holds one layer at a time;
    `cast` rounds every matmul input (the control)."""
    s = sizes(cfg)
    length = min(tokens.shape[1],
                 -(-(int(np.asarray(positions).max()) + 1) // 1024) * 1024)
    items = tuple(sorted((a, b) for a, b in cfg.items()
                         if not isinstance(b, (list, dict))))
    items += (("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),)
    x = _embed(key, tokens[:, :length], items)
    for i, k in enumerate(layer_keys(key, cfg)):
        if i == cfg["cut"]:
            x = _wire(x)
        x = _layer_fwd(k, x, items, i >= s["L_dense"], precision, cast)
    return _head(key, x, positions, items, precision, cast)


def _cfg(items):
    cfg = dict(items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@jax.jit
def _wire(x):
    return split_ref.q8(x)


@functools.partial(jax.jit, static_argnums=2)
def _embed(key, tokens, items):
    return embedding(key, _cfg(items)).astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head(key, x, positions, items, precision, cast):
    h = jnp.take_along_axis(x, positions[..., None], axis=1)
    h = rmsnorm(h, 1.0)
    return jnp.matmul(cast(h), cast(head(key, _cfg(items)).astype(
        jnp.float32)), precision=precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer_fwd(k, x, items, is_moe, precision, cast):
    cfg = _cfg(items)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                               layer(k, cfg, is_moe))
    return block(p, x, cfg, precision, cast)


# ---------------------------------------------------------------------------
# counts of one decode step (`bench/metrics/mfu.serve_step.mla_moe.py`)
# ---------------------------------------------------------------------------

def param_counts(cfg) -> dict:
    """Parameters by part: the client's layers, one server layer's
    attention, router, shared experts and one routed expert, the head."""
    s = sizes(cfg)
    D, H, r = s["D"], s["H"], s["r"]
    attn = (D * H * (s["dn"] + s["dr"]) + D * (r + s["dr"])
            + r * H * (s["dn"] + s["dv"]) + H * s["dv"] * D)
    return {"attn": attn, "router": D * s["E"], "expert": 3 * D * s["F"],
            "shared": 3 * D * s["F"] * s["S"],
            "dense_mlp": 3 * D * s["F_dense"], "head": D * s["V"]}


def decode_step_cost(cfg, live: int, positions: int) -> tuple:
    """(FLOPs, HBM bytes) one batched decode step needs at `live` tenants
    holding `positions` valid cached positions in all.

    Bytes: the client's layers once, each server layer's attention, router
    and shared experts, the head, every held expert weighted by its chance
    of being hit under uniform routing, 1 - (1 - k/E)^live (an estimate:
    the router is not uniform), and the valid latent rows (c_kv and k_pe)
    of every layer.  FLOPs: 2 per active parameter per live token (a
    token reaches k x G / E held experts on average) plus the absorbed
    attention over the valid positions, 2 x H x (2r + dr) per position
    and layer."""
    s = sizes(cfg)
    c = param_counts(cfg)
    item = 2 if cfg["dtype"] == "bfloat16" else 4
    n_moe = s["L"] - s["L_dense"]
    client = s["L_dense"] * (c["attn"] + c["dense_mlp"])
    server = n_moe * (c["attn"] + c["shared"]) + c["head"]
    hit = 1.0 - (1.0 - s["k"] / s["E"]) ** live
    experts = n_moe * s["G"] * c["expert"]
    latent = s["L"] * (s["r"] + s["dr"]) * item * positions
    nbytes = ((client + server + hit * experts) * item
              + n_moe * c["router"] * 4 + latent)
    active = client + server + n_moe * (c["router"] + s["k"] * s["G"]
                                        / s["E"] * c["expert"])
    flops = (2 * active * live
             + 2 * s["H"] * (2 * s["r"] + s["dr"]) * s["L"] * positions)
    return flops, nbytes
