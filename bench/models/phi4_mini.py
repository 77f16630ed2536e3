"""Phi-4-mini (arXiv:2412.08905) as the program configures it: GQA with
RoPE on the whole head (`rope_fraction` 1.0), SwiGLU, RMSNorm (eps
`norm_eps`, unit gain at init), the head tied to the embedding.  The
weight generator, the program model, and the plain reference.

The weights are the program's own initialization scheme
(`repro.models.lm.LM.init`), regenerated here from the seed by the
benchmark's own code, one layer at a time, so that the reference needs
neither the program nor memory for the whole model: embedding N(0,
0.02^2); every projection N(0, 1/fan_in) drawn in f32 and cast to the
configured dtype; norm gains 1.  Key derivation: the model key yields
(embedding, layer-group) keys by successive splits; the group key splits
into one key per layer; a layer key into 4 (norm1, attention, norm2,
mlp); the attention key into 4 (wq, wk, wv, wo) and the mlp key into 3
(gate, up, down); each projection draws from the first half of a split
of its key.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench.lib import split_ref

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def dtype(cfg):
    return DTYPES[cfg["dtype"]]


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def model_keys(key) -> tuple:
    """(embedding key, layer-group key) in the order the model draws
    them: each a fresh subkey split off the running key."""
    key, k_embed = jax.random.split(key)
    key, k_group = jax.random.split(key)
    return k_embed, k_group


def layer_keys(key, cfg):
    """(n_layers, 2) keys, one per layer."""
    return jax.random.split(model_keys(key)[1], cfg["n_layers"])


def embedding(key, cfg):
    k_embed, _ = model_keys(key)
    table = 0.02 * jax.random.normal(k_embed, (cfg["vocab"], cfg["d_model"]))
    return table.astype(dtype(cfg))


def _dense(key, fan_in, fan_out, dt):
    kw, _ = jax.random.split(key)
    return ((1.0 / math.sqrt(fan_in))
            * jax.random.normal(kw, (fan_in, fan_out))).astype(dt)


def layer(key, cfg) -> dict:
    """One layer's parameters in the program's layout."""
    D, F = cfg["d_model"], cfg["d_ff"]
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    dt = dtype(cfg)
    k = jax.random.split(key, 4)
    ka = jax.random.split(k[1], 4)
    km = jax.random.split(k[3], 3)
    return {
        "norm1": {"scale": jnp.ones((D,), dt)},
        "mixer": {"wq": {"w": _dense(ka[0], D, H * hd, dt)},
                  "wk": {"w": _dense(ka[1], D, K * hd, dt)},
                  "wv": {"w": _dense(ka[2], D, K * hd, dt)},
                  "wo": {"w": _dense(ka[3], H * hd, D, dt)}},
        "norm2": {"scale": jnp.ones((D,), dt)},
        "mlp": {"gate": {"w": _dense(km[0], D, F, dt)},
                "up": {"w": _dense(km[1], D, F, dt)},
                "down": {"w": _dense(km[2], F, D, dt)}},
    }


def init_params(key, cfg) -> dict:
    """The whole model in the program's layout (call under jit)."""
    layers = jax.vmap(lambda k: layer(k, cfg))(layer_keys(key, cfg))
    return {"embed": {"table": embedding(key, cfg)},
            "groups": [{"0": layers}],
            "final_norm": {"scale": jnp.ones((cfg["d_model"],),
                                             dtype(cfg))}}


def split(params, cfg):
    """Client: embedding and layers [0, cut); server: the rest, the
    final norm and its own copy of the tied output table."""
    cut = cfg["cut"]
    g = params["groups"][0]["0"]
    head = lambda a, lo, hi: jax.tree_util.tree_map(lambda x: x[lo:hi], a)
    client = {"embed": params["embed"],
              "groups": [{"0": head(g, 0, cut)}]}
    server = {"final_norm": params["final_norm"],
              "tied_head": params["embed"],
              "groups": [{"0": head(g, cut, cfg["n_layers"])}]}
    return client, server


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

ARCH_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab", "rope_theta", "rope_fraction",
             "tie_embeddings")


def program_arch(cfg):
    """The program's ArchConfig for this configuration: its registered
    architecture (`cfg["arch"]`) with the file's sizes."""
    from repro.configs import get_config
    base = get_config(cfg["arch"])
    return dataclasses.replace(base, dtype=dtype(cfg),
                               **{k: cfg[k] for k in ARCH_KEYS})


def program_model(cfg, init):
    """`repro.api.SplitFns` over the program's LM, with the benchmark's
    weight generator as its init."""
    from repro.api import SplitFns
    from repro.models import build_model
    model = build_model(program_arch(cfg))
    cut = cfg["cut"]
    return SplitFns(
        init=init, split=lambda p: model.split_params(p, cut),
        client_apply=lambda pc, b: model.apply_client(pc, b, cut),
        server_apply=lambda ps, a: model.apply_server(ps, a, cut))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    """Rotary embedding over the whole head, halves rotated as pairs
    (i, i + hd/2).  x: (B, S, heads, hd)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def whole_head_rope(cfg):
    if cfg["rope_fraction"] != 1.0:
        raise ValueError("the reference rotates the whole head; "
                         f"rope_fraction {cfg['rope_fraction']} is not "
                         "implemented")


def identity(a):
    return a


def block(p, x, cfg, precision, cast=identity):
    """One decoder layer over a full causal sequence.  `cast` rounds
    every matmul input (the control's lower precision)."""
    B, S, D = x.shape
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    mm = lambda a, w: jnp.matmul(cast(a), cast(w), precision=precision)
    h = rmsnorm(x, p["norm1"]["scale"], cfg["norm_eps"])
    m = p["mixer"]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q = rope(mm(h, m["wq"]["w"]).reshape(B, S, H, hd), pos, cfg["rope_theta"])
    k = rope(mm(h, m["wk"]["w"]).reshape(B, S, K, hd), pos, cfg["rope_theta"])
    v = mm(h, m["wv"]["w"]).reshape(B, S, K, hd)
    q = q.reshape(B, S, K, H // K, hd)
    s = jnp.einsum("bskgd,btkd->bkgst", cast(q), cast(k),
                   precision=precision) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s.astype(jnp.float32), -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", cast(w), cast(v),
                   precision=precision).reshape(B, S, H * hd)
    x = x + mm(o, m["wo"]["w"])
    h = rmsnorm(x, p["norm2"]["scale"], cfg["norm_eps"])
    f = p["mlp"]
    return x + mm(jax.nn.silu(mm(h, f["gate"]["w"])) * mm(h, f["up"]["w"]),
                  f["down"]["w"])


def reference(cfg, control: bool = False):
    """(client_fwd, server_loss, parameter dtype) for split training, in
    plain jnp with f32 weights at `highest` precision; for the control,
    every matmul input rounded to float8 e4m3 (the configuration states
    bf16).  Each side scans its stacked layers."""
    whole_head_rope(cfg)
    precision = jax.lax.Precision.HIGHEST
    cast = fp8 if control else identity

    def run(h, layers):
        return jax.lax.scan(
            lambda x, lp: (block(lp, x, cfg, precision, cast), None),
            h, layers)[0]

    def client_fwd(pc, batch):
        return run(pc["embed"]["table"][batch["tokens"]],
                   pc["groups"][0]["0"])

    def server_loss(ps, act, batch):
        x = rmsnorm(run(act, ps["groups"][0]["0"]),
                    ps["final_norm"]["scale"], cfg["norm_eps"])
        logits = jnp.matmul(cast(x), cast(ps["tied_head"]["table"]).T,
                            precision=precision)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(lp, batch["labels"][..., None],
                                    -1).mean()

    return client_fwd, server_loss, jnp.float32


def served_logits(key, cfg, tokens, positions, precision, cast=identity):
    """Teacher-forced logits (f32) of the served model over the
    sequences `tokens` (B, S), at `positions` (B, A) of each: the
    client's layers, the int8 wire at the cut, the server's layers, the
    final norm and the tied head.  Weights are regenerated layer by
    layer from `key` in f32, so the reference holds one layer at a
    time; `cast` rounds every matmul input (the control)."""
    whole_head_rope(cfg)
    items = tuple(sorted((a, b) for a, b in cfg.items()
                         if isinstance(b, (int, float, str, bool))))
    x = _embed(key, tokens, items)
    keys = layer_keys(key, cfg)
    for i in range(cfg["n_layers"]):
        if i == cfg["cut"]:
            x = _wire(x)
        x = _layer_fwd(keys[i], x, items, precision, cast)
    return _head(key, x, positions, items, precision, cast)


@jax.jit
def _wire(x):
    return split_ref.q8(x)


@functools.partial(jax.jit, static_argnums=2)
def _embed(key, tokens, items):
    return embedding(key, dict(items)).astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head(key, x, positions, items, precision, cast):
    table = embedding(key, dict(items)).astype(jnp.float32)
    h = jnp.take_along_axis(x, positions[..., None], axis=1)
    h = rmsnorm(h, jnp.ones((h.shape[-1],), jnp.float32),
                dict(items)["norm_eps"])
    return jnp.matmul(cast(h), cast(table).T, precision=precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_fwd(k, x, items, precision, cast):
    cfg = dict(items)
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer(k, cfg))
    return block(p, x, cfg, precision, cast)


def fp8(a):
    """The control's precision: values rounded to float8 e4m3."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


# ---------------------------------------------------------------------------
# training inputs and counts
# ---------------------------------------------------------------------------

def make_inputs(key, cfg, traffic, shape_lead: tuple, dtype=None) -> dict:
    """{"tokens", "labels"}: (*lead, seq_len) ids uniform over the
    vocabulary, each label the next id of one (seq_len + 1) draw."""
    ids = jax.random.randint(key, shape_lead + (traffic["seq_len"] + 1,),
                             0, cfg["vocab"])
    return {"tokens": ids[..., :-1], "labels": ids[..., 1:]}


def fwd_flops_per_sample(cfg, traffic) -> int:
    """Forward FLOPs of one sequence: 2 x the matmul weights (layers and
    the tied head; the embedding lookup is free) per token, plus causal
    attention, QK^T and AV over the S(S+1)/2 visible pairs."""
    D, F, L, S = cfg["d_model"], cfg["d_ff"], cfg["n_layers"], \
        traffic["seq_len"]
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    weights = L * (2 * D * H * hd + 2 * D * K * hd + 3 * D * F) \
        + cfg["vocab"] * D
    return 2 * weights * S + 2 * 2 * L * H * hd * (S * (S + 1) // 2)


def cut_shape(cfg, traffic) -> tuple:
    """Shape of one client's cut activation."""
    return (traffic["per_client"], traffic["seq_len"], cfg["d_model"])
