"""Mixture-of-Experts: a dropless top-k router over held experts.

A layer routes every token over all `n_experts` router outputs, but its
params hold only the experts [offset, offset + held): on one chip of an
expert-parallel group the chip's share, on a single program all of them
(`held` 0, offset 0).  `held_experts` computes the held experts' part
of the result, and drops no assignment:

  1. softmax over the router's `n_experts` outputs in f32, top-k     (T, k)
  2. weights renormalized over the k if `norm_topk_prob`, then
     times `routed_scaling_factor`
  3. assignments sorted by held expert, the others after them      (T*k,)
  4. grouped SwiGLU over the held groups (`jax.lax.ragged_dot`):
     rows past the held groups are never computed
  5. each token's k outputs gathered back and summed by weight

The shared experts are every chip's alike and are added once, by the
caller.  Named scopes `MoERoute`, `MoEExperts` and `MoECombine` mark the
three parts in the compiled program's `op_name`s.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.nn import layers as L
from repro.nn import module as nn


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                 # per-expert ffn hidden dim
    n_experts: int            # router outputs: every expert of the layer
    top_k: int
    n_shared: int = 0         # always-on shared experts (deepseek-v2)
    held: int = 0             # experts whose weights this layer holds (0: all)
    expert_offset: int = 0    # id of the first held expert
    norm_topk_prob: bool = True          # renormalize the top-k weights
    routed_scaling_factor: float = 1.0
    router_dtype: Any = jnp.float32
    dtype: Any = jnp.float32
    # expert-parallel shard_map path: name of the mesh axis experts are
    # sharded over (None = single-program GSPMD path).  See moe_apply_ep.
    ep_axis: str | None = None

    @property
    def n_held(self) -> int:
        return self.held or self.n_experts


from repro.nn import dist as _dist

set_ep_mesh = _dist.set_mesh          # back-compat alias


def moe_init(key, cfg: MoEConfig):
    ks = nn.split_keys(key, 5)
    E, D, F = cfg.n_held, cfg.d_model, cfg.d_ff
    p = {
        "router": L.dense_init(ks[0], D, cfg.n_experts, dtype=cfg.router_dtype),
        # stacked held-expert weights: (E, D, F) / (E, F, D)
        "gate": nn.lecun_init(ks[1], (E, D, F), cfg.dtype, fan_in=D),
        "up": nn.lecun_init(ks[2], (E, D, F), cfg.dtype, fan_in=D),
        "down": nn.lecun_init(ks[3], (E, F, D), cfg.dtype, fan_in=F),
    }
    if cfg.n_shared:
        p["shared"] = L.swiglu_init(ks[4], D, F * cfg.n_shared, dtype=cfg.dtype)
    return p


def router_probs(params, cfg: MoEConfig, x_flat):
    logits = L.dense_apply(params["router"],
                           x_flat.astype(cfg.router_dtype))
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # (T, E)


def route(params, cfg: MoEConfig, x_flat):
    """(probs (T, E), weights (T, k), expert ids (T, k)): the top-k of
    the softmax, renormalized only under `norm_topk_prob`, times
    `routed_scaling_factor`."""
    with jax.named_scope("MoERoute"):
        probs = router_probs(params, cfg, x_flat)
        w, eid = jax.lax.top_k(probs, cfg.top_k)
        if cfg.norm_topk_prob:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        return probs, w * cfg.routed_scaling_factor, eid


def held_experts(experts, cfg: MoEConfig, x_flat, w, eid, offset):
    """The held experts' part of the routed result.  experts: "gate",
    "up" (G, D, F), "down" (G, F, D) for the experts [offset, offset+G);
    x_flat (T, D); w, eid (T, k) from `route`.  Every assignment to a
    held expert is computed (none dropped), none to another."""
    T, D = x_flat.shape
    k = eid.shape[-1]
    G = experts["gate"].shape[0]
    with jax.named_scope("MoERoute"):
        local = eid.reshape(-1) - offset                 # (T*k,)
        mine = (local >= 0) & (local < G)
        local = jnp.where(mine, local, G)                # others sort last
        order = jnp.argsort(local, stable=True)
        sizes = jnp.bincount(local, length=G + 1)[:G].astype(jnp.int32)
        rows = x_flat[order // k]                        # (T*k, D)
    with jax.named_scope("MoEExperts"):
        g = jax.lax.ragged_dot(rows, experts["gate"], sizes)
        u = jax.lax.ragged_dot(rows, experts["up"], sizes)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * u, experts["down"], sizes)
    with jax.named_scope("MoECombine"):
        y = y[jnp.argsort(order)].reshape(T, k, D)       # assignment order
        wm = jnp.where(mine.reshape(T, k), w, 0.0)
        y = jnp.where(mine.reshape(T, k, 1), y.astype(jnp.float32), 0.0)
        return jnp.einsum("tkd,tk->td", y, wm).astype(x_flat.dtype)


def _experts(params):
    return {n: params[n] for n in ("gate", "up", "down")}


def moe_apply(params, cfg: MoEConfig, x, *, return_aux: bool = False):
    """x: (B, S, D) -> (B, S, D)  [+ aux losses dict]."""
    if cfg.ep_axis is not None and not return_aux:
        return moe_apply_ep(params, cfg, x)
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    probs, w, eid = route(params, cfg, xf)
    out = held_experts(_experts(params), cfg, xf, w, eid,
                       cfg.expert_offset).reshape(B, S, D)
    if cfg.n_shared:
        out = out + L.swiglu_apply(params["shared"], x)
    if return_aux:
        # load-balance loss (Switch): E * sum_e f_e * p_e
        E = cfg.n_experts
        counts = jnp.bincount(eid.reshape(-1), length=E)
        frac_tokens = counts.astype(jnp.float32) / eid.size
        lb_loss = E * jnp.sum(frac_tokens * probs.mean(axis=0))
        return out, {"load_balance_loss": lb_loss,
                     "drop_fraction": jnp.zeros((), jnp.float32)}
    return out


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path (§Perf optimization, beyond-GSPMD)
# ---------------------------------------------------------------------------

def moe_apply_ep(params, cfg: MoEConfig, x):
    """Expert-parallel MoE via shard_map over cfg.ep_axis.

    Key observation: in the megatron-style layout the activations are
    REPLICATED over the model/expert axis (batch shards live on
    "pod"/"data").  Dispatch therefore needs NO token movement at all:
    every expert shard already sees every local token, computes the
    assignments that target its own experts (`held_experts` at offset
    axis_index x held), and contributes a partial combine that is
    psum'd over the expert axis — the same collective shape as a
    tensor-parallel MLP's output all-reduce.

    This replaces GSPMD's lowering of the global scatter dispatch (an
    all-gather of EVERY token row to EVERY shard — measured 135 GB/layer
    for deepseek-v2 train_4k) with one (T_local, D) psum (~0.7 GB/layer).
    """
    ax = cfg.ep_axis
    mesh = _dist.get_mesh()
    from jax.sharding import PartitionSpec as P
    dp = _dist.batch_axes(mesh) or None
    ep = 1
    for a in (ax if isinstance(ax, tuple) else (ax,)):
        ep *= mesh.shape[a]
    assert cfg.n_held % ep == 0, f"experts {cfg.n_held} % ep {ep}"
    E_l = cfg.n_held // ep

    def body(xl, router, experts):
        Bl, S_, D_ = xl.shape
        xf = xl.reshape(Bl * S_, D_)
        _, w, eid = route({"router": router}, cfg, xf)
        offset = cfg.expert_offset + jax.lax.axis_index(ax) * E_l
        partial = held_experts(experts, cfg, xf, w, eid, offset)
        return jax.lax.psum(partial, ax).reshape(Bl, S_, D_)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(),
                  {n: P(ax, None, None) for n in ("gate", "up", "down")}),
        out_specs=P(dp, None, None))
    out = fn(x, params["router"], _experts(params))
    if cfg.n_shared:
        out = out + L.swiglu_apply(params["shared"], x)
    return out
