"""Mixture-of-Experts: top-k router + sort-based capacity dispatch.

Dispatch is gather-based (argsort over expert assignment), NOT the
(T, E, C) one-hot einsum of GShard — the one-hot dispatch costs
O(T^2 * k * d) FLOPs which poisons the roofline.  Here:

  1. top-k gate per token                                (T, k)
  2. flatten assignments, sort by expert id              (T*k,)
  3. slot-within-expert via sorted positions             static shapes
  4. gather tokens into (E, C, d), grouped matmul        true MoE FLOPs
  5. scatter-add back with gate weights

Experts shard over the mesh "model" axis (expert parallelism); the gather
across token-sharded inputs lowers to an all-to-all, which is exactly the
collective the roofline analysis wants to see.
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
    n_experts: int
    top_k: int
    n_shared: int = 0         # always-on shared experts (deepseek-v2)
    capacity_factor: float = 1.25
    router_dtype: Any = jnp.float32
    dtype: Any = jnp.float32
    # expert-parallel shard_map path: name of the mesh axis experts are
    # sharded over (None = single-program GSPMD path).  See moe_apply_ep.
    ep_axis: str | None = None


from repro.nn import dist as _dist

set_ep_mesh = _dist.set_mesh          # back-compat alias


def moe_init(key, cfg: MoEConfig):
    ks = nn.split_keys(key, 5)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": L.dense_init(ks[0], D, E, dtype=cfg.router_dtype),
        # stacked expert weights: (E, D, F) / (E, F, D)
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
    return jax.nn.softmax(logits, axis=-1)               # (T, E)


def _capacity(T: int, cfg: MoEConfig) -> int:
    c = int(T * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(cfg.top_k, -(-c // 8) * 8)                # round up to 8


def moe_apply(params, cfg: MoEConfig, x, *, return_aux: bool = False):
    """x: (B, S, D) -> (B, S, D)  [+ aux losses dict]."""
    if cfg.ep_axis is not None and not return_aux:
        return moe_apply_ep(params, cfg, x)
    B, S, D = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    C = _capacity(T, cfg)
    xf = x.reshape(T, D)

    probs = router_probs(params, cfg, xf)                # (T, E)
    gate_w, eid = jax.lax.top_k(probs, k)                # (T, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # --- sort-based dispatch -------------------------------------------------
    flat_eid = eid.reshape(-1)                           # (T*k,)
    flat_tok = jnp.repeat(jnp.arange(T), k)              # token of each slot
    flat_w = gate_w.reshape(-1)
    order = jnp.argsort(flat_eid)                        # stable in jnp
    s_eid, s_tok, s_w = flat_eid[order], flat_tok[order], flat_w[order]
    # slot index within expert = position - start offset of that expert
    counts = jnp.bincount(flat_eid, length=E)            # (E,)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    slot = jnp.arange(T * k) - starts[s_eid]             # (T*k,)
    keep = slot < C                                      # overflow dropped
    # dropped slots all land in a scratch row E*C which is discarded
    dest = jnp.where(keep, s_eid * C + slot, E * C)

    # gather tokens into expert buffers (kept dests are unique by construction)
    buf = jnp.zeros((E * C + 1, D), x.dtype).at[dest].set(xf[s_tok])
    buf = buf[:E * C].reshape(E, C, D)

    # --- grouped expert ffn (swiglu) ----------------------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, params["gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["up"])
    h = jax.nn.silu(g) * u
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["down"]).reshape(E * C, D)

    # --- combine: scatter-add back ------------------------------------------
    contrib = y_buf[jnp.minimum(dest, E * C - 1)] \
        * jnp.where(keep, s_w, 0.0)[:, None].astype(x.dtype)
    out = jnp.zeros((T, D), x.dtype).at[s_tok].add(contrib)
    out = out.reshape(B, S, D)

    if cfg.n_shared:
        out = out + L.swiglu_apply(params["shared"], x)

    if return_aux:
        # load-balance loss (Switch): E * sum_e f_e * p_e
        frac_tokens = counts.astype(jnp.float32) / (T * k)
        mean_prob = probs.mean(axis=0)
        lb_loss = E * jnp.sum(frac_tokens * mean_prob)
        dropped = jnp.sum(~keep) / (T * k)
        return out, {"load_balance_loss": lb_loss, "drop_fraction": dropped}
    return out


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path (§Perf optimization, beyond-GSPMD)
# ---------------------------------------------------------------------------

def moe_apply_ep(params, cfg: MoEConfig, x):
    """Expert-parallel MoE via shard_map over cfg.ep_axis.

    Key observation: in the megatron-style layout the activations are
    REPLICATED over the model/expert axis (batch shards live on
    "pod"/"data").  Dispatch therefore needs NO token movement at all:
    every expert shard already sees every local token, selects the
    assignments that target its own experts, and contributes a partial
    combine that is psum'd over the expert axis — the same collective
    shape as a tensor-parallel MLP's output all-reduce.

    This replaces GSPMD's lowering of the global scatter dispatch (an
    all-gather of EVERY token row to EVERY shard — measured 135 GB/layer
    for deepseek-v2 train_4k) with one (T_local, D) psum (~0.7 GB/layer).
    """
    ax = cfg.ep_axis
    mesh = _dist.get_mesh()
    from jax.sharding import PartitionSpec as P
    dp = _dist.batch_axes(mesh) or None
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    ep = 1
    for a in (ax if isinstance(ax, tuple) else (ax,)):
        ep *= mesh.shape[a]
    assert E % ep == 0, f"experts {E} % ep {ep}"
    E_l = E // ep

    def body(xl, router_w, gate, up, down):
        Bl, S_, D_ = xl.shape
        T = Bl * S_
        C = _capacity(T, cfg)
        idx = jax.lax.axis_index(ax)
        e_lo = idx * E_l
        xf = xl.reshape(T, D_)

        logits = xf.astype(cfg.router_dtype) @ router_w
        probs = jax.nn.softmax(logits, axis=-1)          # (T, E) full router
        gate_w, eid = jax.lax.top_k(probs, k)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

        flat_eid = eid.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(T), k)
        flat_w = gate_w.reshape(-1)
        # map to LOCAL expert ids; foreign assignments go to bucket E_l
        local_eid = jnp.where((flat_eid >= e_lo) & (flat_eid < e_lo + E_l),
                              flat_eid - e_lo, E_l)
        order = jnp.argsort(local_eid)
        s_eid, s_tok, s_w = (local_eid[order], flat_tok[order],
                             flat_w[order])
        counts = jnp.bincount(local_eid, length=E_l + 1)
        starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        slot = jnp.arange(T * k) - starts[s_eid]
        keep = (s_eid < E_l) & (slot < C)
        dest = jnp.where(keep, s_eid * C + slot, E_l * C)

        buf = jnp.zeros((E_l * C + 1, D_), xl.dtype).at[dest].set(xf[s_tok])
        buf = buf[:E_l * C].reshape(E_l, C, D_)
        g = jnp.einsum("ecd,edf->ecf", buf, gate)
        u = jnp.einsum("ecd,edf->ecf", buf, up)
        h = jax.nn.silu(g) * u
        y_buf = jnp.einsum("ecf,efd->ecd", h, down).reshape(E_l * C, D_)

        contrib = y_buf[jnp.minimum(dest, E_l * C - 1)] \
            * jnp.where(keep, s_w, 0.0)[:, None].astype(xl.dtype)
        partial = jnp.zeros((T, D_), xl.dtype).at[s_tok].add(contrib)
        out = jax.lax.psum(partial, ax)
        return out.reshape(Bl, S_, D_)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P(ax, None, None), P(ax, None, None), P(ax, None, None)),
        out_specs=P(dp, None, None))
    out = fn(x, params["router"]["w"], params["gate"], params["up"],
             params["down"])
    if cfg.n_shared:
        out = out + L.swiglu_apply(params["shared"], x)
    return out
