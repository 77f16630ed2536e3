"""SplitNN core: cut-layer partitioning of segmented models.

A `SegModel` is any network expressed as an ordered list of segments; the
*cut* is an index into that list.  Ownership is literal: the client holds
the parameter slice for its segments, the server holds the rest, and the
only tensors that ever cross the boundary are the cut activations
(forward) and the cut gradients (backward).  `jax.vjp` is used explicitly
so the wire is a first-class value — `WireRecord`s feed both the
resource-accounting (paper Tables 1-2) and the privacy tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.wire_compress import as_dense


@dataclasses.dataclass(frozen=True)
class SegModel:
    """A model expressed as `n_segments` sequential segments.

    init(key) -> params (indexable by segment via param_slice)
    apply_range(params, x, lo, hi) -> activations after segment hi-1
    param_slice(params, lo, hi) -> the parameters of segments [lo, hi)
    param_join(slices) -> params   (inverse of slicing along segments)
    """
    n_segments: int
    init: Callable
    apply_range: Callable
    param_slice: Callable
    param_join: Callable


def list_segmodel(n_segments, init, layer_apply) -> SegModel:
    """SegModel over a list-of-param-dicts network (VGG/ResNet/MLP)."""
    def apply_range(params, x, lo, hi, *, offset: int = 0):
        for i in range(lo, hi):
            x = layer_apply(params[i - offset] if offset else params[i], i, x)
        return x

    return SegModel(
        n_segments=n_segments,
        init=init,
        apply_range=apply_range,
        param_slice=lambda p, lo, hi: p[lo:hi],
        param_join=lambda slices: sum(slices, []),
    )


@dataclasses.dataclass
class WireRecord:
    """One payload that crossed the client/server boundary.

    `payload_bytes` overrides the dense shape*itemsize count when wire
    middleware changed the physical representation (e.g. int8 quantization
    ships 1 byte/element + per-row scales while the in-graph value stays
    fp32) — `repro.api.wire` sets it from the transform stack.
    """
    name: str
    shape: tuple         # LOGICAL payload shape (pre-pack)
    dtype: Any           # LOGICAL dtype (what the dense value carries)
    direction: str       # "up" (client->server) | "down"
    payload_bytes: int | None = None
    physical: bool = False   # True: bytes derived from a packed payload

    @property
    def bytes(self) -> int:
        if self.payload_bytes is not None:
            return self.payload_bytes
        n = 1
        for s in self.shape:
            n *= s
        return n * jnp.dtype(self.dtype).itemsize


def record(wires: list, name: str, t, direction: str):
    """Record one boundary crossing and return the value AS THE OTHER
    SIDE RECEIVES IT.

    `wires` is either a plain list (no middleware — `t` passes through
    unchanged, the original behaviour) or a `repro.api.wire.WireTape`,
    which applies the plan's `WireTransform` stack to the value in-graph
    and prices the record at the stack's physical wire bytes.  With a
    physical transform in the stack the returned value is the packed
    `(int8, scales)` pytree itself — consumers go through `as_dense`.
    Every grad function in this module uses the RETURN value, so
    middleware composes with all topologies for free."""
    transform = getattr(wires, "transform", None)
    payload, physical = None, False
    if transform is not None:
        t = transform(t, name, direction)
        payload, physical = wires.payload_bytes(t)
    wires.append(WireRecord(name, tuple(t.shape), t.dtype, direction,
                            payload, physical))
    return t


# ---------------------------------------------------------------------------
# Vanilla split: client [0, cut) -> server [cut, L) + loss
# ---------------------------------------------------------------------------

def vanilla_split_grads(model: SegModel, cut: int, params_c, params_s,
                        x, labels, loss_fn, wires: list | None = None):
    """One split training step's gradients.

    Returns (loss, g_client, g_server).  The ONLY values linking the two
    sides are `act` (up) and `g_act` (down) — this is checked by tests.
    Each part runs under its IR step's device scope
    (`repro.engine.program.scope`).
    """
    from repro.engine.program import (ClientBwd, ClientFwd, RecvGrad,
                                      SendCut, ServerFwdBwd, scope)
    wires = wires if wires is not None else []

    def client_fwd(pc):
        return model.apply_range(pc, x, 0, cut)

    with scope(ClientFwd):
        act, client_vjp = jax.vjp(client_fwd, params_c)
    with scope(SendCut):
        act = record(wires, "cut_act", act, "up")
        act_in = as_dense(act)

    def server_loss(ps, a):
        logits = model.apply_range(ps, a, cut, model.n_segments,
                                   offset=cut) \
            if _takes_offset(model) else model.apply_range(ps, a, cut,
                                                           model.n_segments)
        return loss_fn(logits, labels)

    with scope(ServerFwdBwd):
        (loss, ), vjp_s = jax.vjp(lambda ps, a: (server_loss(ps, a),),
                                  params_s, act_in)
        g_server, g_act = vjp_s((jnp.ones(()),))
    with scope(RecvGrad):
        g_act = as_dense(record(wires, "cut_grad", g_act, "down"))
    with scope(ClientBwd):
        (g_client,) = client_vjp(g_act)
    return loss, g_client, g_server, wires


def _takes_offset(model: SegModel) -> bool:
    import inspect
    return "offset" in inspect.signature(model.apply_range).parameters


# ---------------------------------------------------------------------------
# U-shaped split: client [0,c1) + [c2,L) + loss; server [c1,c2).
# Labels NEVER cross (the paper's no-label-sharing configuration).
# ---------------------------------------------------------------------------

def u_shaped_grads(model: SegModel, cut1: int, cut2: int, params_head,
                   params_mid, params_tail, x, labels, loss_fn,
                   wires: list | None = None):
    wires = wires if wires is not None else []

    act1, vjp_head = jax.vjp(
        lambda p: model.apply_range(p, x, 0, cut1), params_head)
    act1 = record(wires, "cut_act_1", act1, "up")

    act2, vjp_mid = jax.vjp(
        lambda p, a: _apply_mid(model, p, a, cut1, cut2), params_mid,
        as_dense(act1))
    act2 = record(wires, "cut_act_2", act2, "down")

    def tail_loss(p, a):
        logits = _apply_tail(model, p, a, cut2)
        return loss_fn(logits, labels)

    loss_val, (g_tail, g_act2) = jax.value_and_grad(
        tail_loss, argnums=(0, 1))(params_tail, as_dense(act2))
    g_act2 = record(wires, "cut_grad_2", g_act2, "up")
    g_mid, g_act1 = vjp_mid(as_dense(g_act2))
    g_act1 = record(wires, "cut_grad_1", g_act1, "down")
    (g_head,) = vjp_head(as_dense(g_act1))
    return loss_val, g_head, g_mid, g_tail, wires


def _apply_mid(model, p, a, cut1, cut2):
    if _takes_offset(model):
        return model.apply_range(p, a, cut1, cut2, offset=cut1)
    return model.apply_range(p, a, cut1, cut2)


def _apply_tail(model, p, a, cut2):
    if _takes_offset(model):
        return model.apply_range(p, a, cut2, model.n_segments, offset=cut2)
    return model.apply_range(p, a, cut2, model.n_segments)


# ---------------------------------------------------------------------------
# Vertical (multi-modal) split: K client branches -> concat -> server trunk
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Branch:
    """A per-modality client-side feature network."""
    init: Callable                    # key -> params
    apply: Callable                   # (params, x) -> features (B, f)


def vertical_split_grads(branches: list[Branch], params_branches,
                         trunk_apply, params_trunk, xs: list, labels,
                         loss_fn, wires: list | None = None):
    """xs[i] is modality i held by client i.  Concat happens server-side
    (or via the fused splitcat kernel on TPU)."""
    wires = wires if wires is not None else []
    acts, vjps = [], []
    for i, (br, pb, x) in enumerate(zip(branches, params_branches, xs)):
        a, v = jax.vjp(lambda p, xi=x, b=br: b.apply(p, xi), pb)
        acts.append(record(wires, f"branch_{i}_act", a, "up"))
        vjps.append(v)

    def server_loss(pt, alist):
        feat = jnp.concatenate(alist, axis=-1)
        return loss_fn(trunk_apply(pt, feat), labels)

    loss, (g_trunk, g_acts) = jax.value_and_grad(
        server_loss, argnums=(0, 1))(params_trunk,
                                     [as_dense(a) for a in acts])
    g_branches = []
    for i, (v, ga) in enumerate(zip(vjps, g_acts)):
        ga = record(wires, f"branch_{i}_grad", ga, "down")
        (gb,) = v(as_dense(ga))
        g_branches.append(gb)
    return loss, g_branches, g_trunk, wires


# ---------------------------------------------------------------------------
# Multi-hop (Tor-like): chain of clients, each owns a contiguous slab.
# ---------------------------------------------------------------------------

def multihop_grads(model: SegModel, cuts: list[int], params_slabs, x, labels,
                   loss_fn, wires: list | None = None):
    """cuts: ascending segment boundaries, e.g. [2, 4, 6]; slab i runs
    [cuts[i-1], cuts[i]) with cuts[-1] == n_segments implied for server."""
    wires = wires if wires is not None else []
    bounds = [0] + list(cuts) + [model.n_segments]
    act = x
    vjps = []
    for i in range(len(bounds) - 2):          # all client hops
        lo, hi = bounds[i], bounds[i + 1]
        act, v = jax.vjp(
            lambda p, a, lo=lo, hi=hi: _apply_hop(model, p, a, lo, hi),
            params_slabs[i], as_dense(act))
        act = record(wires, f"hop_{i}_act", act, "up")
        vjps.append(v)

    lo, hi = bounds[-2], bounds[-1]

    def final_loss(p, a):
        return loss_fn(_apply_hop(model, p, a, lo, hi), labels)

    loss, (g_last, g_act) = jax.value_and_grad(
        final_loss, argnums=(0, 1))(params_slabs[-1], as_dense(act))
    grads = [g_last]
    for i in reversed(range(len(vjps))):
        g_act = record(wires, f"hop_{i}_grad", g_act, "down")
        g_slab, g_act = vjps[i](as_dense(g_act))
        grads.append(g_slab)
    return loss, list(reversed(grads)), wires


def _apply_hop(model, p, a, lo, hi):
    if _takes_offset(model):
        return model.apply_range(p, a, lo, hi, offset=lo)
    return model.apply_range(p, a, lo, hi)


# ---------------------------------------------------------------------------
# Multi-task: shared client trunk(s) -> several server heads/tasks
# ---------------------------------------------------------------------------

def multitask_grads(branches: list[Branch], params_branches,
                    heads: list[Callable], params_heads, xs, labels_per_task,
                    loss_fns, wires: list | None = None):
    wires = wires if wires is not None else []
    acts, vjps = [], []
    for i, (br, pb, x) in enumerate(zip(branches, params_branches, xs)):
        a, v = jax.vjp(lambda p, xi=x, b=br: b.apply(p, xi), pb)
        acts.append(record(wires, f"branch_{i}_act", a, "up"))
        vjps.append(v)

    feat_fn = lambda alist: jnp.concatenate(alist, axis=-1)
    acts_dense = [as_dense(a) for a in acts]
    losses, g_heads = [], []
    g_acts_total = None
    for t, (head, ph, lf, lab) in enumerate(
            zip(heads, params_heads, loss_fns, labels_per_task)):
        def task_loss(p, alist):
            return lf(head(p, feat_fn(alist)), lab)
        lv, (gh, gas) = jax.value_and_grad(task_loss, argnums=(0, 1))(
            ph, acts_dense)
        losses.append(lv)
        g_heads.append(gh)
        g_acts_total = gas if g_acts_total is None else \
            jax.tree_util.tree_map(jnp.add, g_acts_total, gas)

    g_branches = []
    for i, (v, ga) in enumerate(zip(vjps, g_acts_total)):
        ga = record(wires, f"branch_{i}_grad", ga, "down")
        (gb,) = v(as_dense(ga))
        g_branches.append(gb)
    return jnp.stack(losses), g_branches, g_heads, wires


# ---------------------------------------------------------------------------
# Extended vanilla (paper §5.1 Fig. 4a): K modality branches -> concat is
# processed by ANOTHER client before reaching the server.
# ---------------------------------------------------------------------------

def extended_vanilla_grads(branches: list[Branch], params_branches,
                           mid_apply, params_mid, trunk_apply, params_trunk,
                           xs: list, labels, loss_fn,
                           wires: list | None = None):
    """Like vertical_split_grads, but an intermediate client applies
    `mid_apply` to the concatenated features before the server trunk."""
    wires = wires if wires is not None else []
    acts, vjps = [], []
    for i, (br, pb, x) in enumerate(zip(branches, params_branches, xs)):
        a, v = jax.vjp(lambda p, xi=x, b=br: b.apply(p, xi), pb)
        acts.append(record(wires, f"branch_{i}_act", a, "up"))
        vjps.append(v)

    def mid_fwd(pm, alist):
        return mid_apply(pm, jnp.concatenate(alist, axis=-1))

    mid_out, vjp_mid = jax.vjp(mid_fwd, params_mid,
                               [as_dense(a) for a in acts])
    mid_out = record(wires, "mid_act", mid_out, "up")

    def server_loss(pt, m):
        return loss_fn(trunk_apply(pt, m), labels)

    loss, (g_trunk, g_mid_out) = jax.value_and_grad(
        server_loss, argnums=(0, 1))(params_trunk, as_dense(mid_out))
    g_mid_out = record(wires, "mid_grad", g_mid_out, "down")
    g_mid, g_acts = vjp_mid(as_dense(g_mid_out))
    g_branches = []
    for i, (v, ga) in enumerate(zip(vjps, g_acts)):
        ga = record(wires, f"branch_{i}_grad", ga, "down")
        (gb,) = v(as_dense(ga))
        g_branches.append(gb)
    return loss, g_branches, g_mid, g_trunk, wires
