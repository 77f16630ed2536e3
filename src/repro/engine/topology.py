"""Declarative split-learning topologies + the step-program lowering.

A `Topology` names *where* the cut(s) fall and lowers onto the explicit
`jax.vjp` grad functions in `repro.core.split` — it owns no scheduling.
The compiled `RoundEngine` consumes the uniform (client, server) contract:

    init(key)                       -> (client_params, server_params)
    turn_grads(pc, ps, batch, lf)   -> (loss, g_client, g_server)
    turn_grads_wires(..., wires)    -> same, appending WireRecords

`lower()` turns any Topology into a `repro.engine.program.StepProgram`
— the typed step-sequence IR every executor (serial / parallel /
pipelined) interprets; `lower_baseline()` does the same for the fedavg
and large_batch comparison modes.  Each factory below also attaches:

  * `steps` — its step sequence (wire crossings are first-class
    `SendCut`/`RecvGrad` edges carrying the billing metadata the
    engine's `TurnCost` accounting reads);
  * `pipeline_fwd/rest/bwd` — the staged form of one turn the pipelined
    executor double-buffers: fwd runs the client side up to the first
    cut crossing, rest is everything beyond it (server fwd/bwd plus any
    post-cut client work, e.g. the u-shaped tail), bwd rematerializes
    the client forward from the returned cut gradient.

Six paper configurations (Gupta & Raskar §3; Ceballos et al. 2020 for
vertical; Fig. 4 for multi-hop / extended / multi-task):

  vanilla          — client [0, cut), server [cut, L) + loss
  u_shaped         — client head+tail, server mid; labels never cross
  vertical         — K modality branches -> concat -> server trunk
                     (parallel-only)
  multihop         — Tor-like slab chain; client owns the first slab, the
                     remaining slabs + loss run server-side
  multitask        — K modality branches -> concat -> T server heads, one
                     loss per task (parallel-only)
  extended_vanilla — K modality branches -> concat processed by an
                     intermediate client -> server trunk (parallel-only)
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import split as sp
from repro.engine import program as ir

KINDS = ("vanilla", "u_shaped", "vertical", "multihop", "multitask",
         "extended_vanilla")

# kinds whose "clients" axis is K modality branches all feeding ONE step
BRANCH_KINDS = ("vertical", "multitask", "extended_vanilla")


@dataclasses.dataclass(frozen=True)
class Topology:
    kind: str
    init: Callable                # key -> (client_params, server_params)
    turn_grads: Callable          # (pc, ps, batch, loss_fn) -> (loss, g_c, g_s)
    turn_grads_wires: Callable    # (pc, ps, batch, loss_fn, wires) -> same
    evaluate: Callable | None = None   # (pc, ps, batch) -> logits
    client_fwd: Callable | None = None  # (pc, batch) -> first outbound act
    # vertical only: all clients contribute to ONE step
    round_grads: Callable | None = None  # (clients, ps, batch, loss_fn)
    # the step-sequence IR this topology lowers to (see module docstring)
    steps: tuple = ()
    # staged turn (pipelined executor); turn kinds only
    pipeline_fwd: Callable | None = None   # (pc, batch) -> act
    # (pc, ps, act, batch, loss_fn, wires) -> (loss, g_rest, g_s, g_act)
    pipeline_rest: Callable | None = None
    pipeline_bwd: Callable | None = None   # (pc, batch, g_act, g_rest) -> g_c

    @property
    def parallel_only(self) -> bool:
        return self.round_grads is not None


def lower(topology: Topology) -> ir.StepProgram:
    """Topology -> the one `StepProgram` every executor interprets."""
    branch = topology.parallel_only
    return ir.StepProgram(
        kind=topology.kind,
        round_type="branch" if branch else "turn",
        steps=tuple(topology.steps),
        topology=topology,
        split_batch=(ir.split_branch_batch if branch
                     else ir.split_turn_batch))


def lower_baseline(mode: str, *, local_steps: int = 1) -> ir.StepProgram:
    """The comparison baselines' step programs: no cut — the whole
    model (or its gradient) is the wire payload, priced on the
    `WeightHandoff` edges by the same middleware stack."""
    if mode == "fedavg":
        steps = (ir.WeightHandoff(name="model_pull", direction="down"),
                 ir.ClientFwd(stage="local", repeats=local_steps),
                 ir.ClientBwd(stage="local"),
                 ir.WeightHandoff(name="model_push", direction="up"),
                 ir.Aggregate(what="mean_models"))
    elif mode == "large_batch":
        steps = (ir.WeightHandoff(name="model_pull", direction="down"),
                 ir.ClientFwd(stage="full"),
                 ir.ClientBwd(stage="full"),
                 ir.WeightHandoff(name="grad_push", direction="up"),
                 ir.Aggregate(what="mean_grads"))
    else:
        raise ValueError(f"unknown baseline mode {mode!r}")
    return ir.StepProgram(kind=mode, round_type=mode, steps=steps,
                          split_batch=ir.split_turn_batch)


def _turn_steps(*inner) -> tuple:
    """The shared turn-kind frame: optional p2p handoff edge in, one
    optimizer step boundary out."""
    return ((ir.WeightHandoff(name="p2p_handoff", direction="p2p",
                              when="sync=p2p"),)
            + tuple(inner) + (ir.Aggregate(what="step"),))


def _branch_fanin_steps(n_clients: int) -> tuple:
    """The K branch forwards + their billed wire edges (branch kinds)."""
    out = []
    for i in range(n_clients):
        out += [ir.ClientFwd(stage=f"branch_{i}", client=i),
                ir.SendCut(name=f"branch_{i}_act", direction="up",
                           client=i)]
    return tuple(out) + (ir.Aggregate(what="concat_features"),)


def _branch_fanout_steps(n_clients: int) -> tuple:
    out = []
    for i in range(n_clients):
        out += [ir.RecvGrad(name=f"branch_{i}_grad", direction="down",
                            client=i),
                ir.ClientBwd(stage=f"branch_{i}", client=i)]
    return tuple(out) + (ir.Aggregate(what="step"),)


def _drop_wires(turn_grads_wires):
    def turn_grads(pc, ps, batch, loss_fn):
        return turn_grads_wires(pc, ps, batch, loss_fn, [])
    return turn_grads


# ---------------------------------------------------------------------------
# vanilla
# ---------------------------------------------------------------------------

VANILLA_STEPS = _turn_steps(
    ir.ClientFwd(stage="client"),
    ir.SendCut(name="cut_act", direction="up"),
    ir.ServerFwdBwd(),
    ir.RecvGrad(name="cut_grad", direction="down"),
    ir.ClientBwd(stage="client"))


def vanilla(model: sp.SegModel, cut: int) -> Topology:
    def init(key):
        full = model.init(key)
        return (model.param_slice(full, 0, cut),
                model.param_slice(full, cut, model.n_segments))

    def turn_grads_wires(pc, ps, batch, loss_fn, wires):
        loss, g_c, g_s, _ = sp.vanilla_split_grads(
            model, cut, pc, ps, batch["x"], batch["labels"], loss_fn, wires)
        return loss, g_c, g_s

    def evaluate(pc, ps, batch):
        act = model.apply_range(pc, batch["x"], 0, cut)
        if sp._takes_offset(model):
            return model.apply_range(ps, act, cut, model.n_segments,
                                     offset=cut)
        return model.apply_range(ps, act, cut, model.n_segments)

    def pipeline_fwd(pc, batch):
        return model.apply_range(pc, batch["x"], 0, cut)

    def pipeline_rest(pc, ps, act, batch, loss_fn, wires):
        act = sp.record(wires, "cut_act", act, "up")

        def server_loss(ps_, a):
            if sp._takes_offset(model):
                logits = model.apply_range(ps_, a, cut, model.n_segments,
                                           offset=cut)
            else:
                logits = model.apply_range(ps_, a, cut, model.n_segments)
            return loss_fn(logits, batch["labels"])

        (loss,), vjp_s = jax.vjp(lambda p, a: (server_loss(p, a),),
                                 ps, sp.as_dense(act))
        g_s, g_act = vjp_s((jnp.ones(()),))
        g_act = sp.record(wires, "cut_grad", g_act, "down")
        return loss, {}, g_s, sp.as_dense(g_act)

    def pipeline_bwd(pc, batch, g_act, g_rest):
        _, vjp_c = jax.vjp(lambda p: pipeline_fwd(p, batch), pc)
        (g_c,) = vjp_c(g_act)
        return g_c

    return Topology(kind="vanilla", init=init,
                    turn_grads=_drop_wires(turn_grads_wires),
                    turn_grads_wires=turn_grads_wires, evaluate=evaluate,
                    client_fwd=lambda pc, b: model.apply_range(
                        pc, b["x"], 0, cut),
                    steps=VANILLA_STEPS, pipeline_fwd=pipeline_fwd,
                    pipeline_rest=pipeline_rest, pipeline_bwd=pipeline_bwd)


def vanilla_fns(init_full: Callable, split: Callable, client_apply: Callable,
                server_apply: Callable) -> Topology:
    """Vanilla topology over opaque client/server apply functions (the
    `models.lm.LM` split hooks) instead of a SegModel.  Same wire protocol
    and device scopes as `core.split.vanilla_split_grads`: only the cut
    activation (up) and its gradient (down) cross."""
    def init(key):
        return split(init_full(key))

    def turn_grads_wires(pc, ps, batch, loss_fn, wires):
        with ir.scope(ir.ClientFwd):
            act, vjp_c = jax.vjp(lambda p: client_apply(p, batch), pc)
        with ir.scope(ir.SendCut):
            act = sp.as_dense(sp.record(wires, "cut_act", act, "up"))
        with ir.scope(ir.ServerFwdBwd):
            (loss,), vjp_s = jax.vjp(
                lambda p, a: (loss_fn(server_apply(p, a), batch["labels"]),),
                ps, act)
            g_s, g_act = vjp_s((jnp.ones(()),))
        with ir.scope(ir.RecvGrad):
            g_act = sp.as_dense(sp.record(wires, "cut_grad", g_act, "down"))
        with ir.scope(ir.ClientBwd):
            (g_c,) = vjp_c(g_act)
        return loss, g_c, g_s

    def evaluate(pc, ps, batch):
        return server_apply(ps, client_apply(pc, batch))

    def pipeline_rest(pc, ps, act, batch, loss_fn, wires):
        act = sp.record(wires, "cut_act", act, "up")
        (loss,), vjp_s = jax.vjp(
            lambda p, a: (loss_fn(server_apply(p, a), batch["labels"]),),
            ps, sp.as_dense(act))
        g_s, g_act = vjp_s((jnp.ones(()),))
        g_act = sp.record(wires, "cut_grad", g_act, "down")
        return loss, {}, g_s, sp.as_dense(g_act)

    def pipeline_bwd(pc, batch, g_act, g_rest):
        _, vjp_c = jax.vjp(lambda p: client_apply(p, batch), pc)
        (g_c,) = vjp_c(g_act)
        return g_c

    return Topology(kind="vanilla", init=init,
                    turn_grads=_drop_wires(turn_grads_wires),
                    turn_grads_wires=turn_grads_wires, evaluate=evaluate,
                    client_fwd=client_apply,
                    steps=VANILLA_STEPS, pipeline_fwd=client_apply,
                    pipeline_rest=pipeline_rest, pipeline_bwd=pipeline_bwd)


# ---------------------------------------------------------------------------
# u-shaped (label-private)
# ---------------------------------------------------------------------------

def u_shaped(model: sp.SegModel, cut1: int, cut2: int) -> Topology:
    def init(key):
        full = model.init(key)
        client = {"head": model.param_slice(full, 0, cut1),
                  "tail": model.param_slice(full, cut2, model.n_segments)}
        return client, model.param_slice(full, cut1, cut2)

    def turn_grads_wires(pc, ps, batch, loss_fn, wires):
        loss, g_head, g_mid, g_tail, _ = sp.u_shaped_grads(
            model, cut1, cut2, pc["head"], ps, pc["tail"],
            batch["x"], batch["labels"], loss_fn, wires)
        return loss, {"head": g_head, "tail": g_tail}, g_mid

    def evaluate(pc, ps, batch):
        act = model.apply_range(pc["head"], batch["x"], 0, cut1)
        act = sp._apply_mid(model, ps, act, cut1, cut2)
        return sp._apply_tail(model, pc["tail"], act, cut2)

    def pipeline_fwd(pc, batch):
        return model.apply_range(pc["head"], batch["x"], 0, cut1)

    def pipeline_rest(pc, ps, act1, batch, loss_fn, wires):
        act1 = sp.record(wires, "cut_act_1", act1, "up")
        act2, vjp_mid = jax.vjp(
            lambda p, a: sp._apply_mid(model, p, a, cut1, cut2), ps,
            sp.as_dense(act1))
        act2 = sp.record(wires, "cut_act_2", act2, "down")

        def tail_loss(p, a):
            return loss_fn(sp._apply_tail(model, p, a, cut2),
                           batch["labels"])

        loss, (g_tail, g_act2) = jax.value_and_grad(
            tail_loss, argnums=(0, 1))(pc["tail"], sp.as_dense(act2))
        g_act2 = sp.record(wires, "cut_grad_2", g_act2, "up")
        g_mid, g_act1 = vjp_mid(sp.as_dense(g_act2))
        g_act1 = sp.record(wires, "cut_grad_1", g_act1, "down")
        return loss, {"tail": g_tail}, g_mid, sp.as_dense(g_act1)

    def pipeline_bwd(pc, batch, g_act1, g_rest):
        _, vjp_head = jax.vjp(
            lambda p: model.apply_range(p, batch["x"], 0, cut1),
            pc["head"])
        (g_head,) = vjp_head(g_act1)
        return {"head": g_head, "tail": g_rest["tail"]}

    steps = _turn_steps(
        ir.ClientFwd(stage="head"),
        ir.SendCut(name="cut_act_1", direction="up"),
        ir.ServerFwdBwd(stage="mid"),
        ir.SendCut(name="cut_act_2", direction="down"),
        ir.ClientFwd(stage="tail"),
        ir.ClientBwd(stage="tail"),
        ir.RecvGrad(name="cut_grad_2", direction="up"),
        ir.RecvGrad(name="cut_grad_1", direction="down"),
        ir.ClientBwd(stage="head"))

    # client_fwd=None: the eager UShapedTrainer meters no FLOPs for the
    # label-private configuration (the client share is head+tail and the
    # tail fwd needs the mid activation, which a (pc, batch) probe cannot
    # see) — metering only the head would both undercount the true client
    # compute and diverge from the eager reference.
    return Topology(kind="u_shaped", init=init,
                    turn_grads=_drop_wires(turn_grads_wires),
                    turn_grads_wires=turn_grads_wires, evaluate=evaluate,
                    steps=steps, pipeline_fwd=pipeline_fwd,
                    pipeline_rest=pipeline_rest, pipeline_bwd=pipeline_bwd)


# ---------------------------------------------------------------------------
# helpers shared by the branch-per-client kinds
# ---------------------------------------------------------------------------

def _unstack_clients(clients, n):
    return [jax.tree_util.tree_map(lambda a, i=i: a[i], clients)
            for i in range(n)]


def _stack_grads(g_branches):
    return jax.tree_util.tree_map(lambda *gs: jnp.stack(gs), *g_branches)


# ---------------------------------------------------------------------------
# vertical (multi-modal, parallel-only)
# ---------------------------------------------------------------------------

def vertical(branch: sp.Branch, n_clients: int, trunk_init: Callable,
             trunk_apply: Callable) -> Topology:
    """K clients each hold one modality and one (structurally identical)
    feature branch; the server concatenates features into the trunk.
    Round-robin makes no sense here — every step needs all branches — so
    the engine forces schedule="parallel" via `round_grads`.

    Batch layout: {"x": (K, B, ...), "labels": (B,)} — modality i at
    x[i], labels aligned across clients (server-held)."""
    def init(key):
        kb, kt = jax.random.split(key)
        return branch.init(kb), trunk_init(kt)

    def round_grads_wires(clients, ps, batch, loss_fn, wires):
        params_list = _unstack_clients(clients, n_clients)
        xs = [batch["x"][i] for i in range(n_clients)]
        loss, g_branches, g_trunk, _ = sp.vertical_split_grads(
            [branch] * n_clients, params_list, trunk_apply, ps, xs,
            batch["labels"], loss_fn, wires)
        return loss, _stack_grads(g_branches), g_trunk

    def round_grads(clients, ps, batch, loss_fn):
        return round_grads_wires(clients, ps, batch, loss_fn, [])

    def evaluate(clients, ps, batch):
        feats = [branch.apply(pc, batch["x"][i]) for i, pc in
                 enumerate(_unstack_clients(clients, n_clients))]
        return trunk_apply(ps, jnp.concatenate(feats, axis=-1))

    steps = (_branch_fanin_steps(n_clients)
             + (ir.ServerFwdBwd(stage="trunk"),)
             + _branch_fanout_steps(n_clients))
    return Topology(kind="vertical", init=init,
                    turn_grads=None, turn_grads_wires=round_grads_wires,
                    evaluate=evaluate, round_grads=round_grads,
                    client_fwd=lambda pc, b: branch.apply(pc, b["x"][0]),
                    steps=steps)


# ---------------------------------------------------------------------------
# multi-hop (Tor-like)
# ---------------------------------------------------------------------------

def multihop(model: sp.SegModel, cuts: list[int]) -> Topology:
    """Slab chain [0,c0) | [c0,c1) | ... | [c_last, L).  The data-holding
    client owns the first slab; the downstream hops + loss are the
    "server" side (a tuple of slab trees), so N data clients can still
    round-robin against the shared chain."""
    cuts = list(cuts)

    def init(key):
        full = model.init(key)
        bounds = [0] + cuts + [model.n_segments]
        slabs = [model.param_slice(full, bounds[i], bounds[i + 1])
                 for i in range(len(bounds) - 1)]
        return slabs[0], tuple(slabs[1:])

    def turn_grads_wires(pc, ps, batch, loss_fn, wires):
        loss, grads, _ = sp.multihop_grads(
            model, cuts, [pc] + list(ps), batch["x"], batch["labels"],
            loss_fn, wires)
        return loss, grads[0], tuple(grads[1:])

    def evaluate(pc, ps, batch):
        bounds = [0] + cuts + [model.n_segments]
        act = batch["x"]
        for i, slab in enumerate([pc] + list(ps)):
            act = sp._apply_hop(model, slab, act, bounds[i], bounds[i + 1])
        return act

    def pipeline_fwd(pc, batch):
        return model.apply_range(pc, batch["x"], 0, cuts[0])

    def pipeline_rest(pc, ps, act, batch, loss_fn, wires):
        bounds = [0] + cuts + [model.n_segments]
        act = sp.as_dense(sp.record(wires, "hop_0_act", act, "up"))
        vjps = []
        for i in range(1, len(bounds) - 2):      # downstream relay hops
            lo, hi = bounds[i], bounds[i + 1]
            act, v = jax.vjp(
                lambda p, a, lo=lo, hi=hi: sp._apply_hop(model, p, a,
                                                         lo, hi),
                ps[i - 1], act)
            act = sp.as_dense(sp.record(wires, f"hop_{i}_act", act, "up"))
            vjps.append(v)
        lo, hi = bounds[-2], bounds[-1]

        def final_loss(p, a):
            return loss_fn(sp._apply_hop(model, p, a, lo, hi),
                           batch["labels"])

        loss, (g_last, g_act) = jax.value_and_grad(
            final_loss, argnums=(0, 1))(ps[-1], act)
        grads = [g_last]
        for i in reversed(range(1, len(bounds) - 2)):
            g_act = sp.record(wires, f"hop_{i}_grad", g_act, "down")
            g_slab, g_act = vjps[i - 1](sp.as_dense(g_act))
            grads.append(g_slab)
        g_act = sp.record(wires, "hop_0_grad", g_act, "down")
        return loss, {}, tuple(reversed(grads)), sp.as_dense(g_act)

    def pipeline_bwd(pc, batch, g_act, g_rest):
        _, vjp0 = jax.vjp(lambda p: pipeline_fwd(p, batch), pc)
        (g_c,) = vjp0(g_act)
        return g_c

    n_relay = len(cuts) - 1
    steps = _turn_steps(
        ir.ClientFwd(stage="hop_0"),
        ir.SendCut(name="hop_0_act", direction="up"),
        *[ir.SendCut(name=f"hop_{i}_act", direction="up", owner="server")
          for i in range(1, n_relay + 1)],
        ir.ServerFwdBwd(stage="chain"),
        *[ir.RecvGrad(name=f"hop_{i}_grad", direction="down",
                      owner="server")
          for i in reversed(range(1, n_relay + 1))],
        ir.RecvGrad(name="hop_0_grad", direction="down"),
        ir.ClientBwd(stage="hop_0"))

    return Topology(kind="multihop", init=init,
                    turn_grads=_drop_wires(turn_grads_wires),
                    turn_grads_wires=turn_grads_wires, evaluate=evaluate,
                    client_fwd=lambda pc, b: model.apply_range(
                        pc, b["x"], 0, cuts[0]),
                    steps=steps, pipeline_fwd=pipeline_fwd,
                    pipeline_rest=pipeline_rest, pipeline_bwd=pipeline_bwd)


# ---------------------------------------------------------------------------
# multi-task (paper §5.1 Fig. 4b, parallel-only)
# ---------------------------------------------------------------------------

def multitask(branch: sp.Branch, n_clients: int,
              head_inits: list[Callable],
              head_applies: list[Callable]) -> Topology:
    """K clients each hold one modality branch; the server concatenates
    the features and trains T task heads, each with its own labels.  One
    loss per task; the branch gradient is the SUM over tasks (exactly
    `core.split.multitask_grads`).

    Batch layout: {"x": (K, B, ...), "labels": (T, B)} — labels[t] are
    task t's targets, shared across clients (server-held)."""
    n_tasks = len(head_inits)

    def init(key):
        kb, *kh = jax.random.split(key, 1 + n_tasks)
        return branch.init(kb), tuple(hi(k) for hi, k in zip(head_inits, kh))

    def round_grads_wires(clients, ps, batch, loss_fn, wires):
        params_list = _unstack_clients(clients, n_clients)
        xs = [batch["x"][i] for i in range(n_clients)]
        labels_per_task = [batch["labels"][t] for t in range(n_tasks)]
        losses, g_branches, g_heads, _ = sp.multitask_grads(
            [branch] * n_clients, params_list, head_applies, list(ps), xs,
            labels_per_task, [loss_fn] * n_tasks, wires)
        return losses.mean(), _stack_grads(g_branches), tuple(g_heads)

    def round_grads(clients, ps, batch, loss_fn):
        return round_grads_wires(clients, ps, batch, loss_fn, [])

    def evaluate(clients, ps, batch):
        feats = jnp.concatenate(
            [branch.apply(pc, batch["x"][i]) for i, pc in
             enumerate(_unstack_clients(clients, n_clients))], axis=-1)
        # (T, B, C): engine accuracy broadcasts against (T, B) labels
        return jnp.stack([h(p, feats) for h, p in zip(head_applies, ps)])

    steps = (_branch_fanin_steps(n_clients)
             + (ir.ServerFwdBwd(stage="heads"),
                ir.Aggregate(what="sum_task_grads"))
             + _branch_fanout_steps(n_clients))
    return Topology(kind="multitask", init=init,
                    turn_grads=None, turn_grads_wires=round_grads_wires,
                    evaluate=evaluate, round_grads=round_grads,
                    client_fwd=lambda pc, b: branch.apply(pc, b["x"][0]),
                    steps=steps)


# ---------------------------------------------------------------------------
# extended vanilla (paper §5.1 Fig. 4a, parallel-only)
# ---------------------------------------------------------------------------

def extended_vanilla(branch: sp.Branch, n_clients: int,
                     mid_init: Callable, mid_apply: Callable,
                     trunk_init: Callable, trunk_apply: Callable) -> Topology:
    """Like `vertical`, but the concatenated features pass through an
    INTERMEDIATE client's network before reaching the server trunk.  The
    mid + trunk parameters live on the engine's server side as
    {"mid", "trunk"}; the mid_act / mid_grad wires are the intermediate
    client's traffic, not billed to the K data clients (mirrors the
    multihop downstream-hop convention).

    Batch layout: {"x": (K, B, ...), "labels": (B,)}."""
    def init(key):
        kb, km, kt = jax.random.split(key, 3)
        return branch.init(kb), {"mid": mid_init(km), "trunk": trunk_init(kt)}

    def round_grads_wires(clients, ps, batch, loss_fn, wires):
        params_list = _unstack_clients(clients, n_clients)
        xs = [batch["x"][i] for i in range(n_clients)]
        loss, g_branches, g_mid, g_trunk, _ = sp.extended_vanilla_grads(
            [branch] * n_clients, params_list, mid_apply, ps["mid"],
            trunk_apply, ps["trunk"], xs, batch["labels"], loss_fn, wires)
        return loss, _stack_grads(g_branches), {"mid": g_mid,
                                                "trunk": g_trunk}

    def round_grads(clients, ps, batch, loss_fn):
        return round_grads_wires(clients, ps, batch, loss_fn, [])

    def evaluate(clients, ps, batch):
        feats = jnp.concatenate(
            [branch.apply(pc, batch["x"][i]) for i, pc in
             enumerate(_unstack_clients(clients, n_clients))], axis=-1)
        return trunk_apply(ps["trunk"], mid_apply(ps["mid"], feats))

    steps = (_branch_fanin_steps(n_clients)
             + (ir.ClientFwd(stage="mid"),
                ir.SendCut(name="mid_act", direction="up", owner="mid"),
                ir.ServerFwdBwd(stage="trunk"),
                ir.RecvGrad(name="mid_grad", direction="down", owner="mid"),
                ir.ClientBwd(stage="mid"))
             + _branch_fanout_steps(n_clients))
    return Topology(kind="extended_vanilla", init=init,
                    turn_grads=None, turn_grads_wires=round_grads_wires,
                    evaluate=evaluate, round_grads=round_grads,
                    client_fwd=lambda pc, b: branch.apply(pc, b["x"][0]),
                    steps=steps)
