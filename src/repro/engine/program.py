"""The step-program IR: one typed lowering under every engine.

Every Plan mode — the six split topologies plus the two baselines —
lowers (`repro.engine.topology.lower` / `lower_baseline`) into ONE
`StepProgram`: a typed sequence of `Step`s describing a single logical
client turn (or joint round), with the wire crossings (`SendCut` /
`RecvGrad`) and weight movements (`WeightHandoff`) as first-class
*edges*.  Wire middleware and `TurnCost` accounting attach to those
edges — `billed_wires` tells the meter which crossings each client pays
for, replacing the per-engine `kind`-dispatch the engines used to
copy-paste.

Executors are interchangeable interpreters of the same program:

  run_serial    — the paper's round-robin as `lax.scan` over client
                  turns (bit-identical to the pre-IR scan engine);
  run_parallel  — SplitFed-style `vmap` of all turns at once, server
                  steps on the mean cut gradient;
  run_branch    — the joint round of the branch fan-in kinds
                  (vertical / multitask / extended_vanilla);
  run_pipelined — NEW: each client batch splits into M microbatches and
                  double-buffers across the cut — the server consumes
                  microbatch m's staged activation while the client
                  computes microbatch m+1's forward, expressed as a
                  `lax.scan` over a staged (activation, microbatch)
                  carry.  Gradients accumulate over the M microbatches
                  and each party still steps once per turn, so M=1
                  reproduces the serial schedule's math exactly and
                  M>=2 is equal in exact arithmetic (mean-reduction
                  losses make the mean of microbatch gradients the
                  full-batch gradient).  The client loop is unrolled
                  statically: the p2p handoff becomes straight-line
                  dataflow (no dynamic gather/scatter, no masked
                  select), which is where the schedule's single-host
                  speedup comes from; on multi-party hardware the same
                  program overlaps the two sides' compute for real.

The executors interpret the program through the staged callables the
lowering attached (`Topology.pipeline_fwd/rest/bwd`, `turn_grads`,
`round_grads`) — they own scheduling only, never mode dispatch.

Device scopes.  Each part of a turn runs under a `jax.named_scope`
named by its step class (`scope(ClientFwd)` -> "ClientFwd"; the turn
functions in `core.split` and `topology.vanilla_fns`, the handoff in
the executors), and the optimizer step under `OPTIMIZER_SCOPE`: both
optimizers' updates, `apply_updates`, and the write-back of the turn's
client weights and optimizer state into the stacks.  The scope lands in
each HLO instruction's `op_name` metadata, e.g.
`jit(_round)/while/body/closed_call/ClientFwd/jvp()/conv_general_dilated`.
A backward op of `jax.vjp` carries the scope its vjp was CALLED in
(`.../ClientBwd/transpose(jvp())/...`); under `jax.grad` of a function
that opens the scope itself, it carries the forward's inside the
transform (`transpose(jvp(ClientFwd))`).  The rule that maps an
`op_name` to one step: the last path component that names a scope
wins, and `ClientFwd` under `transpose(` reads as `ClientBwd`.  A
profile of the round then splits device time by step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.optim import apply_updates

# the device scope of the optimizer step (see the module docstring)
OPTIMIZER_SCOPE = "optimizer"

# ---------------------------------------------------------------------------
# stacked-pytree helpers (canonical home; repro.engine re-exports)
# ---------------------------------------------------------------------------


def stack_trees(trees: list):
    """[tree] * N -> tree with a leading client axis on every leaf."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def unstack_tree(tree, n: int) -> list:
    """Inverse of stack_trees (static n)."""
    return [jax.tree_util.tree_map(lambda a: a[i], tree) for i in range(n)]


def tree_index(tree, i):
    """Dynamic (traced-index) slice of the leading client axis."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def tree_update(tree, i, sub):
    return jax.tree_util.tree_map(
        lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s, i, 0),
        tree, sub)


def tree_at(tree, i: int):
    """Static slice of the leading client axis (python int index)."""
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def tree_set(tree, i: int, sub):
    """Static update of the leading client axis (python int index)."""
    return jax.tree_util.tree_map(lambda a, s: a.at[i].set(s), tree, sub)


def stack_batches(batches: list[dict]) -> dict:
    """[per-client batch dict] -> dict of (N, ...) arrays."""
    return {k: jnp.stack([b[k] for b in batches]) for k in batches[0]}


def copy_tree(tree):
    """Leafwise device copy — gives a state tree its OWN buffers.  The
    engines donate their input state to XLA (buffer reuse instead of a
    per-round copy), so a state built from another tree's leaves must
    not share them."""
    return jax.tree_util.tree_map(jnp.copy, tree)


def stack_state(state: dict, n: int) -> dict:
    """List-of-trees trainer state -> stacked engine state.  The single
    canonical copy (core.protocol's shims and the tests use it).  The
    non-stacked leaves are COPIED, not shared: the compiled round
    donates its input buffers."""
    return {"clients": stack_trees(state["clients"]),
            "server": copy_tree(state["server"]),
            "opt_c": stack_trees(state["opt_c"]),
            "opt_s": copy_tree(state["opt_s"]),
            "last_trained": jnp.asarray(state["last_trained"], jnp.int32)}


def unstack_state(est: dict, n: int) -> dict:
    return {"clients": unstack_tree(est["clients"], n),
            "server": est["server"],
            "opt_c": unstack_tree(est["opt_c"], n),
            "opt_s": est["opt_s"],
            "last_trained": int(est["last_trained"])}


# ---------------------------------------------------------------------------
# the typed steps
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Step:
    """One typed step of a round program."""

    def describe(self) -> str:
        name = type(self).__name__
        bits = [f"{f.name}={getattr(self, f.name)!r}"
                for f in dataclasses.fields(self)
                if getattr(self, f.name) != f.default]
        return f"{name}({', '.join(bits)})" if bits else name


@dataclasses.dataclass(frozen=True)
class ClientFwd(Step):
    """A client-side forward (`stage` names which client network)."""
    stage: str = "client"      # "client" | "head" | "tail" | "hop_0" | ...
    client: int | None = None  # branch index (branch kinds only)
    repeats: int = 1           # fedavg: local_steps full fwd/bwd passes


@dataclasses.dataclass(frozen=True)
class SendCut(Step):
    """An activation crossing the cut — a wire edge.  `name` is the
    `WireRecord` name the middleware stack and `TurnCost` price; `owner`
    says whose traffic it is ("client" = billed to the turn's client, or
    to branch client `client`; "server"/"mid" = peer-side relay,
    unbilled)."""
    name: str = "cut_act"
    direction: str = "up"
    owner: str = "client"
    client: int | None = None


@dataclasses.dataclass(frozen=True)
class RecvGrad(Step):
    """A cut-gradient crossing back — the matching wire edge."""
    name: str = "cut_grad"
    direction: str = "down"
    owner: str = "client"
    client: int | None = None


@dataclasses.dataclass(frozen=True)
class ServerFwdBwd(Step):
    """The server-side forward + backward between wire edges."""
    stage: str = "server"


@dataclasses.dataclass(frozen=True)
class ClientBwd(Step):
    """A client-side backward from a received cut gradient."""
    stage: str = "client"
    client: int | None = None


@dataclasses.dataclass(frozen=True)
class Aggregate(Step):
    """A cross-party reduction (feature concat, task-grad sum, model or
    gradient mean, optimizer step boundary)."""
    what: str = "step"


@dataclasses.dataclass(frozen=True)
class WeightHandoff(Step):
    """A whole-parameter-tree movement — the round-robin p2p handoff or
    a baseline's model pull/push — also a priced wire edge."""
    name: str = "p2p_handoff"
    direction: str = "p2p"
    when: str = "always"       # "sync=p2p": only under the p2p schedule


WIRE_STEPS = (SendCut, RecvGrad)


def scope(step: type):
    """The device scope of one IR step class: ops traced under it carry
    the class name in their `op_name` (module docstring)."""
    return jax.named_scope(step.__name__)


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StepProgram:
    """One mode, lowered: the typed step sequence for a single logical
    turn (turn kinds) or joint round (branch kinds / baselines), plus
    the compute callables executors interpret."""
    kind: str                      # one of the 8 Plan modes
    round_type: str                # "turn" | "branch" | "fedavg" | "large_batch"
    steps: tuple
    topology: Any = None           # the (wire-wrapped) Topology, split modes
    split_batch: Callable | None = None   # (batch, M) -> (M, ...) microbatches

    def describe(self) -> tuple:
        """Compact step strings — the golden-test surface."""
        return tuple(s.describe() for s in self.steps)

    def wire_steps(self) -> tuple:
        return tuple(s for s in self.steps if isinstance(s, WIRE_STEPS))

    def handoff_steps(self) -> tuple:
        return tuple(s for s in self.steps if isinstance(s, WeightHandoff))

    def billed_wires(self, client: int) -> tuple:
        """Names of the wire crossings client `client` pays for — the
        accounting attachment point (replaces per-engine kind dispatch)."""
        return tuple(
            s.name for s in self.wire_steps()
            if s.owner == "client" and s.client in (None, client))


@dataclasses.dataclass(frozen=True)
class ExecContext:
    """Everything an executor needs beyond the program: party count,
    sync policy, optimizers, the wire stack, and the microbatch count
    for the pipelined schedule."""
    n_clients: int
    sync: str
    loss_fn: Callable
    optimizer_client: Any
    optimizer_server: Any
    wire_stack: Any = None
    wire_handoff: bool = False
    microbatches: int = 1


# ---------------------------------------------------------------------------
# microbatch splitting
# ---------------------------------------------------------------------------


def split_turn_batch(batch: dict, m: int) -> dict:
    """One client's batch (leading axis B) -> (M, B/M, ...) microbatches."""
    def leaf(a):
        if a.shape[0] % m:
            raise ValueError(
                f"pipelined schedule: batch axis {a.shape[0]} must divide "
                f"evenly into microbatches={m}")
        return a.reshape(m, a.shape[0] // m, *a.shape[1:])
    return {k: leaf(v) for k, v in batch.items()}


def microbatch_mean(fn: Callable, batch: dict, m: int,
                    split_batch: Callable | None = None):
    """Run `fn(microbatch)` over the M microbatches of `batch` under
    `lax.scan` and return the leafwise MEAN of its outputs — the one
    accumulation primitive every pipelined gradient path shares (the
    branch joint round here, the baselines' local/sync gradients in
    `repro.api.baseline`).  For mean-reduction losses the mean of
    microbatch gradients equals the full-batch gradient."""
    mbs = (split_batch or split_turn_batch)(batch, m)
    _, outs = lax.scan(lambda _, mb: (0, fn(mb)), 0, mbs)
    return jax.tree_util.tree_map(lambda a: a.mean(0), outs)


def split_branch_batch(batch: dict, m: int) -> dict:
    """Branch-kind joint batch {"x": (K, B, ...), "labels": (B,)|(T, B)}
    -> the same layout per microbatch, stacked on a leading M axis."""
    x = batch["x"]
    if x.shape[1] % m:
        raise ValueError(
            f"pipelined schedule: batch axis {x.shape[1]} must divide "
            f"evenly into microbatches={m}")
    out = dict(batch)
    out["x"] = jnp.moveaxis(
        x.reshape(x.shape[0], m, x.shape[1] // m, *x.shape[2:]), 1, 0)
    lab = batch["labels"]
    if lab.ndim == 1:                        # shared labels (B,)
        out["labels"] = lab.reshape(m, lab.shape[0] // m)
    else:                                    # multitask labels (T, B)
        out["labels"] = jnp.moveaxis(
            lab.reshape(lab.shape[0], m, lab.shape[1] // m), 1, 0)
    return out


# ---------------------------------------------------------------------------
# executors: interchangeable interpreters of one program
# ---------------------------------------------------------------------------


def run_serial(program: StepProgram, ctx: ExecContext, state, batches):
    """Round-robin as `lax.scan`; carry = (clients, opt_c, server,
    opt_s, last_trained).  Bit-identical to the pre-IR scan engine."""
    topo = program.topology
    n, sync = ctx.n_clients, ctx.sync

    def body(carry, inp):
        ci, batch = inp
        clients, opt_c, server, opt_s, last = carry
        pc = tree_index(clients, ci)
        if sync == "p2p" and n > 1:
            # pull the last trained client's weights (p2p handoff);
            # with wire middleware the payload crosses the same
            # quantized wire the cut activations do
            with scope(WeightHandoff):
                prev = tree_index(clients, jnp.maximum(last, 0))
                if ctx.wire_handoff:
                    prev = ctx.wire_stack.handoff_recv(prev)
                take = (last >= 0) & (last != ci)
                pc = jax.tree_util.tree_map(
                    lambda own, pv: jnp.where(take, pv, own), pc, prev)
        loss, g_c, g_s = topo.turn_grads(pc, server, batch, ctx.loss_fn)
        with jax.named_scope(OPTIMIZER_SCOPE):
            ups_c, oc = ctx.optimizer_client.update(
                g_c, tree_index(opt_c, ci), pc)
            pc = apply_updates(pc, ups_c)
            ups_s, opt_s = ctx.optimizer_server.update(g_s, opt_s, server)
            server = apply_updates(server, ups_s)
            clients = tree_update(clients, ci, pc)
            opt_c = tree_update(opt_c, ci, oc)
        return (clients, opt_c, server, opt_s, ci), loss

    carry = (state["clients"], state["opt_c"], state["server"],
             state["opt_s"], state["last_trained"])
    (clients, opt_c, server, opt_s, last), losses = jax.lax.scan(
        body, carry, (jnp.arange(n, dtype=jnp.int32), batches))
    return {"clients": clients, "server": server, "opt_c": opt_c,
            "opt_s": opt_s, "last_trained": last}, losses


def run_parallel(program: StepProgram, ctx: ExecContext, state, batches):
    """SplitFed: vmap client turns, server steps on the MEAN cut
    gradient; no p2p handoff (clients stay independent)."""
    topo = program.topology
    losses, g_c, g_s = jax.vmap(
        lambda pc, b: topo.turn_grads(pc, state["server"], b, ctx.loss_fn),
        in_axes=(0, 0))(state["clients"], batches)
    g_s_mean = jax.tree_util.tree_map(lambda g: g.mean(0), g_s)
    return _branch_step(ctx, state, losses, g_c, g_s_mean)


def run_branch(program: StepProgram, ctx: ExecContext, state, batches):
    """Branch fan-in kinds: all K branches contribute to ONE step;
    client grads come back stacked from the topology."""
    loss, g_c, g_s = program.topology.round_grads(
        state["clients"], state["server"], batches, ctx.loss_fn)
    return _branch_step(ctx, state, loss[None], g_c, g_s)


def run_branch_pipelined(program: StepProgram, ctx: ExecContext, state,
                         batches):
    """Branch fan-in kinds under the pipelined schedule: the joint batch
    splits into M microbatches scanned through the same round_grads;
    gradients accumulate (mean) and each party steps ONCE — M=1 is
    exactly `run_branch`."""
    topo = program.topology
    loss, g_c, g_s = microbatch_mean(
        lambda mb: topo.round_grads(state["clients"], state["server"],
                                    mb, ctx.loss_fn),
        batches, ctx.microbatches, program.split_batch)
    return _branch_step(ctx, state, loss[None], g_c, g_s)


def _branch_step(ctx, state, losses, g_c, g_s):
    """Every client steps on its own stacked gradient, the server once
    on `g_s`."""
    with jax.named_scope(OPTIMIZER_SCOPE):
        ups_c, opt_c = jax.vmap(ctx.optimizer_client.update)(
            g_c, state["opt_c"], state["clients"])
        clients = apply_updates(state["clients"], ups_c)
        ups_s, opt_s = ctx.optimizer_server.update(
            g_s, state["opt_s"], state["server"])
        server = apply_updates(state["server"], ups_s)
    return {"clients": clients, "server": server, "opt_c": opt_c,
            "opt_s": opt_s, "last_trained": state["last_trained"]}, losses


def run_pipelined(program: StepProgram, ctx: ExecContext, state, batches):
    """The microbatch-pipelined round-robin.  Turn order, p2p handoff
    and one optimizer step per party per turn all match `run_serial`;
    within each turn the batch streams through the cut as M microbatches
    double-buffered by `_pipelined_turn`.  The client loop is unrolled
    statically, so the handoff is plain dataflow — client k+1's adopted
    weights are client k's post-step output, no masked select — and
    only the round boundary (client 0 adopting `last_trained`) keeps the
    traced select the serial carry needs."""
    if program.round_type == "branch":
        if ctx.microbatches == 1:
            return run_branch(program, ctx, state, batches)
        return run_branch_pipelined(program, ctx, state, batches)
    topo = program.topology
    n, m = ctx.n_clients, ctx.microbatches
    sync = ctx.sync == "p2p" and n > 1
    clients, opt_c = state["clients"], state["opt_c"]
    server, opt_s = state["server"], state["opt_s"]
    last = state["last_trained"]
    losses, prev_pc = [], None
    for ci in range(n):
        batch = {k: v[ci] for k, v in batches.items()}
        pc = tree_at(clients, ci)
        if sync:
            with scope(WeightHandoff):
                if prev_pc is None:
                    # round boundary: adopt the globally last-trained
                    # client's weights (masked out before the first turn)
                    prev = tree_index(clients, jnp.maximum(last, 0))
                    if ctx.wire_handoff:
                        prev = ctx.wire_stack.handoff_recv(prev)
                    take = (last >= 0) & (last != ci)
                    pc = jax.tree_util.tree_map(
                        lambda own, pv: jnp.where(take, pv, own), pc, prev)
                else:
                    pc = (ctx.wire_stack.handoff_recv(prev_pc)
                          if ctx.wire_handoff else prev_pc)
        loss, g_c, g_s = _pipelined_turn(topo, ctx.loss_fn, pc, server,
                                         batch, m, program.split_batch)
        with jax.named_scope(OPTIMIZER_SCOPE):
            ups_c, oc = ctx.optimizer_client.update(g_c, tree_at(opt_c, ci),
                                                    pc)
            pc = apply_updates(pc, ups_c)
            ups_s, opt_s = ctx.optimizer_server.update(g_s, opt_s, server)
            server = apply_updates(server, ups_s)
            clients = tree_set(clients, ci, pc)
            opt_c = tree_set(opt_c, ci, oc)
        prev_pc = pc
        losses.append(loss)
    return {"clients": clients, "server": server, "opt_c": opt_c,
            "opt_s": opt_s,
            "last_trained": jnp.asarray(n - 1, jnp.int32)}, jnp.stack(losses)


def _pipelined_turn(topo, loss_fn, pc, ps, batch, m, split_batch):
    """One client turn as an M-deep software pipeline across the cut.

    The `lax.scan` carry stages (activation, microbatch) — the double
    buffer: at slot j the server consumes microbatch j-1's STAGED
    activation (fwd/bwd to its cut gradient) while the client computes
    microbatch j's forward.  Client backwards rematerialize their
    forward from the staged cut gradients (standard 1F1B remat — client
    weights are constant within the turn, so recompute is exact) and
    run vmapped over the M microbatches once the pipeline drains.
    Gradients are the microbatch mean; the loss is the mean microbatch
    loss (equal to the full-batch loss for mean-reduction losses)."""
    fwd, rest, bwd = topo.pipeline_fwd, topo.pipeline_rest, topo.pipeline_bwd
    if m == 1:                       # no pipeline: exactly the serial math
        act = fwd(pc, batch)
        loss, g_rest, g_s, g_act = rest(pc, ps, act, batch, loss_fn, [])
        return loss, bwd(pc, batch, g_act, g_rest), g_s
    mbs = split_batch(batch, m)
    mb0 = {k: v[0] for k, v in mbs.items()}
    tail = {k: v[1:] for k, v in mbs.items()}
    act0 = fwd(pc, mb0)              # pipeline fill

    def body(carry, mb):
        act_prev, mb_prev = carry
        # the staged buffer: server fwd/bwd on microbatch j-1 ...
        loss, g_rest, g_s, g_act = rest(pc, ps, act_prev, mb_prev,
                                        loss_fn, [])
        # ... overlapped with the client forward of microbatch j
        act = fwd(pc, mb)
        return (act, mb), (loss, g_rest, g_s, g_act)

    (act_l, mb_l), (ls, g_rests, g_ss, g_acts) = lax.scan(
        body, (act0, mb0), tail)
    # drain: the last staged activation
    loss_l, g_rest_l, g_s_l, g_act_l = rest(pc, ps, act_l, mb_l, loss_fn, [])
    cat = lambda s, x: jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b[None]]), s, x)
    ls = jnp.concatenate([ls, loss_l[None]])
    g_s = jax.tree_util.tree_map(
        lambda a, b: (a.sum(0) + b) / m, g_ss, g_s_l)
    g_acts, g_rests = cat(g_acts, g_act_l), cat(g_rests, g_rest_l)
    g_cs = jax.vmap(lambda mb, ga, gr: bwd(pc, mb, ga, gr))(
        mbs, g_acts, g_rests)
    g_c = jax.tree_util.tree_map(lambda a: a.mean(0), g_cs)
    return ls.mean(), g_c, g_s


EXECUTORS = {
    "round_robin": run_serial,
    "serial": run_serial,
    "parallel": run_parallel,
    "pipelined": run_pipelined,
}

__all__ = [
    "Step", "ClientFwd", "SendCut", "ServerFwdBwd", "RecvGrad", "ClientBwd",
    "Aggregate", "WeightHandoff", "StepProgram", "ExecContext", "EXECUTORS",
    "OPTIMIZER_SCOPE", "scope",
    "run_serial", "run_parallel", "run_branch", "run_branch_pipelined",
    "run_pipelined", "split_turn_batch", "split_branch_batch",
    "stack_trees", "unstack_tree", "tree_index", "tree_update", "tree_at",
    "tree_set", "stack_batches", "copy_tree", "stack_state", "unstack_state",
]
