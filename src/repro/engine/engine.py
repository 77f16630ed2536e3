"""Compiled multi-client round engine — a thin executor selection over
the step-program IR.

The seed trainers drove every client turn as an eager Python loop; the
engine stacks the N client pytrees along a leading client axis and runs
ONE WHOLE ROUND as a single compiled program.  Since the IR refactor the
engine owns no schedule or mode dispatch of its own: the topology lowers
to a `repro.engine.program.StepProgram` once, and `schedule=` picks the
interpreter —

  schedule="round_robin"  — `program.run_serial`: `jax.lax.scan` over
      client turns, preserving the paper's serial round-robin + p2p
      weight-handoff semantics inside the scan carry;
  schedule="parallel"     — `program.run_parallel`: SplitFed-style
      (Thapa et al., AAAI 2022) vmap of all client turns, server steps
      on the mean cut gradient;
  schedule="pipelined"    — `program.run_pipelined`: each client batch
      splits into `microbatches` microbatches double-buffered across
      the cut (the server works on microbatch m while the client
      computes m+1's forward — a staged-carry `lax.scan`); M=1
      reproduces the serial math, M>=2 is the schedule the pre-IR
      engines could not express.

Branch fan-in topologies (vertical / multitask / extended_vanilla) have
no turn axis; their joint round runs through `program.run_branch`
whatever the schedule names.

Resource accounting stays exact under jit: wire shapes are static per
(topology, batch shape), so the engine traces ONE probe
(`accounting.probe_wire_records`) and then accumulates `TurnCost`s
analytically per turn.  WHICH crossings each client pays for is read
off the program's `SendCut`/`RecvGrad` edges (`program.billed_wires`)
— the billing metadata lives on the IR, not in per-engine dispatch —
and byte/FLOP totals match the eager `Meter` path bit-for-bit
(tests/test_engine.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.accounting import (Meter, TurnCost, bytes_of_tree,
                                   flops_of_fn, probe_wire_records)
from repro.engine.program import (EXECUTORS, ExecContext, run_branch,
                                  run_branch_pipelined, stack_trees,
                                  tree_index)
from repro.engine.topology import Topology, lower

SCHEDULES = ("round_robin", "parallel", "pipelined")


@dataclasses.dataclass
class RoundEngine:
    """One compiled training round over N split-learning clients."""
    topology: Topology
    loss_fn: Callable
    optimizer_client: "Optimizer"
    optimizer_server: "Optimizer"
    n_clients: int
    schedule: str = "round_robin"       # see SCHEDULES
    sync: str = "p2p"                   # "p2p" | "none"  (serial/pipelined)
    wire_stack: Any = None              # repro.api.wire.WireStack | None
    microbatches: int = 1               # pipelined schedule only

    def __post_init__(self):
        if self.schedule == "serial":       # IR executor name, accepted
            self.schedule = "round_robin"
        if self.schedule not in SCHEDULES:
            raise ValueError(f"schedule must be one of {SCHEDULES}")
        if self.topology.parallel_only and self.schedule == "round_robin":
            raise ValueError(
                f"{self.topology.kind} topology is parallel-only")
        if self.microbatches < 1:
            raise ValueError("microbatches must be >= 1")
        if self.microbatches > 1 and self.schedule != "pipelined":
            raise ValueError("microbatches > 1 requires "
                             "schedule='pipelined'")
        if (self.schedule == "pipelined"
                and not self.topology.parallel_only
                and self.topology.pipeline_fwd is None):
            raise ValueError(
                f"{self.topology.kind} topology exposes no staged turn "
                "(pipeline_fwd/rest/bwd) — pipelined schedule unavailable")
        self.meter = Meter(self.n_clients)
        self.rounds = 0                 # rounds run (the span's `round`)
        self.host_reads = 0             # device values read on the host
        self._client_param_bytes = 0
        self._turn_costs: dict = {}     # batch-shape key -> TurnCost
        # p2p handoff middleware: transforms flagged handoff=True squeeze
        # the previously-trained client's weights through the wire before
        # the next client adopts them (identical math for the fake and
        # physical quantizers — the fleet engine additionally moves the
        # PACKED form over its ppermute ring)
        stack = self.wire_stack
        self._wire_handoff = bool(stack is not None
                                  and getattr(stack, "has_handoff", False))
        # ONE lowering, many interpreters: the program carries the step
        # sequence (wire edges + billing) and the staged callables
        self.program = lower(self.topology)
        self._ctx = ExecContext(
            n_clients=self.n_clients, sync=self.sync, loss_fn=self.loss_fn,
            optimizer_client=self.optimizer_client,
            optimizer_server=self.optimizer_server,
            wire_stack=self.wire_stack, wire_handoff=self._wire_handoff,
            microbatches=self.microbatches)
        # the incoming train-state is donated: XLA reuses its buffers for
        # the round's output instead of allocating a full copy per round
        self._round_jit = jax.jit(self._round, donate_argnums=(0,))

    # ---- state ------------------------------------------------------------

    def init(self, key, *, identical_clients: bool = True):
        """Stacked engine state.  identical_clients=True reproduces the
        paper setting (every client starts from the same init — what the
        eager trainers do); False gives each client its own init (the
        natural choice for vertical modality branches)."""
        if identical_clients:
            pc, ps = self.topology.init(key)
            clients = stack_trees([pc] * self.n_clients)
        else:
            keys = jax.random.split(key, self.n_clients)
            inits = [self.topology.init(k) for k in keys]
            clients = stack_trees([pc for pc, _ in inits])
            ps = inits[0][1]
        self._client_param_bytes = bytes_of_tree(clients) // self.n_clients
        opt_c = stack_trees(
            [self.optimizer_client.init(tree_index(clients, i))
             for i in range(self.n_clients)])
        return {"clients": clients, "server": ps,
                "opt_c": opt_c, "opt_s": self.optimizer_server.init(ps),
                "last_trained": jnp.asarray(-1, jnp.int32)}

    # ---- one compiled round ----------------------------------------------

    def run_round(self, state, batches):
        """batches: dict of (N, ...) arrays (see stack_batches), except
        vertical where labels are shared: {"x": (N,B,...), "labels": (B,)}.
        Returns (state, per-turn losses (N,)).  Also meters the round.

        Host spans `repro.engine.*` (recorded only while a profiler
        session is active) name the round's parts; `host_reads` counts
        the device values it reads back."""
        with TraceAnnotation("repro.engine.run_round", round=self.rounds):
            with TraceAnnotation("repro.engine.host_read"):
                first = bool(state["last_trained"] < 0)
            self.host_reads += 1
            with TraceAnnotation("repro.engine.turn_cost"):
                self.turn_cost(state, batches)      # probe once per shape
            with TraceAnnotation("repro.engine.launch"):
                state, losses = self._round_jit(state, batches)
            with TraceAnnotation("repro.engine.account"):
                self._account_round(state, batches, first_round=first)
        self.rounds += 1
        return state, losses

    def _round(self, state, batches):
        prog, ctx = self.program, self._ctx
        if prog.round_type == "branch":
            if self.schedule == "pipelined" and self.microbatches > 1:
                return run_branch_pipelined(prog, ctx, state, batches)
            return run_branch(prog, ctx, state, batches)
        return EXECUTORS[self.schedule](prog, ctx, state, batches)

    # ---- jit-safe resource accounting -------------------------------------

    def turn_cost(self, state, batches) -> TurnCost:
        """Static per-turn `TurnCost` for this batch shape.  One traced
        probe (`probe_wire_records` under eval_shape + one XLA cost-model
        query for the client forward) per shape; every later round is
        pure arithmetic — nothing is appended inside traced code."""
        key = tuple(sorted((k, tuple(v.shape), str(v.dtype))
                           for k, v in batches.items()))
        if key not in self._turn_costs:
            one = (batches if self.topology.parallel_only
                   else {k: v[0] for k, v in batches.items()})
            pc = tree_index(state["clients"], 0)
            side = (state["clients"] if self.topology.parallel_only else pc)
            wires = probe_wire_records(
                lambda pc_, ps_, b_, w: self.topology.turn_grads_wires(
                    pc_, ps_, b_, self.loss_fn, w),
                side, state["server"], one)
            flops = 0.0
            if self.topology.client_fwd is not None:
                flops = 3.0 * flops_of_fn(self.topology.client_fwd, pc, one)
            if not self._client_param_bytes:
                self._client_param_bytes = (
                    bytes_of_tree(state["clients"]) // self.n_clients)
            # the p2p handoff is wire traffic too: price it through the
            # stack's handoff transforms (int8 + row scales under
            # quantize_int8) instead of the dense param bytes
            sync_bytes = (self.wire_stack.handoff_bytes(pc)
                          if self._wire_handoff
                          else self._client_param_bytes)
            self._turn_costs[key] = TurnCost(
                wires=tuple(wires), flops=flops, sync_bytes=sync_bytes)
        return self._turn_costs[key]

    def _account_round(self, state, batches, *, first_round: bool):
        """Bill the round from the program's wire edges: each client
        pays for the `SendCut`/`RecvGrad` steps whose `owner`/`client`
        metadata point at it (`program.billed_wires`) — relay traffic
        (multihop downstream hops, the extended_vanilla intermediate
        client) stays unbilled, exactly as the eager meters do."""
        cost = self.turn_cost(state, batches)
        by_name: dict = {}
        for w in cost.wires:
            by_name.setdefault(w.name, []).append(w)
        handoff = (self.schedule in ("round_robin", "pipelined")
                   and self.program.round_type == "turn"
                   and self.sync == "p2p" and self.n_clients > 1)
        for ci in range(self.n_clients):
            self.meter.add_flops(ci, cost.flops)
            self.meter.add_wires(ci, [
                w for name in self.program.billed_wires(ci)
                for w in by_name.get(name, ())])
            if handoff and not (first_round and ci == 0):
                self.meter.sync_bytes[ci] += cost.sync_bytes

    # ---- eval --------------------------------------------------------------

    def evaluate(self, state, batch, *, client: int = 0):
        if self.topology.parallel_only:
            logits = self.topology.evaluate(
                state["clients"], state["server"], batch)
        else:
            pc = jax.tree_util.tree_map(lambda a: a[client],
                                        state["clients"])
            logits = self.topology.evaluate(pc, state["server"], batch)
        return (jnp.argmax(logits, -1) == batch["labels"]).mean()

    def evaluate_all(self, state, batch):
        """Per-client accuracy over the WHOLE stacked client axis in one
        vmapped forward — clients diverge under the parallel schedule,
        so evaluating only client 0 hides the fleet's spread.  Branch
        fan-in kinds have a single joint fleet: shape (1,) there,
        (n_clients,) otherwise."""
        if self.topology.parallel_only:
            return self.evaluate(state, batch)[None]
        accs = jax.vmap(
            lambda pc: (jnp.argmax(
                self.topology.evaluate(pc, state["server"], batch),
                -1) == batch["labels"]).mean())(state["clients"])
        return accs
