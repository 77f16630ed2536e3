"""Faults planted in a training session's compiled round, for the
checks that must see `correct` come out false (the benchmark's tests,
and `bench/tools/calibrate.py`, which reads their numbers on the chip).

Each takes a compiled `repro.api.Session` and replaces its engine's
jitted round (`engine._round_jit`, what `Session.run_round` calls) with
a faulty one that donates the incoming state as the engine's own does;
the window and the check then run as in a sound run.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def _jit(fn):
    # without the donation, XLA places one of the 4L cell's handoff
    # kernels' operands so that the kernel runs out of VMEM on the chip
    return jax.jit(fn, donate_argnums=(0,))


def state_unchanged(sess):
    """The round returns the state it was given."""
    eng = sess.engine
    orig = eng._round
    eng._round_jit = _jit(lambda st, b: (st, orig(st, b)[1]))


def half_batch(sess):
    """Every client's turn sees the first half of its rows; the loss and
    the gradients are means over that half."""
    eng = sess.engine
    orig = eng._round
    eng._round_jit = _jit(lambda st, b: orig(
        st, {k: v[:, :v.shape[1] // 2] for k, v in b.items()}))


def server_unchanged(sess):
    """The clients train, but the server's weights and optimizer state
    come back as they went in."""
    eng = sess.engine
    orig = eng._round

    def rnd(st, b):
        new, losses = orig(st, b)
        return dict(new, server=st["server"], opt_s=st["opt_s"]), losses
    eng._round_jit = _jit(rnd)


def half_clients(sess):
    """Only the first half of the clients take their turn; the rest
    keep their weights and optimizer state.  The round still returns
    one loss per client (the trained half's, repeated)."""
    from repro.engine.program import EXECUTORS
    eng = sess.engine
    n = eng.n_clients
    half = max(1, n // 2)
    ctx = dataclasses.replace(eng._ctx, n_clients=half)
    run = EXECUTORS[eng.schedule]

    def rnd(st, b):
        sub = lambda t: jax.tree_util.tree_map(lambda a: a[:half], t)
        part = dict(st, clients=sub(st["clients"]), opt_c=sub(st["opt_c"]))
        new, losses = run(eng.program, ctx, part, sub(b))
        put = lambda full, head: jax.tree_util.tree_map(
            lambda a, h: a.at[:half].set(h), full, head)
        out = dict(new, clients=put(st["clients"], new["clients"]),
                   opt_c=put(st["opt_c"], new["opt_c"]))
        return out, jnp.resize(losses, (n,))
    eng._round_jit = _jit(rnd)


TRAINING = {"state_unchanged": state_unchanged, "half_batch": half_batch,
            "server_unchanged": server_unchanged,
            "half_clients": half_clients}
