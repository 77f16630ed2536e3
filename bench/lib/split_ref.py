"""Plain reference of split training: round-robin turns with the p2p
weight handoff, the per-row int8 wire both ways and Adam, written from
the protocol's description (Vepakomma et al. 2018, arXiv:1812.00564,
Sec. 2; the int8 wire as `docs/wire.md` states it).  It imports nothing
of the program.

A model is given as two functions, `client_fwd(pc, batch)` -> cut
activation and `server_loss(ps, act, batch)` -> scalar loss, written in
plain jax.numpy by the model's file under `bench/models/`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def q8(x):
    """Per-last-axis-row symmetric int8 quantize and dequantize: scale =
    max|row| * f32(1/127) (at least 1e-12), values rounded and clipped to
    [-127, 127].  A 0-d leaf is one row of one element."""
    if x.ndim == 0:
        return q8(x[None])[0]
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) * (1.0 / 127.0)
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xf / scale), -127, 127)
    return (q * scale).astype(x.dtype)


def adam_init(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"step": jnp.zeros((), jnp.int32),
            "m": jax.tree_util.tree_map(z, params),
            "v": jax.tree_util.tree_map(z, params)}


def adam_step(params, grads, st, opt: dict):
    """Adam with bias correction (weight decay 0), moments in f32; the
    new parameters are cast back to the parameters' own dtype."""
    b1, b2, lr, eps = opt["b1"], opt["b2"], opt["lr"], opt["eps"]
    step = st["step"] + 1
    t = step.astype(jnp.float32)
    m = jax.tree_util.tree_map(
        lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
        st["m"], grads)
    v = jax.tree_util.tree_map(
        lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(g.astype(jnp.float32)),
        st["v"], grads)
    mh, vh = 1.0 / (1 - b1 ** t), 1.0 / (1 - b2 ** t)
    new = jax.tree_util.tree_map(
        lambda p, m_, v_: (p - lr * (m_ * mh) / (jnp.sqrt(v_ * vh) + eps)
                           ).astype(p.dtype), params, m, v)
    return new, {"step": step, "m": m, "v": v}


def turn_grads(client_fwd, server_loss, pc, ps, batch):
    """One turn: client forward, activation over the int8 wire, server
    forward and backward, cut gradient over the int8 wire, client
    backward.  Returns (loss, g_client, g_server)."""
    act, vjp_c = jax.vjp(lambda p: client_fwd(p, batch), pc)
    loss, (g_s, g_act) = jax.value_and_grad(server_loss, argnums=(0, 1))(
        ps, q8(act), batch)
    (g_c,) = vjp_c(q8(g_act))
    return loss, g_c, g_s


def make_turn(client_fwd, server_loss, opt: dict):
    """One jitted turn: the client (having adopted, with `adopt`, the
    last-trained client's weights through the int8 wire) trains against
    the server, and both take one Adam step.  The parameter and optimizer
    buffers passed in are donated: the reference holds one copy of each.
    (pc, opt_c, ps, opt_s, batch) -> (pc, opt_c, ps, opt_s, loss)."""
    def turn(pc, oc, ps, os_, batch, adopt):
        if adopt:
            pc = jax.tree_util.tree_map(q8, pc)
        loss, g_c, g_s = turn_grads(client_fwd, server_loss, pc, ps, batch)
        pc, oc = adam_step(pc, g_c, oc, opt)
        ps, os_ = adam_step(ps, g_s, os_, opt)
        return pc, oc, ps, os_, loss

    return jax.jit(turn, static_argnums=5, donate_argnums=(0, 1, 2, 3))
