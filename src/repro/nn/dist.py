"""Shared mesh registry + collective pytree helpers for shard_map code.

jax's ambient-mesh context does not flow into shard_map(mesh=None) on
this version, so launchers register the mesh explicitly before tracing:

    from repro.nn import dist
    dist.set_mesh(mesh)

The tree-level collective helpers below are the vocabulary the fleet
engines (`repro.engine.fleet`, `repro.api.baseline`) are written in:
every one maps a per-leaf `lax` collective / select over a whole
parameter tree so engine code reads like the single-device version.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def shard_map_norep(f, **kw):
    """`jax.shard_map` with replication checking off — ONLY for the fleet
    engines' round bodies: `pallas_call` (the physical wire's
    quantize/pack kernels run inside them) has no replication rule, so
    `check_vma=True` would reject any fleet round with a physical wire.
    Everything else (attention/MoE model parallelism) keeps the strict
    default — `check_vma` is exactly the net that catches a forgotten
    psum on a replicated output."""
    return jax.shard_map(f, check_vma=False, **kw)


_MESH = None


# ---------------------------------------------------------------------------
# collective pytree helpers (used inside shard_map bodies)
# ---------------------------------------------------------------------------

def tree_where(pred, on_true, on_false):
    """Leafwise `jnp.where(pred, ...)` over two same-structure trees.
    `pred` is a scalar (or broadcastable) bool — typically a device-
    activity mask like `lax.axis_index(ax) == phase`."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), on_true, on_false)


def tree_psum(tree, axis_name: str):
    """`lax.psum` every leaf over `axis_name`."""
    return jax.tree_util.tree_map(
        lambda a: lax.psum(a, axis_name), tree)


def tree_ppermute(tree, axis_name: str, perm):
    """`lax.ppermute` every leaf over `axis_name` with the same perm —
    the p2p handoff primitive for carries that walk a device ring."""
    return jax.tree_util.tree_map(
        lambda a: lax.ppermute(a, axis_name, perm), tree)


def tree_replicate_from(tree, axis_name: str, pred):
    """Broadcast the shard where `pred` is True to every shard along
    `axis_name` (masked psum: exactly one shard may be active).  Turns a
    device-varying value — e.g. the final carry of a ppermute ring —
    back into a replicated one so it can leave shard_map under `P()`."""
    return jax.tree_util.tree_map(
        lambda a: lax.psum(jnp.where(pred, a, jnp.zeros_like(a)),
                           axis_name), tree)


def set_mesh(mesh):
    global _MESH
    _MESH = mesh


def get_mesh():
    assert _MESH is not None, \
        "call repro.nn.dist.set_mesh(mesh) before tracing shard_map paths"
    return _MESH


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
