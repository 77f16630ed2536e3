"""Production mesh + sharding rules.

Mesh (TPU v5e pods): single pod = (data=16, model=16) = 256 chips;
multi-pod = (pod=2, data=16, model=16) = 512 chips.

Split-learning placement note (DESIGN.md §2): in the multi-pod mesh the
`pod` axis is the client/server boundary — batch (= client shard-groups)
spans ("pod", "data"), so the cut-layer activation transfer appears in
HLO as the reshard collective between the client segment's layout and the
server segment's tensor-parallel layout.

Everything here is a FUNCTION of a params/caches shape-tree: rules match
on tree paths and check divisibility against the mesh before committing a
sharded dim (falling back to replication, never to a compile error).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.nn import module as nn

# every mesh axis is `Auto`: arrays are placed by `NamedSharding` and GSPMD
# resolves the rest (jax's default `Explicit` axes would make every gather
# on a sharded client stack name its output sharding)
AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


# ---------------------------------------------------------------------------
# Fleet mesh: the client-axis mesh the fleet engines shard over
# ---------------------------------------------------------------------------

FLEET_CLIENT_AXIS = "clients"
FLEET_MODEL_AXIS = "model"


def make_fleet_mesh(n_devices: int | None = None, *, model_parallel: int = 1,
                    client_axis: str = FLEET_CLIENT_AXIS,
                    model_axis: str = FLEET_MODEL_AXIS):
    """THE mesh factory for the fleet engines: a ("clients", "model")
    mesh whose leading axis partitions the stacked client pytree and
    whose trailing axis is reserved for server tensor parallelism
    (size 1 until the server side is sharded).

    `n_devices=None` takes every visible device.  On CPU the visible
    device count honors XLA's host-platform override, so CI exercises
    real 8-way sharding on one machine:

        XLA_FLAGS=--xla_force_host_platform_device_count=8

    (set it BEFORE the first jax import — the backend reads it once)."""
    avail = jax.device_count()
    if n_devices is None:
        n_devices = max(1, avail // model_parallel)
    need = n_devices * model_parallel
    if need > avail:
        raise ValueError(
            f"fleet mesh needs {need} devices ({n_devices} x "
            f"{model_parallel}) but only {avail} are visible. On CPU, "
            "export XLA_FLAGS="
            f"'--xla_force_host_platform_device_count={need}' before "
            "importing jax to split the host into virtual devices.")
    return jax.make_mesh((n_devices, model_parallel), (client_axis, model_axis),
                         axis_types=(AUTO, AUTO))


def batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axis_size(mesh, axis) -> int:
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= _axis_size(mesh, a)
        return out
    return mesh.shape[axis]


def _ok(mesh, dim_size: int, axis) -> bool:
    return axis is not None and dim_size % _axis_size(mesh, axis) == 0


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------

# leaf/parent names whose *last* dim is tensor-parallel (column parallel)
_COL = {"wq", "wk", "wv", "gate", "up", "in_x", "in_gate", "in_proj",
        "wq_b", "wk_b", "wv_b", "fc1", "gate_a", "gate_x"}
# names whose second-to-last dim is tensor-parallel (row parallel)
_ROW = {"wo", "down", "out", "out_proj", "fc2"}
# MoE stacked expert tensors: leaf itself named gate/up/down with rank>=3
_EXPERT_LEAVES = {"gate", "up", "down"}


_FSDP_MIN_SIZE = 1 << 20      # only 2D-shard leaves >= 1M elements


def _param_spec(path: tuple, shape: tuple, mesh, *, fsdp: bool = False) -> P:
    model_ax = "model"
    name = path[-1]
    parent = path[-2] if len(path) >= 2 else ""
    rank = len(shape)

    def maybe_fsdp(spec_tail: tuple) -> tuple:
        """FSDP/ZeRO-3-style 2D weight sharding: additionally shard the
        largest still-unsharded dim over the data axes.  Without this,
        weights (and their fp32 adam m/v) replicate 16x across the data
        axis — the dry-run measured 147 GB/device for deepseek-v2 train,
        9x over v5e HBM.  GSPMD inserts per-layer weight all-gathers in
        exchange (the standard memory/collective trade)."""
        n = 1
        for s in shape:
            n *= s
        if not fsdp or n < _FSDP_MIN_SIZE:
            return spec_tail
        da = batch_axes(mesh)
        tail = list(spec_tail)
        # candidate dims within the tail, largest first
        offset = rank - len(tail)
        order = sorted(range(len(tail)),
                       key=lambda i: -shape[offset + i])
        for i in order:
            if tail[i] is None and _ok(mesh, shape[offset + i], da):
                tail[i] = da
                break
        return tuple(tail)

    def pad(spec_tail: tuple) -> P:
        spec_tail = maybe_fsdp(spec_tail)
        return P(*(((None,) * (rank - len(spec_tail))) + spec_tail))

    # MoE experts: (..., E, D, F) — expert-parallel over model axis
    if name in _EXPERT_LEAVES and rank >= 3 and parent == "mlp":
        e_dim = shape[-3]
        if _ok(mesh, e_dim, model_ax):
            return pad((model_ax, None, None))
        return pad((None, None, None))
    # embedding / tied head table: vocab-parallel
    if name == "table":
        if _ok(mesh, shape[-2], model_ax):
            return pad((model_ax, None))
        return pad((None, None))
    # generic dense weights
    if name == "w":
        owner = parent
        if owner in _COL and _ok(mesh, shape[-1], model_ax):
            return pad((None, model_ax))
        if owner in _ROW and _ok(mesh, shape[-2], model_ax):
            return pad((model_ax, None))
        # head / unlisted: shard the bigger dim if divisible
        if shape[-1] >= shape[-2] and _ok(mesh, shape[-1], model_ax):
            return pad((None, model_ax))
        if _ok(mesh, shape[-2], model_ax):
            return pad((model_ax, None))
        return pad((None, None))
    if name == "b":
        owner = parent
        if owner in _COL and _ok(mesh, shape[-1], model_ax):
            return pad((model_ax,))
        return pad((None,))
    # everything else (norm scales, A_log, dt_bias, lam, conv, router)
    return P(*([None] * rank))


def param_pspecs(param_shapes, mesh, *, fsdp: bool = False):
    """param_shapes: pytree of ShapeDtypeStruct (jax.eval_shape output).
    fsdp=True additionally shards large weights over the data axes
    (ZeRO-3-style 2D sharding) — required for models whose params +
    fp32 optimizer state exceed HBM under pure tensor parallelism."""
    return nn.map_with_path(
        lambda path, leaf: _param_spec(path, leaf.shape, mesh, fsdp=fsdp),
        param_shapes)


def param_shardings(param_shapes, mesh, *, fsdp: bool = False):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        param_pspecs(param_shapes, mesh, fsdp=fsdp))


# ---------------------------------------------------------------------------
# Batch sharding
# ---------------------------------------------------------------------------

def batch_pspecs(batch_specs: dict, mesh) -> dict:
    """Shard the leading (global-batch) dim over ("pod","data"); if batch
    is too small (long_500k B=1), shard the sequence dim instead."""
    ba = batch_axes(mesh)
    out = {}
    for k, v in batch_specs.items():
        rank = len(v.shape)
        if _ok(mesh, v.shape[0], ba):
            out[k] = P(*((ba,) + (None,) * (rank - 1)))
        elif rank >= 2 and _ok(mesh, v.shape[1], ba):
            out[k] = P(*((None, ba) + (None,) * (rank - 2)))
        else:
            out[k] = P(*([None] * rank))
    return out


def batch_shardings(batch_specs: dict, mesh) -> dict:
    return {k: NamedSharding(mesh, s)
            for k, s in batch_pspecs(batch_specs, mesh).items()}


# ---------------------------------------------------------------------------
# KV/state cache sharding (decode)
# ---------------------------------------------------------------------------

_SEQ_CACHE_LEAVES = {"k", "v", "c_kv", "k_pe"}


def _cache_spec(path: tuple, shape: tuple, mesh) -> P:
    """Caches may be stacked (leading n_layers dim).  Layout for ring
    caches: (layers?, B, len, heads?, hd?).  Shard batch over
    ("pod","data") when divisible, cache length over "model" when
    divisible (sequence-sharded KV — memory-optimal for long contexts;
    GSPMD inserts the reduction for the softmax contraction)."""
    name = path[-1]
    rank = len(shape)
    ba = batch_axes(mesh)
    if name == "pos":
        return P()
    # find batch dim: stacked caches have it at index 1, flat at 0.
    spec: list = [None] * rank
    if name in _SEQ_CACHE_LEAVES and rank >= 3:
        b_dim = 1 if rank >= 4 else 1  # (L,B,len,...) or (B,len,r)
        # heuristics: the length dim follows the batch dim
        if rank == 3:            # (B, len, r)  [flat mla]
            b_dim, l_dim = 0, 1
        elif rank == 4:
            # k/v at rank 4 are FLAT (B, len, K, hd); only the MLA
            # latents (c_kv, k_pe) are stacked at rank 4 (L, B, len, r).
            stacked = name in ("c_kv", "k_pe")
            b_dim, l_dim = (1, 2) if stacked else (0, 1)
        else:                    # rank 5: (L, B, len, K, hd)
            b_dim, l_dim = 1, 2
        if _ok(mesh, shape[b_dim], ba):
            spec[b_dim] = ba
        if _ok(mesh, shape[l_dim], "model"):
            spec[l_dim] = "model"
        return P(*spec)
    if name in ("conv", "h", "ssm", "0"):
        # recurrent states: shard batch if divisible, else replicate
        for b_dim in (1, 0):
            if b_dim < rank and _ok(mesh, shape[b_dim], ba):
                spec[b_dim] = ba
                break
        return P(*spec)
    # default: try batch on dim 0/1
    for b_dim in (1, 0):
        if b_dim < rank and _ok(mesh, shape[b_dim], ba):
            spec[b_dim] = ba
            break
    return P(*spec)


def cache_pspecs(cache_shapes, mesh):
    return nn.map_with_path(
        lambda path, leaf: _cache_spec(path, leaf.shape, mesh), cache_shapes)


def cache_shardings(cache_shapes, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), cache_pspecs(cache_shapes, mesh))


# ---------------------------------------------------------------------------
# Optimizer state: mirrors params (plus scalar step)
# ---------------------------------------------------------------------------

def opt_pspecs(opt_shapes, params_pspecs):
    """m/v mirror the param specs; scalars replicate."""
    def fix(path, leaf):
        if path and path[0] in ("m", "v", "mu"):
            sub = params_pspecs
            for pth in path[1:]:
                sub = sub[pth] if isinstance(sub, dict) else sub[int(pth)]
            return sub
        return P(*([None] * len(leaf.shape)))
    return nn.map_with_path(fix, opt_shapes)
