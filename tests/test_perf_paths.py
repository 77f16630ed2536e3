"""Correctness of the §Perf optimization paths (shard_map MoE EP,
split-KV decode, quantized wire) against their GSPMD/base equivalents.
All run on a 1x1 mesh — numerics must be exact regardless of shard
count."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import attention as A
from repro.nn import dist
from repro.nn import moe as M


@pytest.fixture(scope="module")
def mesh11():
    from repro.launch.mesh import AUTO
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AUTO, AUTO))
    dist.set_mesh(mesh)
    return mesh


def test_moe_ep_matches_gspmd_path(mesh11):
    key = jax.random.PRNGKey(0)
    cfg = M.MoEConfig(d_model=16, d_ff=32, n_experts=4, top_k=2,
                      n_shared=1, capacity_factor=8.0)
    params = M.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 8, 16))
    ref = M.moe_apply(params, cfg, x)
    cfg_ep = dataclasses.replace(cfg, ep_axis="model")
    with mesh11:
        out = M.moe_apply(params, cfg_ep, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_moe_ep_grads_flow(mesh11):
    key = jax.random.PRNGKey(1)
    cfg = M.MoEConfig(d_model=8, d_ff=16, n_experts=4, top_k=2,
                      capacity_factor=8.0, ep_axis="model")
    params = M.moe_init(key, cfg)
    x = jax.random.normal(key, (1, 4, 8))
    with mesh11:
        g = jax.grad(lambda p: jnp.sum(M.moe_apply(p, cfg, x) ** 2))(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert bool(jnp.isfinite(leaf).all())
    assert float(jnp.abs(g["gate"]).max()) > 0


@pytest.mark.slow
@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("window", [None, 8])
def test_split_kv_decode_matches_base(mesh11, bias, window):
    key = jax.random.PRNGKey(2)
    cfg = A.AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                       qkv_bias=bias, window=window)
    cfg_s = dataclasses.replace(cfg, decode_kv_shard="model")
    params = A.gqa_init(key, cfg)
    ca = A.gqa_init_cache(cfg, 2, 32)
    cb = A.gqa_init_cache(cfg, 2, 32)
    errs = []
    for t in range(16):                       # crosses the ring wrap
        x = jax.random.normal(jax.random.fold_in(key, t), (2, 1, 32))
        ya, ca = A.gqa_decode(params, cfg, x, ca)
        with mesh11:
            yb, cb = A.gqa_decode(params, cfg_s, x, cb)
        errs.append(float(jnp.abs(ya - yb).max()))
    assert max(errs) < 1e-5, max(errs)


def test_quantized_wire_roundtrip_and_grad():
    from repro.core.wire_compress import quantized_wire, wire_bytes
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (8, 64))
    y = quantized_wire(x)
    # int8 fake-quant: relative error bounded by scale/2 per element
    assert float(jnp.abs(y - x).max()) < float(jnp.abs(x).max()) / 127.0
    # backward wire is quantized too (custom_vjp), but close to identity
    g = jax.grad(lambda a: jnp.sum(quantized_wire(a) * 3.0))(x)
    np.testing.assert_allclose(np.asarray(g), 3.0, rtol=0.02)
    # 4x byte reduction vs fp32 wire (modulo row scales)
    assert wire_bytes((8, 64), quantized=True) \
        < wire_bytes((8, 64), quantized=False, base_dtype=jnp.float32) / 3


def test_quantized_wire_split_training_learns():
    """Split training with an int8 wire must still learn (parity check)."""
    from repro import optim
    from repro.configs import get_config
    from repro.core.wire_compress import quantized_wire
    from repro.data import synthetic as syn
    from repro.models import build_model

    cfg = get_config("phi4_mini_3_8b").reduced(n_layers=2, vocab=64)
    m = build_model(cfg)
    key = jax.random.PRNGKey(4)
    params = m.init(key)
    cut = 1
    pc, ps = m.split_params(params, cut)
    opt = optim.adamw(1e-2)
    sc, ss = opt.init(pc), opt.init(ps)

    def split_loss(pc_, ps_, b):
        act = quantized_wire(m.apply_client(pc_, b, cut))   # int8 wire
        logits = m.apply_server(ps_, act, cut)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.take_along_axis(lp, b["labels"][..., None], -1).mean()

    @jax.jit
    def step(pc_, ps_, sc_, ss_, b):
        loss, (gc, gs) = jax.value_and_grad(split_loss, argnums=(0, 1))(
            pc_, ps_, b)
        uc, sc_ = opt.update(gc, sc_, pc_)
        us, ss_ = opt.update(gs, ss_, ps_)
        return optim.apply_updates(pc_, uc), optim.apply_updates(ps_, us), \
            sc_, ss_, loss

    gen = syn.lm_stream(key, batch=8, seq=16, vocab=cfg.vocab)
    losses = []
    for _ in range(30):
        pc, ps, sc, ss, loss = step(pc, ps, sc, ss, next(gen))
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.4, (losses[0], losses[-1])


def test_int8_kv_cache_decode_close_to_native():
    """int8 KV cache: per-step decode outputs track the native cache
    within quantization tolerance, and the cache payload is 1 byte/elem."""
    key = jax.random.PRNGKey(7)
    cfg = A.AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8)
    cfg_q = dataclasses.replace(cfg, kv_cache_dtype="int8")
    params = A.gqa_init(key, cfg)
    ca = A.gqa_init_cache(cfg, 2, 16)
    cb = A.gqa_init_cache(cfg_q, 2, 16)
    assert cb["k"].dtype == jnp.int8
    errs, mags = [], []
    for t in range(12):
        x = jax.random.normal(jax.random.fold_in(key, t), (2, 1, 32))
        ya, ca = A.gqa_decode(params, cfg, x, ca)
        yb, cb = A.gqa_decode(params, cfg_q, x, cb)
        errs.append(float(jnp.abs(ya - yb).max()))
        mags.append(float(jnp.abs(ya).max()))
    assert max(errs) < 0.05 * max(mags), (max(errs), max(mags))
