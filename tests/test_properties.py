"""Hypothesis property-based tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed (pip install -r requirements.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import split as sp
from repro.data import partition as part
from repro.nn import attention as A
from repro.nn import moe as M

SETTINGS = dict(max_examples=20, deadline=None)


# ---------------------------------------------------------------------------
# MoE dispatch invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(st.integers(2, 8), st.integers(1, 4), st.integers(4, 32),
       st.integers(0, 1000))
def test_moe_matches_dense_oracle(n_exp, k, toks, seed):
    """Sorted grouped dispatch == the dense every-expert-computes oracle
    (the layer is dropless)."""
    k = min(k, n_exp)
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    d, f = 8, 16
    cfg = M.MoEConfig(d_model=d, d_ff=f, n_experts=n_exp, top_k=k)
    params = M.moe_init(k1, cfg)
    x = jax.random.normal(k2, (1, toks, d))
    out = M.moe_apply(params, cfg, x)

    # dense oracle
    xf = x.reshape(toks, d)
    probs = M.router_probs(params, cfg, xf)
    gw, eid = jax.lax.top_k(probs, k)
    gw = gw / jnp.maximum(gw.sum(-1, keepdims=True), 1e-9)
    expect = jnp.zeros_like(xf)
    for e in range(n_exp):
        g = jax.nn.silu(xf @ params["gate"][e]) * (xf @ params["up"][e])
        y_e = g @ params["down"][e]
        w_e = jnp.where(eid == e, gw, 0.0).sum(-1)
        expect = expect + y_e * w_e[:, None]
    np.testing.assert_allclose(np.asarray(out.reshape(toks, d)),
                               np.asarray(expect), atol=1e-4, rtol=1e-4)


@settings(**SETTINGS)
@given(st.integers(0, 100))
def test_moe_drop_fraction_bounded(seed):
    key = jax.random.PRNGKey(seed)
    cfg = M.MoEConfig(d_model=8, d_ff=16, n_experts=4, top_k=2)
    params = M.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 32, 8))
    out, aux = M.moe_apply(params, cfg, x, return_aux=True)
    assert float(aux["drop_fraction"]) == 0.0          # dropless
    assert float(aux["load_balance_loss"]) >= 0.99  # >= 1 up to fp error
    assert not bool(jnp.isnan(out).any())


# ---------------------------------------------------------------------------
# Attention invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(st.integers(1, 3), st.integers(2, 16), st.integers(0, 1000))
def test_causal_attention_is_causal(b, s, seed):
    """Perturbing future tokens never changes past outputs."""
    key = jax.random.PRNGKey(seed)
    cfg = A.AttnConfig(d_model=16, n_heads=2, n_kv_heads=2, head_dim=8)
    params = A.gqa_init(key, cfg)
    x = jax.random.normal(key, (b, s, 16))
    y1 = A.gqa_apply(params, cfg, x)
    x2 = x.at[:, -1].set(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                           (b, 16)))
    y2 = A.gqa_apply(params, cfg, x2)
    np.testing.assert_allclose(np.asarray(y1[:, :-1]),
                               np.asarray(y2[:, :-1]), atol=1e-5)


@settings(**SETTINGS)
@given(st.integers(0, 500))
def test_rope_preserves_norm_and_relativity(seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (1, 6, 2, 16))
    pos = jnp.arange(6)
    y = A.apply_rope(x, pos, theta=10000.0)
    # rotation preserves norms
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(x, axis=-1)),
                               np.asarray(jnp.linalg.norm(y, axis=-1)),
                               rtol=1e-5)
    # relative property: <R_m q, R_n k> depends only on m - n
    q = x[:, 0:1]
    k = x[:, 1:2]
    def dot_at(m, n):
        qm = A.apply_rope(q, jnp.array([m]), theta=10000.0)
        kn = A.apply_rope(k, jnp.array([n]), theta=10000.0)
        return float(jnp.sum(qm * kn))
    assert abs(dot_at(3, 1) - dot_at(7, 5)) < 1e-3


# ---------------------------------------------------------------------------
# Partitioning invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(st.integers(2, 8), st.integers(1, 5), st.integers(0, 99))
def test_horizontal_partition_is_disjoint_cover(n_clients, per, seed):
    key = jax.random.PRNGKey(seed)
    n = n_clients * per
    batch = {"x": jax.random.normal(key, (n, 3)),
             "labels": jnp.arange(n)}
    shards = part.horizontal_partition(batch, n_clients)
    seen = jnp.concatenate([s["labels"] for s in shards])
    assert seen.shape[0] == n
    assert bool(jnp.all(jnp.sort(seen) == jnp.arange(n)))


@settings(**SETTINGS)
@given(st.integers(0, 99))
def test_vertical_partition_aligns_samples(seed):
    key = jax.random.PRNGKey(seed)
    batch = {"mod_a": jax.random.normal(key, (10, 4)),
             "mod_b": jax.random.normal(key, (10, 6)),
             "labels": jnp.arange(10)}
    shards = part.vertical_partition(batch, ["mod_a", "mod_b"])
    assert set(shards[0]) == {"mod_a", "labels"}
    assert set(shards[1]) == {"mod_b"}
    assert shards[0]["mod_a"].shape[0] == shards[1]["mod_b"].shape[0]


def test_dirichlet_label_skew_covers_all():
    key = jax.random.PRNGKey(5)
    labels = jnp.array([0, 1, 2, 3] * 25)
    idxs = part.dirichlet_label_skew(key, labels, 4, alpha=0.5)
    allidx = sorted(int(i) for ix in idxs for i in ix)
    assert allidx == list(range(100))


# ---------------------------------------------------------------------------
# Optimizer invariants
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(st.integers(0, 200))
def test_clip_by_global_norm_bounds(seed):
    from repro.optim import clip_by_global_norm, global_norm
    key = jax.random.PRNGKey(seed)
    g = {"a": jax.random.normal(key, (7,)) * 100,
         "b": jax.random.normal(key, (3, 3)) * 100}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(global_norm(clipped)) <= 1.0 + 1e-4


def test_adamw_decays_only_matrices():
    from repro import optim
    opt = optim.adamw(1e-2, weight_decay=0.1)
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    state = opt.init(params)
    zero_g = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))}
    ups, _ = opt.update(zero_g, state, params)
    assert float(jnp.abs(ups["w"]).max()) > 0      # decay applied
    assert float(jnp.abs(ups["b"]).max()) == 0     # bias not decayed
