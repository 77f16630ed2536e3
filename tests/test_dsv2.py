"""DeepSeek-V2-Lite through the program against the plain float32
reference (`dsv2_reference.py`), at a reduced size on seeded weights,
on logits:

* YaRN's inverse frequencies against the published formula, below,
  inside and above its ramp; without scaling `apply_rope` is bitwise
  what it was;
* the split prefill (client layers, cut, server layers) and then decoding
  through both halves' caches, position by position, past the reduced
  `original_max`;
* the MoE layer of one chip's share: over every disjoint share of the
  experts, the routed parts add up, with the shared experts counted
  once, to the reference's whole layer; and every token forced onto one
  expert still gives the reference's layer (nothing dropped);
* MLA's query-blocked prefill attention is the whole-sequence one.

Tolerances: the program and the reference both compute in f32 and
differ only in the order of operations (absorbed decode, sorted grouped
experts, blocked attention), a few f32 ulps of the logits, ~1e-6 of
their scale.  `LOGIT_TOL` is 1e-4 of the largest reference logit: the
program in bf16 misses it by two orders of magnitude
(`test_tolerance_fails_bf16`).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dsv2_reference as ref
from repro.configs import get_config
from repro.models import build_model
from repro.nn import attention as A
from repro.nn import layers as L
from repro.nn import moe as M

LOGIT_TOL = 1e-4
ARCH = "deepseek_v2_lite"


def _cfg(**over):
    return get_config(ARCH).reduced(vocab=97, **over)


def _gap(got, want):
    """Largest difference over the largest reference magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("where", ["below", "low", "ramp", "high", "above"])
def test_yarn_inv_freq_matches_formula(where):
    """At the published values the ramp runs from 10 to 23 of the 32
    frequencies.  rtol 1e-6: the program computes in f32 (a few ulps);
    a bf16 rendering of the same frequencies is off by ~4e-3."""
    y = get_config(ARCH).yarn
    d, theta = 64, 10000.0
    assert y.band(d, theta) == (10, 23)
    i = {"below": 3, "low": 10, "ramp": 16, "high": 23, "above": 30}[where]
    want = ref.yarn_inv_freq(d, theta, y.factor, y.original_max,
                             y.beta_fast, y.beta_slow)
    got = np.asarray(A.yarn_freqs(d, theta, y), np.float64)
    f = theta ** (-2.0 * i / d)
    assert want[i] == pytest.approx(
        {"below": f, "low": f, "ramp": f * (1 - 6 / 13) + f / 40 * 6 / 13,
         "high": f / 40, "above": f / 40}[where], rel=1e-12)
    np.testing.assert_allclose(got[i], want[i], rtol=1e-6)
    bf16 = float(jnp.asarray(want[i], jnp.bfloat16))
    assert abs(bf16 - want[i]) > 1e-6 * want[i]


def test_mla_softmax_scale_is_yarn_mscale_squared():
    cfg = build_model(get_config(ARCH)).groups[0].specs[0].attn
    assert A.mla_scale(cfg) == pytest.approx(
        (0.1 * 0.707 * np.log(40) + 1) ** 2 / np.sqrt(192), rel=1e-12)


def test_apply_rope_without_scaling_is_unchanged():
    """The rotary without YaRN, bitwise the formula it always had."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16))
    pos = jnp.arange(100, 109)
    rot = 16
    freqs = 1.0 / (10000.0 ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                               / rot))
    ang = pos[None, :, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    want = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    got = A.apply_rope(x, pos, theta=10000.0)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the whole model, split: prefill, then decode through the caches
# ---------------------------------------------------------------------------

def _model(cfg, seed=0):
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def _split_prefill(model, params, cut, tokens, max_len):
    cp, sp = model.split_params(params, cut)
    cc, sc = model.init_cache_split(tokens.shape[0], max_len, cut)
    act, cc = model.prefill_client(cp, {"tokens": tokens}, cut, cc)
    logits, sc = model.prefill_server(sp, act, cut, sc)
    return (cp, sp), logits, cc, sc


@pytest.mark.parametrize("held", [0, 2], ids=["all_experts", "share"])
def test_split_prefill_logits_match_reference(held):
    cfg = _cfg(experts_held=held, expert_offset=held)
    model, params = _model(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 13), 0, 97)
    _, logits, _, _ = _split_prefill(model, params, cfg.default_cut,
                                     tokens, 32)
    assert _gap(logits, ref.forward(params, cfg, tokens)) < LOGIT_TOL


def test_prefill_then_decode_matches_reference_past_original_max():
    """Prefill 10 positions, then teacher-forced decode one position at a
    time through both halves' caches up to position 23, past the reduced
    `original_max` of 16; each step's logits against the reference's
    full forward at that position."""
    cfg = _cfg(experts_held=2, expert_offset=1)
    assert cfg.yarn.original_max == 16
    model, params = _model(cfg, seed=2)
    cut, P, S = cfg.default_cut, 10, 24
    seq = jax.random.randint(jax.random.PRNGKey(3), (2, S), 0, 97)
    want = ref.forward(params, cfg, seq)
    (cp, sp), logits, cc, sc = _split_prefill(model, params, cut,
                                              seq[:, :P], 32)
    assert _gap(logits, want[:, :P]) < LOGIT_TOL
    gaps = []
    for t in range(P, S):
        act, cc = model.decode_step_client(cp, seq[:, t:t + 1], cut, cc)
        step, sc = model.decode_step_server(sp, act, cut, sc)
        gaps.append(_gap(step[:, 0], want[:, t]))
    assert max(gaps) < LOGIT_TOL, gaps


def test_tolerance_fails_bf16():
    """The same prefill with the program in bf16 misses `LOGIT_TOL`."""
    cfg = _cfg()
    _, params = _model(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 13), 0, 97)
    want = ref.forward(params, cfg, tokens)
    cfg16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    p16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)
    _, logits, _, _ = _split_prefill(build_model(cfg16), p16,
                                     cfg.default_cut, tokens, 32)
    assert _gap(logits, want) > 10 * LOGIT_TOL


# ---------------------------------------------------------------------------
# one chip's share of the experts, dropless
# ---------------------------------------------------------------------------

E, K, D, F, T = 8, 3, 32, 48, 40


def _moe_layer(seed, **over):
    cfg = M.MoEConfig(d_model=D, d_ff=F, n_experts=E, top_k=K, n_shared=1,
                      norm_topk_prob=False, routed_scaling_factor=1.5,
                      **over)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return cfg, M.moe_init(k1, cfg), jax.random.normal(k2, (2, T // 2, D))


def _share(params, lo, n):
    out = {k: v[lo:lo + n] for k, v in params.items()
           if k in ("gate", "up", "down")}
    out["router"] = params["router"]
    return out


def _ref_layer(cfg, params, x):
    with jax.default_matmul_precision("highest"):
        return ref.moe(params, cfg, x, 0)


@pytest.mark.parametrize("held", [1, 2, 4])
def test_chip_shares_add_up_to_the_whole_layer(held):
    """Each share `held` experts at offset s*held, computed as one chip
    would (held_experts only); the shares' sum plus the shared experts
    once is the reference's uncut layer.  atol: f32 rounding of sums of
    O(1) terms."""
    cfg, params, x = _moe_layer(held)
    parts = []
    for lo in range(0, E, held):
        c = dataclasses.replace(cfg, held=held, expert_offset=lo, n_shared=0)
        parts.append(M.moe_apply(_share(params, lo, held), c, x))
    got = sum(parts) + L.swiglu_apply(params["shared"], x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_ref_layer(cfg, params, x)),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("held", [0, 2], ids=["all_experts", "share"])
def test_forced_imbalance_is_dropless(held):
    """Every token's first choice is expert 1 (a large logit from a
    constant feature): 40 of 40 tokens on one expert, where a capacity of
    1.25 x T x k / E = 19 rows would drop half of them.  The program's
    layer still equals the reference's."""
    cfg, params, x = _moe_layer(7)
    x = x.at[..., 0].set(3.0)
    w = jnp.zeros((D, E)).at[0, 1].set(10.0)
    params = dict(params, router={"w": w + 0.01 * params["router"]["w"]})
    _, _, eid = M.route(params, cfg, x.reshape(T, D))
    assert bool((eid[:, 0] == 1).all())
    if held:
        lo = 0
        c = dataclasses.replace(cfg, held=held, expert_offset=lo)
        got = M.moe_apply(_share(params, lo, held) | {
            "shared": params["shared"]}, c, x)
        with jax.default_matmul_precision("highest"):
            want = ref.moe(_share(params, lo, held) | {
                "shared": params["shared"]}, c, x, lo)
    else:
        got, aux = M.moe_apply(params, cfg, x, return_aux=True)
        assert float(aux["drop_fraction"]) == 0.0
        want = _ref_layer(cfg, params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# blocked prefill attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [16, 21])
def test_blocked_causal_attention_is_the_whole(S):
    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 8))
    k = jax.random.normal(ks[1], (2, S, 4, 8))
    v = jax.random.normal(ks[2], (2, S, 4, 6))
    whole = A.causal_attention(q, k, v, scale=0.3)
    blocked = A.causal_attention(q, k, v, scale=0.3, block=8)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole),
                               atol=1e-6, rtol=1e-6)
