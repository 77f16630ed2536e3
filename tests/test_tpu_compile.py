"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

The kernels run in interpret mode everywhere else in this suite; here
they are lowered and compiled for a described (not attached) v5e chip
at real widths, which catches what the interpreter cannot: blocks not
aligned to the chip's tiling, and more VMEM than a kernel may use.
Nothing runs, so this says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.splitcat_linear import splitcat_linear_q8_pallas
from repro.kernels.wire_quant import wire_dequant_pallas, wire_quant_pallas


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


# cut activations of the paper's VGG (64 channels), a phi4-mini prefill
# (d_model 3072), and one decode step's logits row (vocab 200,064)
WIRE_CASES = [((8, 32, 32, 64), jnp.float32),
              ((8, 512, 3072), jnp.bfloat16),
              ((4, 1, 200064), jnp.float32)]
WIRE_IDS = ["vgg_cut_f32", "lm_prefill_bf16", "logits_row_f32"]


@pytest.mark.parametrize("shape,dtype", WIRE_CASES, ids=WIRE_IDS)
def test_wire_quant_compiles_for_v5e(one_chip, shape, dtype):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compiled_text(wire_quant_pallas, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,dtype", WIRE_CASES, ids=WIRE_IDS)
def test_wire_dequant_compiles_for_v5e(one_chip, shape, dtype):
    q = jax.ShapeDtypeStruct(shape, jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct(shape[:-1] + (1,), jnp.float32,
                             sharding=one_chip)
    text = _compiled_text(lambda q, s: wire_dequant_pallas(q, s, dtype), q, s)
    assert "tpu_custom_call" in text


def test_splitcat_linear_q8_compiles_for_v5e(one_chip):
    """The fused packed-wire server entry: 4 decode rows of int8 at
    d_model 3072 into the fused QKV width 5120, bf16 weights."""
    q = jax.ShapeDtypeStruct((4, 3072), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((4, 1), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((3072, 5120), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        lambda q, s, w: splitcat_linear_q8_pallas([q], [s], w,
                                                  out_dtype=jnp.bfloat16),
        q, s, w)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tokens", [8, 6144], ids=["decode", "prefill"])
def test_held_experts_compile_for_v5e(one_chip, tokens):
    """DeepSeek-V2-Lite's MoE on one chip of eight: 8 held experts of
    width 1,408 at d_model 2,048, top-6 of 64, over a decode step of 8
    slots and a 6,144-token prefill.  The grouped product compiles to the
    chip's ragged dot (its group metadata kernel), not a dense product
    per expert."""
    from repro.nn import moe as M
    cfg = M.MoEConfig(d_model=2048, d_ff=1408, n_experts=64, top_k=6,
                      held=8, norm_topk_prob=False, dtype=jnp.bfloat16)
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    experts = {"gate": sd((8, 2048, 1408), jnp.bfloat16),
               "up": sd((8, 2048, 1408), jnp.bfloat16),
               "down": sd((8, 1408, 2048), jnp.bfloat16)}

    def layer(router, experts, x):
        _, w, eid = M.route({"router": {"w": router}}, cfg, x)
        return M.held_experts(experts, cfg, x, w, eid, 0)

    text = _compiled_text(layer, sd((2048, 64), jnp.float32), experts,
                          sd((tokens, 2048), jnp.bfloat16))
    assert "ragged-dot" in text
