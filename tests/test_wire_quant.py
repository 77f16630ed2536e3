"""True low-precision wire: kernel correctness, packed-payload plumbing,
fake<->physical training parity, and the bytes-accounting invariant.

* kernel sweeps — the fused Pallas quantize-pack / dequant kernels
  (interpret mode) match the pure-jnp oracles BITWISE, including the
  fused dequant+concat+matmul splitcat variant;
* gradients — `wire_roundtrip`'s custom bwd squeezes the cotangent
  through the same int8 wire (== the fake `quantized_wire` semantics);
* parity — every Plan mode trains identically under
  `quantize_int8(physical=True)` and the fake `quantize_int8()`
  (`dequant(pack(x)) == fake_quant(x)` bitwise), cut payloads, p2p
  handoff and baseline model payloads alike;
* accounting — metered bytes equal the ACTUAL nbytes of the packed
  payload pytree whenever a physical transform is active, and the
  `bytes_fn` claim cannot drift from it (`WireAccountingError`);
* dispatch — `REPRO_KERNELS=pallas|interp|ref`; pallas raises off-TPU.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.api import (MODES, Plan, dp_noise, leakage_probe, quantize_int8,
                       softmax_xent)
from repro.api.wire import WireAccountingError, WireStack, WireTransform
from repro.core import split as sp
from repro.core import wire_compress as wc
from repro.data import synthetic as syn
from repro.kernels import ops, ref
from repro.kernels.wire_quant import wire_roundtrip
from repro.nn import convnets as C
from repro.nn import layers as L

KEY = jax.random.PRNGKey(0)
N_CLS = 4
CFG = C.CNNConfig(name="wq", width_mult=0.25, plan=(16, 16, "M", 32, "M"),
                  n_classes=N_CLS)
PLAN_LAYERS = C.vgg_plan(CFG)


def make_model():
    return sp.list_segmodel(
        n_segments=len(PLAN_LAYERS),
        init=lambda k: C.vgg_init(k, CFG),
        layer_apply=lambda p, i, x: C.vgg_layer_apply(p, PLAN_LAYERS[i], x))


def make_branch(din=64, dout=16):
    return sp.Branch(
        init=lambda k: {"w": L.dense_init(k, din, dout, bias=True)},
        apply=lambda p, x: jax.nn.relu(L.dense_apply(p["w"], x)))


def _dense(k_in, k_out):
    init = lambda k: {"w": L.dense_init(k, k_in, k_out, bias=True)}
    apply = lambda p, f: L.dense_apply(p["w"], f)
    return init, apply


def image_shards(key, n, per=8):
    b = syn.image_batch(key, per * n, N_CLS)
    return [{"x": b["images"][i * per:(i + 1) * per],
             "labels": b["labels"][i * per:(i + 1) * per]}
            for i in range(n)]


def modal_batch(key, per_task_labels=False):
    b = syn.multimodal_batch(key, 16, N_CLS, dim_a=64, dim_b=64)
    labels = b["labels"]
    if per_task_labels:
        labels = jnp.stack([labels, (labels + 1) % N_CLS])
    return {"x": jnp.stack([b["mod_a"], b["mod_b"]]), "labels": labels}


def tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# kernels vs oracles (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 128), (2, 7, 33), (257, 16),
                                   (1, 1, 5), (13,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wire_quant_kernel_bitwise_vs_ref(shape, dtype):
    x = jax.random.normal(jax.random.fold_in(KEY, len(shape)), shape, dtype)
    q, s = ops.wire_quantize(x, interpret=True)
    qr, sr = ref.wire_quant_ref(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    d = ops.wire_dequantize(q, s, dtype, interpret=True)
    np.testing.assert_array_equal(np.asarray(d),
                                  np.asarray(ref.wire_dequant_ref(q, s,
                                                                  dtype)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_roundtrip_equals_fake_quant_bitwise(dtype):
    """dequant(pack(x)) == _fake_quant_int8(x) — the identity the whole
    physical path's training parity rests on."""
    x = jax.random.normal(KEY, (6, 31, 24), dtype) * 3.0
    q, s = ops.wire_quantize(x, interpret=True)
    d = ops.wire_dequantize(q, s, dtype, interpret=True)
    np.testing.assert_array_equal(np.asarray(d, np.float32),
                                  np.asarray(wc._fake_quant_int8(x),
                                             np.float32))


def test_quant_handles_scalar_leaves():
    """Param trees routed through the wire (p2p handoff, baseline model
    pull/push) may hold 0-d leaves (e.g. a learned temperature): both
    flavours must preserve the () shape."""
    x = jnp.float32(3.5)
    f = wc._fake_quant_int8(x)
    assert f.shape == () and np.isfinite(float(f))
    p = wc.pack_int8(x)
    assert p.q.shape == () and p.scale.shape == ()
    d = wc.as_dense(p)
    assert d.shape == ()
    np.testing.assert_array_equal(np.asarray(d), np.asarray(f))
    assert wc.payload_nbytes(p) == 5       # 1 int8 + 1 fp32 scale
    # handoff over a tree with a scalar leaf survives both flavours
    stack = WireStack((quantize_int8(physical=True),))
    tree = {"w": jnp.ones((3, 4)), "temp": jnp.float32(0.7)}
    out = stack.handoff_unpack(stack.handoff_pack(tree))
    assert out["temp"].shape == () and out["w"].shape == (3, 4)
    np.testing.assert_array_equal(
        np.asarray(out["temp"]),
        np.asarray(stack.handoff_recv(tree)["temp"]))


def test_baseline_physical_wire_report_flags_and_checks():
    sess = Plan(mode="large_batch", model=make_model(),
                loss_fn=softmax_xent, optimizer=optim.sgd(0.05),
                n_clients=2,
                wire=(quantize_int8(physical=True),)).compile()
    rep = sess.wire_report(image_shards(jax.random.PRNGKey(41), 2))
    assert all(w["physical"] for w in rep)
    assert rep[0]["bytes"] < sess.engine._param_bytes


def test_quant_handles_zero_and_tiny_rows():
    x = jnp.stack([jnp.zeros((8,)), jnp.full((8,), 1e-30),
                   jnp.ones((8,))])
    q, s = ops.wire_quantize(x, interpret=True)
    assert np.all(np.isfinite(np.asarray(s)))
    d = ops.wire_dequantize(q, s, jnp.float32, interpret=True)
    np.testing.assert_array_equal(np.asarray(d[0]), np.zeros((8,)))
    assert np.all(np.isfinite(np.asarray(d)))


def test_wire_roundtrip_gradient_matches_quantized_wire():
    """fwd AND custom bwd: the cotangent crosses the same int8 wire —
    identical to core.wire_compress.quantized_wire's vjp."""
    x = jax.random.normal(KEY, (5, 40))
    ct = jax.random.normal(jax.random.fold_in(KEY, 1), (5, 40))

    out, vjp = jax.vjp(wire_roundtrip, x)
    out_ref, vjp_ref = jax.vjp(wc.quantized_wire, x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    (g,), (g_ref,) = vjp(ct), vjp_ref(ct)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))
    # and through a composite loss
    gx = jax.grad(lambda t: (wire_roundtrip(t) ** 2).sum())(x)
    assert np.all(np.isfinite(np.asarray(gx)))


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("dims", [((9, 48), (9, 16), 128),
                                  ((130, 64), (130, 64), 256)])
def test_splitcat_q8_fused_matches_ref(dims, bias):
    (ra, ka), (rb, kb), cout = dims
    a = jax.random.normal(jax.random.fold_in(KEY, 2), (ra, ka))
    b = jax.random.normal(jax.random.fold_in(KEY, 3), (rb, kb))
    w = jax.random.normal(jax.random.fold_in(KEY, 4),
                          (ka + kb, cout)) * 0.1
    bb = (jax.random.normal(jax.random.fold_in(KEY, 5), (cout,))
          if bias else None)
    pa, pb = wc.pack_int8(a), wc.pack_int8(b)
    out = ops.splitcat_linear_q8([pa.q, pb.q], [pa.scale, pb.scale], w, bb,
                                 interpret=True)
    expect = ref.splitcat_linear_q8_ref([pa.q, pb.q], [pa.scale, pb.scale],
                                        w, bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)
    # equals dense splitcat over the dequantized parts too
    dense = ref.splitcat_linear_ref([wc.as_dense(pa), wc.as_dense(pb)],
                                    w, bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=1e-4, rtol=1e-4)


def test_splitcat_linear_packed_dispatches_on_payload():
    a = jax.random.normal(jax.random.fold_in(KEY, 6), (7, 24))
    b = jax.random.normal(jax.random.fold_in(KEY, 7), (7, 8))
    w = jax.random.normal(jax.random.fold_in(KEY, 8), (32, 128)) * 0.1
    packed = wc.splitcat_linear_packed([wc.pack_int8(a), wc.pack_int8(b)], w)
    dense = wc.splitcat_linear_packed([a, b], w)
    # packed path consumed int8 directly; result == dense over fake-quant
    np.testing.assert_allclose(
        np.asarray(packed),
        np.asarray(ref.splitcat_linear_ref(
            [wc._fake_quant_int8(a), wc._fake_quant_int8(b)], w)),
        atol=1e-4, rtol=1e-4)
    assert packed.shape == dense.shape


# ---------------------------------------------------------------------------
# packed payload pytree
# ---------------------------------------------------------------------------

def test_packed_payload_nbytes_and_logical_view():
    x = jax.random.normal(KEY, (8, 16, 64))
    p = wc.pack_int8(x)
    assert p.shape == x.shape and p.dtype == x.dtype
    assert wc.payload_nbytes(p) == x.size * 1 + (x.size // 64) * 4
    assert wc.payload_nbytes(p) < x.nbytes / 3.5
    leaves = jax.tree_util.tree_leaves(p)
    assert {leaf.dtype for leaf in leaves} == {jnp.dtype(jnp.int8),
                                              jnp.dtype(jnp.float32)}
    # survives jit boundaries as a pytree
    out = jax.jit(lambda t: wc.as_dense(t))(p)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(wc._fake_quant_int8(x)))


# ---------------------------------------------------------------------------
# fake <-> physical training parity, all Plan modes
# ---------------------------------------------------------------------------

def _plan_for(mode, wire):
    opt = optim.adamw(1e-2)
    common = dict(loss_fn=softmax_xent, optimizer=opt, n_clients=2,
                  wire=wire)
    if mode == "vanilla":
        return Plan(mode=mode, model=make_model(), cut=2, **common)
    if mode == "u_shaped":
        return Plan(mode=mode, model=make_model(), cuts=(1, 4),
                    sync="none", **common)
    if mode == "multihop":
        return Plan(mode=mode, model=make_model(), cuts=[1, 3], **common)
    if mode == "vertical":
        return Plan(mode=mode, branch=make_branch(),
                    trunk=_dense(32, N_CLS), **common)
    if mode == "multitask":
        return Plan(mode=mode, branch=make_branch(),
                    heads=(_dense(32, N_CLS), _dense(32, N_CLS)), **common)
    if mode == "extended_vanilla":
        return Plan(mode=mode, branch=make_branch(), mid=_dense(32, 24),
                    trunk=_dense(24, N_CLS), **common)
    if mode == "fedavg":
        return Plan(mode=mode, model=make_model(), local_steps=2, **common)
    return Plan(mode="large_batch", model=make_model(), **common)


def _round_data(mode, key, r):
    k = jax.random.fold_in(key, r)
    if mode == "multitask":
        return modal_batch(k, per_task_labels=True)
    if mode in ("vertical", "extended_vanilla"):
        return modal_batch(k)
    return image_shards(k, 2)


@pytest.mark.parametrize("mode", MODES)
def test_physical_quant_training_matches_fake_all_modes(mode):
    """Every Plan mode trains under quantize_int8(physical=True) with a
    loss trajectory AND final state matching the fake-quant run within
    quantization tolerance (here: exactly, since dequant(pack(x)) is
    bitwise fake_quant(x))."""
    key = jax.random.PRNGKey(13)
    runs = {}
    for tag, phys in (("fake", False), ("physical", True)):
        sess = _plan_for(mode, (quantize_int8(physical=phys),)).compile()
        sess.init(key)
        losses = sess.fit(lambda r: _round_data(mode, key, r), rounds=3)
        assert all(np.isfinite(losses)), (mode, tag, losses)
        runs[tag] = (losses, sess.state)
    np.testing.assert_allclose(runs["fake"][0], runs["physical"][0],
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(runs["fake"][1]),
                    jax.tree_util.tree_leaves(runs["physical"][1])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-6)


def test_physical_quant_composes_with_noise_and_probe():
    key = jax.random.PRNGKey(17)
    sess = Plan(mode="vanilla", model=make_model(), cut=2,
                loss_fn=softmax_xent, optimizer=optim.adamw(1e-2),
                n_clients=2, sync="none",
                wire=(quantize_int8(physical=True), dp_noise(0.02),
                      leakage_probe())).compile()
    sess.init(key)
    losses = sess.fit(lambda r: image_shards(jax.random.fold_in(key, r), 2),
                      rounds=8)
    assert np.mean(losses[-3:]) < losses[0], losses
    rep = sess.leakage_report(image_shards(key, 2)[0])
    assert 0.0 <= rep["dcor_input_vs_act"] <= 1.0
    # the wire after [quant, noise] stays physically packed
    wr = sess.wire_report(image_shards(key, 2))
    assert all(w["physical"] for w in wr)


def test_p2p_handoff_crosses_the_quantized_wire():
    """round_robin + p2p: the weight handoff is wire traffic — with a
    quantize stack the sync bytes shrink to int8+scales and fake vs
    physical stay bit-identical (the handoff is quantized once, at the
    source)."""
    key = jax.random.PRNGKey(19)
    mk = lambda wire: Plan(mode="vanilla", model=make_model(), cut=2,
                           loss_fn=softmax_xent, optimizer=optim.sgd(0.05),
                           n_clients=2, wire=wire).compile()
    plain, fake, phys = mk(()), mk((quantize_int8(),)), \
        mk((quantize_int8(physical=True),))
    for s in (plain, fake, phys):
        s.init(key)
        s.fit(lambda r: image_shards(jax.random.fold_in(key, r), 2),
              rounds=3)
    tree_equal(fake.state, phys.state)
    assert sum(fake.engine.meter.sync_bytes) > 0
    assert sum(fake.engine.meter.sync_bytes) == \
        sum(phys.engine.meter.sync_bytes)
    # int8 + per-row fp32 scales: < 1/2 of the dense fp32 handoff (the
    # exact ratio depends on the last-axis width of each param leaf)
    assert sum(fake.engine.meter.sync_bytes) < \
        sum(plain.engine.meter.sync_bytes) / 2
    # the quantized handoff changed training vs the plain wire
    a = jax.tree_util.tree_leaves(plain.state["clients"])[0]
    b = jax.tree_util.tree_leaves(fake.state["clients"])[0]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# bytes-accounting invariant
# ---------------------------------------------------------------------------

def test_metered_bytes_equal_physical_payload_nbytes():
    """The invariant: with a physical transform active, every metered
    wire record equals the ACTUAL nbytes of the packed payload pytree
    (int8 q + fp32 scales) — derived from dtypes, not bookkeeping.
    A 64-channel cut (the paper's VGG client share) compresses >= 3.5x
    vs the fp32 wire."""
    key = jax.random.PRNGKey(23)
    cfg = C.CNNConfig(name="wide", width_mult=1.0, plan=(64, "M", 32, "M"),
                      n_classes=N_CLS)
    layers = C.vgg_plan(cfg)
    wide = sp.list_segmodel(
        n_segments=len(layers),
        init=lambda k: C.vgg_init(k, cfg),
        layer_apply=lambda p, i, x: C.vgg_layer_apply(p, layers[i], x))
    sess = Plan(mode="vanilla", model=wide, cut=1,
                loss_fn=softmax_xent, optimizer=optim.sgd(0.05),
                n_clients=2, sync="none",
                wire=(quantize_int8(physical=True),)).compile()
    report = sess.wire_report(image_shards(key, 2))
    assert {w["name"] for w in report} == {"cut_act", "cut_grad"}
    for w in report:
        assert w["physical"]
        assert w["shape"][-1] == 64
        n = int(np.prod(w["shape"]))
        rows = n // w["shape"][-1]
        packed = wc.payload_nbytes(
            wc.pack_int8(jnp.zeros(w["shape"], w["dtype"])))
        assert w["bytes"] == packed == n + 4 * rows
        assert w["bytes"] * 3.5 < n * 4        # >= 3.5x under fp32 wire


def test_bytes_fn_drift_raises_accounting_error():
    """A physical transform whose bytes_fn lies about the payload must
    be caught the moment a value crosses the wire."""
    lying = WireTransform(
        name="lying_quant",
        apply=lambda t, name, d: wc.pack_int8(wc.as_dense(t)),
        bytes_fn=lambda shape, dtype, nbytes: nbytes,   # claims dense!
        physical=True)
    sess = Plan(mode="vanilla", model=make_model(), cut=2,
                loss_fn=softmax_xent, optimizer=optim.sgd(0.05),
                n_clients=2, sync="none", wire=(lying,)).compile()
    with pytest.raises(WireAccountingError, match="drifted"):
        sess.wire_report(image_shards(jax.random.PRNGKey(29), 2))


def test_stack_handoff_bytes_price_int8():
    stack = WireStack((quantize_int8(physical=True),))
    tree = {"w": jnp.zeros((9, 3, 3, 16)), "b": jnp.zeros((16,))}
    expect = (9 * 3 * 3 * 16 + 9 * 3 * 3 * 4) + (16 + 4)
    assert stack.handoff_bytes(tree) == expect
    assert stack.tree_wire_bytes(tree) == expect


# ---------------------------------------------------------------------------
# REPRO_KERNELS dispatch
# ---------------------------------------------------------------------------

def test_repro_kernels_env_dispatch(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    assert ops.kernel_mode() == "ref"
    monkeypatch.setenv("REPRO_KERNELS", "interp")
    assert ops.kernel_mode() == "interp"
    # pallas is the real lowering: on a host with no TPU it raises
    # instead of quietly running the interpreter
    monkeypatch.setenv("REPRO_KERNELS", "pallas")
    if any(d.platform == "tpu" for d in jax.devices()):
        assert ops.kernel_mode() == "pallas"
    else:
        with pytest.raises(RuntimeError, match="needs a TPU"):
            ops.kernel_mode()
    monkeypatch.setenv("REPRO_KERNELS", "bogus")
    with pytest.raises(ValueError, match="REPRO_KERNELS"):
        ops.kernel_mode()
    # unset: the automatic default follows the backend
    monkeypatch.delenv("REPRO_KERNELS")
    assert ops.kernel_mode() == ("pallas" if any(
        d.platform == "tpu" for d in jax.devices()) else "interp")


def test_all_kernel_modes_agree_on_wire_quant(monkeypatch):
    x = jax.random.normal(KEY, (10, 48))
    outs = {}
    for mode in ("interp", "ref"):
        monkeypatch.setenv("REPRO_KERNELS", mode)
        q, s = ops.wire_quantize(x)
        outs[mode] = (np.asarray(q), np.asarray(s))
    np.testing.assert_array_equal(outs["interp"][0], outs["ref"][0])
    np.testing.assert_array_equal(outs["interp"][1], outs["ref"][1])


def test_eager_wire_kernels_compile_once():
    """Called outside any jit (the serving batcher densifies each step's
    logits eagerly), the wire kernels reuse one compiled program per
    shape instead of compiling on every call."""
    x = jax.random.normal(KEY, (4, 40))
    q, s = ops.wire_quantize(x)                       # warm both kernels
    ops.wire_dequantize(q, s)
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for _ in range(3):
            q, s = ops.wire_quantize(x)
            ops.wire_dequantize(q, s)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []


def test_ref_mode_trains_a_physical_plan(monkeypatch):
    """The whole physical path also runs on the pure-jnp oracles —
    REPRO_KERNELS=ref is a usable debugging lane."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    key = jax.random.PRNGKey(31)
    sess = Plan(mode="vanilla", model=make_model(), cut=2,
                loss_fn=softmax_xent, optimizer=optim.adamw(1e-2),
                n_clients=2,
                wire=(quantize_int8(physical=True),)).compile()
    sess.init(key)
    losses = sess.fit(lambda r: image_shards(jax.random.fold_in(key, r), 2),
                      rounds=3)
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# fleet: the ppermute ring carries the packed handoff
# ---------------------------------------------------------------------------

@pytest.mark.skipif(os.environ.get("XLA_FLAGS", "").find(
    "host_platform_device_count") < 0 and jax.device_count() < 2,
    reason="needs >1 (virtual) device")
def test_fleet_ring_physical_matches_engine():
    from repro.engine.fleet import FleetSpec
    key = jax.random.PRNGKey(37)
    n = jax.device_count()
    mk = lambda fleet: Plan(
        mode="vanilla", model=make_model(), cut=2, loss_fn=softmax_xent,
        optimizer=optim.sgd(0.05), n_clients=n,
        wire=(quantize_int8(physical=True),),
        fleet=FleetSpec(n_devices=n) if fleet else None).compile()
    single, fleet = mk(False), mk(True)
    for s in (single, fleet):
        s.init(key)
        s.fit(lambda r: image_shards(jax.random.fold_in(key, r), n),
              rounds=2)
    for a, b in zip(jax.tree_util.tree_leaves(single.state["clients"]),
                    jax.tree_util.tree_leaves(fleet.state["clients"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
