"""Engine-equivalence and jit-safe-accounting tests.

The compiled round engine must be a drop-in for the eager trainers:
  * scanned round-robin == eager SplitTrainer loop — same per-round
    losses, same final client/server params (allclose at fp32 tolerance,
    losses bitwise in practice since the op sequence is identical);
  * analytic TurnCost accumulation == eager Meter byte/FLOP totals,
    exactly (they are integers / identical float probes);
  * the parallel (SplitFed-style) schedule and the u-shaped / vertical /
    multihop topologies train.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import protocol as pr
from repro.core import split as sp
from repro.data import synthetic as syn
from repro.engine import (RoundEngine, multihop, stack_batches, stack_state,
                          stack_trees, topology, u_shaped, unstack_tree,
                          vanilla, vertical)
from repro.nn import convnets as C
from repro.nn import layers as L


def ce(logits, labels):
    lp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(lp, labels[:, None], 1).mean()


CFG = C.CNNConfig(name="t", width_mult=0.25, plan=(16, 16, "M", 32, "M"),
                  n_classes=4)
PLAN = C.vgg_plan(CFG)


def make_model():
    return sp.list_segmodel(
        n_segments=len(PLAN),
        init=lambda k: C.vgg_init(k, CFG),
        layer_apply=lambda p, i, x: C.vgg_layer_apply(p, PLAN[i], x))


def client_shards(key, n_clients, per=16):
    b = syn.image_batch(key, per * n_clients, 4)
    return [{"x": b["images"][i * per:(i + 1) * per],
             "labels": b["labels"][i * per:(i + 1) * per]}
            for i in range(n_clients)]


def tree_allclose(a, b, atol=1e-6):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol)


# ---------------------------------------------------------------------------
# scanned round-robin == eager loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sync", ["p2p", "none"])
def test_scan_matches_eager_split_trainer(sync):
    n = 3
    mk = lambda: dict(model=make_model(), cut=2, loss_fn=ce,
                      optimizer_client=optim.sgd(0.05, 0.9),
                      optimizer_server=optim.sgd(0.05, 0.9),
                      n_clients=n, sync=sync)
    eager = pr.SplitTrainer(**mk(), backend="eager")
    comp = pr.SplitTrainer(**mk(), backend="engine")
    key = jax.random.PRNGKey(0)
    st_e, st_c = eager.init(key), comp.init(key)
    for r in range(3):
        key, k = jax.random.split(key)
        shards = client_shards(k, n)
        st_e, loss_e = eager.train_round(st_e, shards)
        st_c, loss_c = comp.train_round(st_c, shards)
        np.testing.assert_allclose(float(loss_c), float(loss_e), atol=1e-6)
    for i in range(n):
        tree_allclose(st_c["clients"][i], st_e["clients"][i])
    tree_allclose(st_c["server"], st_e["server"])
    assert st_c["last_trained"] == st_e["last_trained"] == n - 1


def test_engine_accounting_matches_eager_meter():
    """Analytic TurnCost accumulation must equal the eager wire/FLOP
    meters EXACTLY (bytes are ints, flops come from the same probe)."""
    n = 3
    mk = lambda: dict(model=make_model(), cut=2, loss_fn=ce,
                      optimizer_client=optim.sgd(0.05),
                      optimizer_server=optim.sgd(0.05), n_clients=n)
    eager = pr.SplitTrainer(**mk(), backend="eager")
    comp = pr.SplitTrainer(**mk(), backend="engine")
    key = jax.random.PRNGKey(1)
    st_e, st_c = eager.init(key), comp.init(key)
    for r in range(2):
        key, k = jax.random.split(key)
        shards = client_shards(k, n)
        st_e, _ = eager.train_round(st_e, shards)
        st_c, _ = comp.train_round(st_c, shards)
    assert comp.meter.bytes_up == eager.meter.bytes_up
    assert comp.meter.bytes_down == eager.meter.bytes_down
    assert comp.meter.sync_bytes == eager.meter.sync_bytes
    assert comp.meter.flops == eager.meter.flops
    assert sum(comp.meter.sync_bytes) > 0       # p2p handoffs metered


def test_engine_evaluate_matches_trainer():
    tr = pr.SplitTrainer(model=make_model(), cut=2, loss_fn=ce,
                         optimizer_client=optim.adamw(1e-2),
                         optimizer_server=optim.adamw(1e-2), n_clients=2)
    key = jax.random.PRNGKey(2)
    state = tr.init(key)
    state, _ = tr.train_round(state, client_shards(key, 2))
    ev = syn.image_batch(jax.random.PRNGKey(9), 32, 4)
    batch = {"x": ev["images"], "labels": ev["labels"]}
    acc_tr = float(tr.evaluate(state, batch))
    est = stack_state(state, 2)
    acc_en = float(tr.engine.evaluate(est, batch))
    assert acc_tr == acc_en
    # evaluate_all scores every stack slice at once; identical init +
    # identical rounds keep both clients' slices in agreement with the
    # single-slice path here
    accs = tr.engine.evaluate_all(est, batch)
    assert accs.shape == (2,)
    assert float(accs[0]) == acc_tr


# ---------------------------------------------------------------------------
# parallel (SplitFed) schedule
# ---------------------------------------------------------------------------

def test_parallel_schedule_trains_and_keeps_clients_independent():
    n = 4
    eng = RoundEngine(topology=vanilla(make_model(), 2), loss_fn=ce,
                      optimizer_client=optim.adamw(1e-2),
                      optimizer_server=optim.adamw(1e-2),
                      n_clients=n, schedule="parallel")
    key = jax.random.PRNGKey(3)
    st = eng.init(key)
    losses = []
    for r in range(10):
        key, k = jax.random.split(key)
        st, ls = eng.run_round(st, stack_batches(client_shards(k, n)))
        assert ls.shape == (n,)
        losses.append(float(ls.mean()))
    assert losses[-1] < losses[0], losses
    # no weight handoff: clients diverge (different local batches)
    leaves = jax.tree_util.tree_leaves(st["clients"])
    assert any(float(jnp.abs(a[0] - a[1]).max()) > 0 for a in leaves)
    # and no p2p sync bytes were metered
    assert sum(eng.meter.sync_bytes) == 0
    assert all(b > 0 for b in eng.meter.bytes_up)


# ---------------------------------------------------------------------------
# u-shaped topology through the engine
# ---------------------------------------------------------------------------

def test_u_shaped_round_matches_eager_turns():
    n = 2
    mk = lambda: dict(model=make_model(), cut1=1, cut2=4, loss_fn=ce,
                      optimizer=optim.adamw(3e-3), n_clients=n)
    eager = pr.UShapedTrainer(**mk())
    comp = pr.UShapedTrainer(**mk())
    key = jax.random.PRNGKey(4)
    st_e, st_c = eager.init(key), comp.init(key)
    for r in range(2):
        key, k = jax.random.split(key)
        shards = client_shards(k, n, per=8)
        for ci, b in enumerate(shards):
            st_e, loss_e = eager.client_turn(st_e, ci, b)
        st_c, loss_c = comp.train_round(st_c, shards)
        assert jnp.isfinite(loss_c)
    for i in range(n):
        tree_allclose(st_c["clients"][i], st_e["clients"][i])
    tree_allclose(st_c["server"], st_e["server"])
    # wires match: u-shaped has 4 wires/turn (act1 up, act2 down,
    # g_act2 up, g_act1 down)
    assert comp.meter.bytes_up == eager.meter.bytes_up
    assert comp.meter.bytes_down == eager.meter.bytes_down
    # neither backend meters FLOPs for the label-private configuration
    assert comp.meter.flops == eager.meter.flops == [0.0] * n


# ---------------------------------------------------------------------------
# vertical topology (parallel-only)
# ---------------------------------------------------------------------------

def _branch(dim_in, dim_out):
    return sp.Branch(
        init=lambda k: {"w": L.dense_init(k, dim_in, dim_out, bias=True)},
        apply=lambda p, x: jax.nn.relu(L.dense_apply(p["w"], x)))


def test_vertical_topology_trains():
    n, din, dfeat, ncls = 2, 64, 16, 4
    trunk_init = lambda k: {"w": L.dense_init(k, n * dfeat, ncls,
                                              bias=True)}
    trunk_apply = lambda p, f: L.dense_apply(p["w"], f)
    topo = vertical(_branch(din, dfeat), n, trunk_init, trunk_apply)
    eng = RoundEngine(topology=topo, loss_fn=ce,
                      optimizer_client=optim.adamw(1e-2),
                      optimizer_server=optim.adamw(1e-2),
                      n_clients=n, schedule="parallel")
    key = jax.random.PRNGKey(5)
    st = eng.init(key, identical_clients=False)
    losses = []
    for r in range(30):
        key, k = jax.random.split(key)
        b = syn.multimodal_batch(k, 32, ncls, dim_a=din, dim_b=din)
        batch = {"x": jnp.stack([b["mod_a"], b["mod_b"]]),
                 "labels": b["labels"]}
        st, ls = eng.run_round(st, batch)
        losses.append(float(ls.mean()))
    assert losses[-1] < losses[0] - 0.2, (losses[0], losses[-1])
    # per-client wires: each client pays only for ITS branch act/grad
    assert all(b > 0 for b in eng.meter.bytes_up)
    assert all(b > 0 for b in eng.meter.bytes_down)
    ev = syn.multimodal_batch(jax.random.PRNGKey(6), 64, ncls,
                              dim_a=din, dim_b=din)
    acc = float(eng.evaluate(st, {"x": jnp.stack([ev["mod_a"],
                                                  ev["mod_b"]]),
                                  "labels": ev["labels"]}))
    assert acc > 0.5


def test_vertical_rejects_round_robin():
    topo = vertical(_branch(8, 4), 2, lambda k: {}, lambda p, f: f)
    with pytest.raises(ValueError, match="parallel-only"):
        RoundEngine(topology=topo, loss_fn=ce,
                    optimizer_client=optim.sgd(0.1),
                    optimizer_server=optim.sgd(0.1), n_clients=2)


# ---------------------------------------------------------------------------
# multihop topology
# ---------------------------------------------------------------------------

def test_multihop_round_robin_trains_and_meters_hops():
    n = 2
    topo = multihop(make_model(), cuts=[1, 3])
    eng = RoundEngine(topology=topo, loss_fn=ce,
                      optimizer_client=optim.adamw(1e-2),
                      optimizer_server=optim.adamw(1e-2), n_clients=n)
    key = jax.random.PRNGKey(7)
    st = eng.init(key)
    # one repeated round of data: learning shows as a falling loss on it
    # (fresh random batches each round leave no margin to assert on)
    batches = stack_batches(client_shards(jax.random.fold_in(key, 1), n))
    losses = []
    for r in range(10):
        st, ls = eng.run_round(st, batches)
        losses.append(float(ls.mean()))
    assert losses[-1] < losses[0], losses
    # two hops -> 2 up + 2 down wires per turn probed...
    cost = next(iter(eng._turn_costs.values()))
    ups = [w for w in cost.wires if w.direction == "up"]
    downs = [w for w in cost.wires if w.direction == "down"]
    assert len(ups) == 2 and len(downs) == 2
    # ...but the data client is only billed for the FIRST hop's wire;
    # hop-to-hop traffic downstream is server-side
    hop0_up = sum(w.bytes for w in ups if w.name == "hop_0_act")
    assert eng.meter.bytes_up[0] == 10 * hop0_up


# ---------------------------------------------------------------------------
# stacked-state helpers round-trip
# ---------------------------------------------------------------------------

def test_stack_unstack_roundtrip():
    key = jax.random.PRNGKey(8)
    trees = [{"a": jax.random.normal(jax.random.fold_in(key, i), (3, 2)),
              "b": jnp.full((4,), float(i))} for i in range(5)]
    back = unstack_tree(stack_trees(trees), 5)
    for t0, t1 in zip(trees, back):
        tree_allclose(t0, t1, atol=0)


def test_ragged_batches_fall_back_to_eager():
    """Unequal per-client batch sizes (dataset remainder) cannot stack;
    the wrapper must keep the eager per-turn path working."""
    tr = pr.SplitTrainer(model=make_model(), cut=2, loss_fn=ce,
                         optimizer_client=optim.sgd(0.05),
                         optimizer_server=optim.sgd(0.05), n_clients=2)
    key = jax.random.PRNGKey(10)
    state = tr.init(key)
    b = syn.image_batch(key, 24, 4)
    ragged = [{"x": b["images"][:16], "labels": b["labels"][:16]},
              {"x": b["images"][16:], "labels": b["labels"][16:]}]
    state, loss = tr.train_round(state, ragged)
    assert jnp.isfinite(loss)
    assert state["last_trained"] == 1
    assert all(u > 0 for u in tr.meter.bytes_up)


def test_topology_kind_validation():
    eng = RoundEngine(topology=vanilla(make_model(), 2), loss_fn=ce,
                      optimizer_client=optim.sgd(0.1),
                      optimizer_server=optim.sgd(0.1), n_clients=2)
    with pytest.raises(ValueError, match="schedule"):
        RoundEngine(topology=vanilla(make_model(), 2), loss_fn=ce,
                    optimizer_client=optim.sgd(0.1),
                    optimizer_server=optim.sgd(0.1), n_clients=2,
                    schedule="bogus")
    assert eng.schedule == "round_robin"
