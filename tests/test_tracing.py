"""The program's own tracing.

* a profiler capture of a reduced `Batcher` (two joins, three steps)
  and of reduced round-robin rounds holds every `repro.*` host span,
  nested as documented, with its `tenant` / `slot` / `round` arguments;
* `Batcher.host_reads` and `RoundEngine.host_reads` are exact: one per
  join, one per step with a live tenant, one per round;
* the compiled round carries an `op_name` under every IR step scope
  and under `optimizer`, for both vanilla turn functions; the compiled
  server step and prefill of an MLA + MoE model carry the MoE and MLA
  scopes;
* none of it compiles anything new on a second call.
"""
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import optim
from repro.api import Plan, SplitFns
from repro.api.wire import parse_wire
from repro.configs import get_config
from repro.core import split as sp
from repro.core.wire_compress import stack_packed
from repro.engine import program as ir
from repro.serve import Batcher, ServePlan, ServeSession

N_CLIENTS, BATCH, D_IN, N_CLASSES = 2, 4, 8, 4
ROUND_PARTS = ("repro.engine.host_read", "repro.engine.turn_cost",
               "repro.engine.launch", "repro.engine.account")
JOIN_PARTS = ("repro.batcher.prefill", "repro.batcher.scatter",
              "repro.batcher.price", "repro.batcher.first_token")
STEP_PARTS = ("repro.batcher.client", "repro.batcher.stack",
              "repro.batcher.server", "repro.batcher.tokens")
SCOPES = ("ClientFwd", "SendCut", "ServerFwdBwd", "RecvGrad", "ClientBwd",
          "WeightHandoff", ir.OPTIMIZER_SCOPE)


def ce(logits, labels):
    lp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(lp, labels[:, None], 1).mean()


def _mlp_init(key):
    ks = jax.random.split(key, 3)
    dims = (D_IN, 16, 16, N_CLASSES)
    return [{"w": 0.3 * jax.random.normal(k, (a, b)), "b": jnp.zeros(b)}
            for k, a, b in zip(ks, dims, dims[1:])]


def _layer(p, i, x):
    y = x @ p["w"] + p["b"]
    return jax.nn.relu(y) if i < 2 else y


def _seg_model():
    return sp.list_segmodel(n_segments=3, init=_mlp_init, layer_apply=_layer)


def _fns_model():
    """The same MLP through `SplitFns` (`topology.vanilla_fns`)."""
    def client(pc, b):
        return _layer(pc[0], 0, b["x"])

    def server(ps, a):
        return _layer(ps[1], 2, _layer(ps[0], 1, a))
    return SplitFns(init=_mlp_init, split=lambda p: (p[:1], p[1:]),
                    client_apply=client, server_apply=server)


def _session(model):
    sess = Plan(mode="vanilla", model=model, cut=1, n_clients=N_CLIENTS,
                schedule="round_robin", sync="p2p",
                optimizer=optim.adam(1e-3), loss_fn=ce,
                wire=parse_wire("quantize_int8")).compile()
    sess.init(jax.random.PRNGKey(0))
    return sess


BATCHES = {"x": jnp.ones((N_CLIENTS, BATCH, D_IN)),
           "labels": jnp.zeros((N_CLIENTS, BATCH), jnp.int32)}


def capture(tmp_path, fn) -> list:
    """Run `fn` under a profiler session; the `repro.*` host spans it
    recorded as [(name, start_ns, end_ns, arguments)], by start.  Host
    events of level 1 only: the annotations, not the runtime's own."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 1, 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        fn()
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            spans += [(e.name, int(e.start_ns), int(e.end_ns), dict(e.stats))
                      for line in plane.lines for e in line.events
                      if e.name.startswith("repro.")]
    return sorted(spans, key=lambda s: s[1])


def parent(spans, span):
    """The innermost other span that holds `span`, or None."""
    held = [s for s in spans if s is not span
            and s[1] <= span[1] and span[2] <= s[2]]
    return min(held, key=lambda s: s[2] - s[1]) if held else None


def named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.fixture(scope="module")
def engine_run():
    """A reduced session, warmed, then two rounds under the profiler."""
    sess = _session(_seg_model())
    sess.run_round(BATCHES)
    jax.block_until_ready(sess.state)
    sizes = sess.engine._round_jit._cache_size()
    reads = sess.engine.host_reads

    def two_rounds():
        for _ in range(2):
            jax.block_until_ready(sess.run_round(BATCHES))
    return sess, sizes, reads, two_rounds


@pytest.fixture(scope="module")
def batcher_run():
    """A reduced split server, warmed on two joins and a step."""
    cfg = get_config("phi4_mini_3_8b").reduced(vocab=97)
    bat = Batcher(ServeSession(ServePlan(arch=cfg, cut=1, max_batch=2,
                                         max_len=24),
                               jax.random.PRNGKey(0)))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (6,), 0, cfg.vocab)
    bat.join(prompt, 2)
    bat.join(prompt, 2)
    bat.run()
    jits = (bat._jit_client, bat._jit_server, bat._jit_scatter,
            bat.session._jit_prefill)
    return bat, prompt, jits


def test_round_spans_nest_with_their_round(engine_run, tmp_path):
    sess, _, _, two_rounds = engine_run
    first = sess.engine.rounds
    spans = capture(tmp_path, two_rounds)
    rounds = named(spans, "repro.engine.run_round")
    assert [r[3]["round"] for r in rounds] == [first, first + 1]
    assert all(parent(spans, r) is None for r in rounds)
    for name in ROUND_PARTS:
        found = named(spans, name)
        assert len(found) == 2, name
        assert all(parent(spans, s)[0] == "repro.engine.run_round"
                   for s in found)


def test_batcher_spans_nest_with_tenant_and_slot(batcher_run, tmp_path):
    bat, prompt, _ = batcher_run
    serial = bat.joined
    slots = []

    def serve():
        slots.extend(bat.join(prompt, 8) for _ in range(2))
        for _ in range(3):
            bat.step()
    spans = capture(tmp_path, serve)
    joins = named(spans, "repro.batcher.join")
    assert [(j[3]["tenant"], j[3]["slot"]) for j in joins] == [
        (serial, slots[0]), (serial + 1, slots[1])]
    steps = named(spans, "repro.batcher.step")
    assert [s[3]["live"] for s in steps] == [2, 2, 2]
    assert all(parent(spans, s) is None for s in joins + steps)
    for names, outer, count in ((JOIN_PARTS, "repro.batcher.join", 2),
                                (STEP_PARTS, "repro.batcher.step", 3)):
        for name in names:
            found = named(spans, name)
            # one client dispatch per live tenant per step
            want = 2 * count if name == "repro.batcher.client" else count
            assert len(found) == want, name
            assert all(parent(spans, s)[0] == outer for s in found), name
    clients = named(spans, "repro.batcher.client")
    assert sorted({(c[3]["tenant"], c[3]["slot"]) for c in clients}) == \
        sorted((serial + i, s) for i, s in enumerate(slots))
    bat.run()
    bat.finished.clear()


def test_host_reads_are_exact(engine_run, batcher_run):
    sess, _, _, _ = engine_run
    reads, rounds = sess.engine.host_reads, sess.engine.rounds
    for _ in range(3):
        sess.run_round(BATCHES)
    assert sess.engine.host_reads - reads == sess.engine.rounds - rounds == 3

    bat, prompt, _ = batcher_run
    reads, tokens, steps = bat.host_reads, bat.tokens_generated, bat.steps
    bat.join(prompt, 8)
    bat.join(prompt, 8)
    assert bat.host_reads - reads == 2                  # one per join
    for _ in range(3):
        bat.step()
    assert bat.host_reads - reads == 2 + 3              # one per step
    assert bat.steps - steps == 3
    assert bat.host_reads - bat.joined == bat.steps
    assert bat.tokens_generated - tokens == 2 + 2 * 3   # one per live tenant
    bat.run()
    bat.finished.clear()


@pytest.mark.parametrize("make", [_seg_model, _fns_model],
                         ids=["segmodel", "split_fns"])
def test_compiled_round_carries_every_step_scope(make):
    sess = _session(make())
    # a persistent cache's key leaves metadata out: an entry compiled
    # from a program without scopes would come back without them
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = sess.engine._round_jit.lower(
            sess.state, BATCHES).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in SCOPES:
        assert any(re.search(rf"\b{scope}\b", p) for p in paths), scope
    # backward ops carry the scope their vjp was called in
    assert any("ClientBwd/transpose(" in p for p in paths)


def _compiled_text(jitted, *args) -> str:
    """Compiled text of a jitted function, outside the persistent cache
    (its key leaves metadata out: an entry compiled without scopes would
    come back without them)."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jitted.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


def test_compiled_serving_carries_moe_and_mla_scopes():
    """The batched server step (decode through the latent cache, MoE
    layers) carries every MoE scope and `MLAAttend`; the prefill's
    attention is under `MLAAttend` too."""
    cfg = get_config("deepseek_v2_lite").reduced(vocab=97, experts_held=2)
    bat = Batcher(ServeSession(ServePlan(arch=cfg, max_batch=2, max_len=24,
                                         wire="quantize_int8:physical"),
                               jax.random.PRNGKey(0)))
    sess = bat.session
    payload = stack_packed([bat._part(b) for b in range(2)], axis=0)
    step = _compiled_text(bat._jit_server, sess.server_params, payload,
                          bat._sc)
    prefill = _compiled_text(sess._jit_prefill, sess.client_params,
                             sess.server_params,
                             {"tokens": jnp.zeros((1, 6), jnp.int32)})
    for text, scopes in ((step, ("MoERoute", "MoEExperts", "MoECombine",
                                 "MLAAttend")),
                         (prefill, ("MoEExperts", "MLAAttend"))):
        paths = re.findall(r'op_name="([^"]*)"', text)
        for scope in scopes:
            assert any(re.search(rf"\b{scope}\b", p) for p in paths), scope


def test_spans_compile_nothing_new(engine_run, batcher_run, tmp_path):
    sess, sizes, _, two_rounds = engine_run
    bat, prompt, jits = batcher_run
    before = [j._cache_size() for j in jits]

    def both():
        two_rounds()
        bat.join(prompt, 3)
        bat.run()
    capture(tmp_path, both)
    both()
    bat.finished.clear()
    assert sess.engine._round_jit._cache_size() == sizes
    assert [j._cache_size() for j in jits] == before
