"""Split-serving engine parity + metering suite (serve/).

* compiled prefill == per-token decode-loop prefill (logits and caches),
  for an attention arch, an SSM arch, and the encoder-decoder;
* split greedy decode (fp32 wire) generates token-for-token what the
  MONOLITHIC model generates — the cut is invisible at the protocol
  level;
* the physical packed-int8 wire generates BIT-IDENTICAL tokens to the
  fake-quant wire (`dequant(pack(x)) == fake_quant(x)`), and its metered
  decode payload is >= 3x smaller than the fp32 split wire's, derived
  from the actual packed leaf dtypes via `TurnCost`;
* the multi-tenant `Batcher` reproduces every tenant's solo token
  stream slot-for-slot, including a tenant joining mid-flight; a step
  reads its tokens on the host once, however many tenants are live,
  and a tenant decodes next from whatever its `cur` holds;
* the fused packed-entry path (`splitcat_linear_packed` consuming the
  payload inside the server's first block) generates the same tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.wire_compress import PackedInt8, payload_nbytes, stack_packed
from repro.models import build_model
from repro.models.registry import supports_split_serving
from repro.serve import Batcher, ServePlan, ServeSession, greedy_decode_scan

B, S, GEN = 2, 7, 6
MAX_LEN = S + GEN + 2


def _setup(arch, **red):
    cfg = get_config(arch).reduced(vocab=97, **red)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    return cfg, model, params, prompt


def _mono_generate(model, params, prompt, max_new):
    cache = model.init_cache(prompt.shape[0], MAX_LEN)
    logits, cache = model.prefill(params, {"tokens": prompt}, cache)
    tok0 = jnp.argmax(logits[:, -1], -1)[:, None]
    rest, _ = greedy_decode_scan(model, params, cache, tok0, max_new - 1)
    return jnp.concatenate([tok0, rest], 1)


ARCHS = [("phi4_mini_3_8b", {}),                     # GQA attention
         ("mamba2_130m", {}),                        # SSM ring-free cache
         ("recurrentgemma_2b", {"n_layers": 6}),     # rglru+window hybrid
         ("deepseek_v2_lite",                        # MLA latent cache, YaRN,
          {"experts_held": 2, "expert_offset": 2})]  # MoE: a chip's share
# the stacked-cache paths: attention, SSM and the latent cache with MoE
BATCHED = ARCHS[:2] + ARCHS[3:]


@pytest.mark.parametrize("arch,red", ARCHS, ids=[a for a, _ in ARCHS])
def test_prefill_matches_decode_loop(arch, red):
    """ONE compiled prefill == the O(S) decode_step loop: same
    last-position logits, and greedy continuation token-identical."""
    cfg, model, params, prompt = _setup(arch, **red)
    cache_l = model.init_cache(B, MAX_LEN)
    logits_l = None
    for t in range(S):
        logits_l, cache_l = model.decode_step(params, prompt[:, t:t + 1],
                                              cache_l)
    cache_p = model.init_cache(B, MAX_LEN)
    logits_p, cache_p = model.prefill(params, {"tokens": prompt}, cache_p)
    np.testing.assert_allclose(np.asarray(logits_l[:, -1]),
                               np.asarray(logits_p[:, -1]),
                               rtol=1e-4, atol=1e-4)
    tok = jnp.argmax(logits_p[:, -1], -1)[:, None]
    a, _ = greedy_decode_scan(model, params, cache_l, tok, GEN)
    b, _ = greedy_decode_scan(model, params, cache_p, tok, GEN)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_prefill_matches_decode_loop_encdec():
    cfg = get_config("whisper_base").reduced(vocab=97)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    audio = 0.02 * jax.random.normal(
        jax.random.PRNGKey(2), (B, cfg.n_audio_frames, cfg.d_model),
        cfg.dtype)
    cache_l = model.init_cache(params, audio, MAX_LEN)
    logits_l = None
    for t in range(S):
        logits_l, cache_l = model.decode_step(params, prompt[:, t:t + 1],
                                              cache_l)
    cache_p = model.init_cache(params, audio, MAX_LEN)
    logits_p, cache_p = model.prefill(params, prompt, cache_p)
    np.testing.assert_allclose(np.asarray(logits_l[:, -1]),
                               np.asarray(logits_p[:, -1]),
                               rtol=1e-4, atol=1e-4)
    tok = jnp.argmax(logits_p[:, -1], -1)[:, None]
    a, _ = greedy_decode_scan(model, params, cache_l, tok, GEN)
    b, _ = greedy_decode_scan(model, params, cache_p, tok, GEN)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch,red", ARCHS, ids=[a for a, _ in ARCHS])
def test_split_fp32_matches_monolithic(arch, red):
    cfg, model, params, prompt = _setup(arch, **red)
    mono = _mono_generate(model, params, prompt, GEN)
    sess = ServeSession(ServePlan(arch=cfg, max_batch=B, max_len=MAX_LEN),
                        params)
    split = sess.generate(prompt, GEN)
    assert np.array_equal(np.asarray(mono), np.asarray(split))


@pytest.mark.parametrize("arch,red", BATCHED, ids=[a for a, _ in BATCHED])
def test_packed_wire_bitwise_fake_and_3x_smaller(arch, red):
    cfg, model, params, prompt = _setup(arch, **red)
    mk = lambda wire: ServeSession(
        ServePlan(arch=cfg, max_batch=B, max_len=MAX_LEN, wire=wire), params)
    phys, fake, fp32 = (mk("quantize_int8:physical"), mk("quantize_int8"),
                        mk(""))
    t_phys = phys.generate(prompt, GEN)
    t_fake = fake.generate(prompt, GEN)
    assert np.array_equal(np.asarray(t_phys), np.asarray(t_fake))

    c_q8, c_fp = phys.decode_cost(batch=1), fp32.decode_cost(batch=1)
    b_q8 = c_q8.bytes_up + c_q8.bytes_down
    b_fp = c_fp.bytes_up + c_fp.bytes_down
    assert b_fp >= 3 * b_q8, (b_fp, b_q8)
    # physical records are priced from the ACTUAL packed leaf dtypes
    assert all(w.physical for w in c_q8.wires)


def test_session_init_matches_split_params():
    """A session built from a key (init and split in one compiled
    program) holds exactly the halves `split_params` cuts from the
    compiled `model.init`, and its tied head shares the client's
    embedding.  (A compiled init may round differently from the eager
    one: the reference is compiled too.)"""
    cfg = get_config("phi4_mini_3_8b").reduced(vocab=97)
    model = build_model(cfg)
    key = jax.random.PRNGKey(3)
    sess = ServeSession(ServePlan(arch=cfg, max_batch=1, max_len=MAX_LEN),
                        key)
    want = model.split_params(jax.jit(model.init)(key), sess.cut)
    got = (sess.client_params, sess.server_params)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert sess.server_params["tied_head"] is sess.client_params["embed"]


def test_decode_cost_counts_both_hops():
    cfg, model, params, prompt = _setup("phi4_mini_3_8b")
    sess = ServeSession(ServePlan(arch=cfg, max_batch=1, max_len=MAX_LEN,
                                  wire="quantize_int8:physical"), params)
    cost = sess.decode_cost(batch=1)
    names = sorted(w.name for w in cost.wires)
    assert names == ["cut_act", "logits"]
    assert cost.bytes_up > 0 and cost.bytes_down > 0
    # up hop: d_model int8 + one fp32 scale per row
    assert cost.bytes_up == cfg.d_model + 4


@pytest.mark.parametrize("arch,red", BATCHED, ids=[a for a, _ in BATCHED])
def test_batcher_matches_solo_slot_for_slot(arch, red):
    cfg, model, params, prompt = _setup(arch, **red)
    solo = ServeSession(ServePlan(arch=cfg, max_batch=B, max_len=MAX_LEN,
                                  wire="quantize_int8:physical"),
                        params).generate(prompt, GEN)
    sess = ServeSession(ServePlan(arch=cfg, max_batch=3, max_len=MAX_LEN,
                                  wire="quantize_int8:physical"), params)
    bat = Batcher(sess)
    s0 = bat.join(prompt[0], GEN)
    s1 = bat.join(prompt[1], GEN)
    got = {t.slot: t.tokens for t in bat.run()}
    want = np.asarray(solo)
    assert got[s0] == [int(x) for x in want[0]]
    assert got[s1] == [int(x) for x in want[1]]
    assert bat.bytes_per_token > 0 and bat.tokens_generated == 2 * GEN


def test_batcher_midstream_join():
    """Continuous batching: a tenant joining after 3 steps still gets
    its exact solo stream; the incumbent is unperturbed."""
    cfg, model, params, prompt = _setup("phi4_mini_3_8b")
    solo = ServeSession(ServePlan(arch=cfg, max_batch=B, max_len=MAX_LEN,
                                  wire="quantize_int8:physical"),
                        params).generate(prompt, GEN)
    sess = ServeSession(ServePlan(arch=cfg, max_batch=3, max_len=MAX_LEN,
                                  wire="quantize_int8:physical"), params)
    bat = Batcher(sess)
    s0 = bat.join(prompt[0], GEN)
    for _ in range(3):
        bat.step()
    s1 = bat.join(prompt[1], GEN)
    got = {t.slot: t.tokens for t in bat.run()}
    want = np.asarray(solo)
    assert got[s0] == [int(x) for x in want[0]]
    assert got[s1] == [int(x) for x in want[1]]


@pytest.fixture(scope="module")
def batcher3():
    cfg, _, params, prompt = _setup("phi4_mini_3_8b")
    bat = Batcher(ServeSession(ServePlan(arch=cfg, max_batch=3,
                                         max_len=MAX_LEN,
                                         wire="quantize_int8:physical"),
                               params))
    return cfg, bat, prompt


@pytest.mark.parametrize("live", [1, 3], ids=["one_live", "batch_full"])
def test_batcher_step_reads_tokens_once(batcher3, live):
    """One host read per step whatever the occupancy; each tenant's next
    client step takes its `cur`, also when overwritten between steps."""
    cfg, bat, prompt = batcher3
    for i in range(live):
        bat.join(prompt[i % B], GEN)
    assert bat.host_reads - bat.joined == bat.steps
    taken = []                          # each client step's token, slot order
    client = bat._jit_client

    def spy(cp, tok, cc):
        taken.append(int(np.asarray(tok)[0, 0]))
        return client(cp, tok, cc)
    bat._jit_client = spy
    try:
        for n in range(3):
            reads, steps = bat.host_reads, bat.steps
            tenants = sorted(bat.tenants.values(), key=lambda t: t.slot)
            first = tenants[0]
            if n == 1:                          # the planted fault's move
                first.tokens[-1] = (first.tokens[-1] + 1) % cfg.vocab
                first.cur = jnp.asarray([[first.tokens[-1]]], jnp.int32)
            want = [t.tokens[-1] for t in tenants]
            del taken[:]
            out = bat.step()
            assert taken == want
            assert sorted(out) == [t.slot for t in tenants]
            assert bat.host_reads - reads == bat.steps - steps == 1
            assert bat.host_reads - bat.joined == bat.steps
    finally:
        bat._jit_client = client
    bat.run()
    assert bat.host_reads - bat.joined == bat.steps


def test_batcher_eos_frees_slot():
    cfg, model, params, prompt = _setup("phi4_mini_3_8b")
    sess = ServeSession(ServePlan(arch=cfg, max_batch=1, max_len=MAX_LEN,
                                  wire="quantize_int8:physical"), params)
    solo = [int(t) for t in np.asarray(sess.generate(prompt[:1], GEN))[0]]
    # EOS is checked on every sampled token, the prefill's first one
    # included: pick the first token that has not appeared before it
    k = next(i for i in range(1, GEN) if solo[i] not in solo[:i])
    eos = solo[k]
    bat = Batcher(ServeSession(ServePlan(arch=cfg, max_batch=1,
                                         max_len=MAX_LEN,
                                         wire="quantize_int8:physical"),
                               params), eos_id=eos)
    bat.join(prompt[0], GEN)
    done = bat.run()
    assert done[0].tokens[-1] == eos and len(done[0].tokens) == k + 1
    assert bat.free_slots() == [0]             # slot immediately reusable
    bat.join(prompt[1], 2)
    assert len(bat.run()) == 1


def test_fused_entry_same_tokens():
    """Entry-fused server (packed payload straight into the q8 kernel,
    rmsnorm folded into the row scales) decodes the same tokens."""
    cfg, model, params, prompt = _setup("phi4_mini_3_8b")
    base = ServeSession(ServePlan(arch=cfg, max_batch=B, max_len=MAX_LEN,
                                  wire="quantize_int8:physical"), params)
    fused = ServeSession(ServePlan(arch=cfg, max_batch=B, max_len=MAX_LEN,
                                   wire="quantize_int8:physical",
                                   fused_entry=True), params)
    assert fused._fused is not None
    a = base.generate(prompt, GEN)
    b = fused.generate(prompt, GEN)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fused_entry_requires_physical_wire():
    cfg, _, params, _ = _setup("phi4_mini_3_8b")
    with pytest.raises(ValueError, match="fused_entry"):
        ServeSession(ServePlan(arch=cfg, max_batch=B, max_len=MAX_LEN,
                               fused_entry=True), params)


def test_stack_packed_bitwise():
    """Batch-concat of packed payloads == packing the concat (per-row
    quantization never mixes rows)."""
    from repro.core.wire_compress import pack_int8
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 1, 16))
    parts = [pack_int8(x[i:i + 1]) for i in range(3)]
    stacked = stack_packed(parts, axis=0)
    whole = pack_int8(x)
    assert isinstance(stacked, PackedInt8)
    assert np.array_equal(np.asarray(stacked.q), np.asarray(whole.q))
    assert np.array_equal(np.asarray(stacked.scale), np.asarray(whole.scale))
    assert payload_nbytes(stacked) == sum(payload_nbytes(p) for p in parts)


def test_encdec_refuses_split_serving():
    cfg = get_config("whisper_base").reduced(vocab=97)
    ok, why = supports_split_serving(cfg)
    assert not ok and "monolithic" in why
    with pytest.raises(ValueError, match="monolithic"):
        ServeSession(ServePlan(arch=cfg, max_batch=1, max_len=MAX_LEN),
                     build_model(cfg).init(jax.random.PRNGKey(0)))


def test_vlm_split_serving():
    """VLM: patches enter at prefill (client side); decode is text-only.
    Split fp32 serving matches the monolithic stream."""
    cfg = get_config("internvl2_2b").reduced(vocab=97)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    patches = 0.02 * jax.random.normal(
        jax.random.PRNGKey(2), (B, cfg.n_patches, cfg.vision_dim), cfg.dtype)
    extra = {"patch_embeds": patches}
    # monolithic reference (vision rows occupy the front of the cache)
    cache = model.init_cache(B, MAX_LEN + cfg.n_patches)
    logits, cache = model.prefill(params, {"tokens": prompt, **extra}, cache)
    tok0 = jnp.argmax(logits[:, -1], -1)[:, None]
    rest, _ = greedy_decode_scan(model, params, cache, tok0, GEN - 1)
    mono = jnp.concatenate([tok0, rest], 1)
    sess = ServeSession(
        ServePlan(arch=cfg, max_batch=B, max_len=MAX_LEN + cfg.n_patches),
        params)
    split = sess.generate(prompt, GEN, extra=extra)
    assert np.array_equal(np.asarray(mono), np.asarray(split))
