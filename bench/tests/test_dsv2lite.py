"""The `dsv2lite.serve_long` cell at a size a CPU test holds, through
`bench/run.py`: the sound program is `correct`, an altered token and the
float8 control are not; the benchmark's weight generator is the
program's own init, and its reference's logits are the program's."""
import importlib.util
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import harness

BENCH = harness.BENCH_DIR
CELL = "dsv2lite.serve_long"


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run_dsv2",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = _run_module()
MODEL = harness.load_module(BENCH / "models" / "deepseek_v2_lite.py",
                            "deepseek_v2_lite")


def _load(path):
    return json.loads((BENCH / path).read_text())


def tiny_cfg(**over):
    """Every kind of layer at toy widths: one dense layer, two MoE layers
    holding 2 of 8 experts (ids 2 and 3), YaRN crossed at 16 positions."""
    cfg = _load("configs/deepseek-v2-lite-ep8.json")
    cfg.update(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=16, v_head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, n_routed_experts=8,
               num_experts_per_tok=3, n_shared_experts=1, n_experts=2,
               expert_offset=2, vocab_size=256, vocab=256, max_batch=2,
               max_len=64, dtype="float32",
               rope_scaling=dict(cfg["rope_scaling"],
                                 original_max_position_embeddings=16))
    cfg.update(over)
    return cfg


def serve_cell():
    traffic = dict(_load("traffic/poisson_long.json"), rate_per_s=8.0,
                   warm_s=0.5,
                   prompt={"median": 12, "sigma": 0.6, "min": 8, "max": 24,
                           "buckets": [16, 24]},
                   answer={"median": 8, "sigma": 0.5, "min": 4, "max": 12})
    limits = dict(_load(f"limits/{CELL}.json"), min_tokens=8)
    return ({"name": "dsv2lite.tiny", "chips": 1}, tiny_cfg(), traffic,
            limits, [])


def run(cell, seed, hooks=None):
    return RUN.main(["--workload", cell[0]["name"], "--seed", str(seed),
                     "--seconds", "1.5", "--trace", "0"],
                    cell=cell, hooks=hooks, require_chip=False)


def test_cell_is_in_the_benchmark():
    cell, cfg, traffic, limits, _ = RUN.find_cell(
        harness.load_json(harness.ROOT / "BENCHMARK.json"), CELL)
    assert cell["chips"] == 1 and cfg["model"] == "deepseek_v2_lite"
    assert cfg["n_experts"] < cfg["n_routed_experts"]
    assert traffic["prompt"]["max"] + traffic["answer"]["max"] \
        <= cfg["max_len"]


def test_sound_program_is_correct():
    result, checks = run(serve_cell(), seed=2**33 + 5)
    assert result["correct"], checks


def _altered_token(bat):
    """Each step, one live tenant's sampled token is replaced by the next
    id, as if produced wrong; it decodes on from it."""
    orig = bat.step

    def step():
        out = orig()
        for slot, tok in out.items():
            t = next((t for t in list(bat.tenants.values()) + bat.finished
                      if t.slot == slot and t.tokens[-1] == tok), None)
            if t is not None:
                t.tokens[-1] = (tok + 1) % bat.session.cfg.vocab
                t.cur = jnp.asarray([[t.tokens[-1]]], jnp.int32)
            break
        return out
    bat.step = step


def test_altered_token_is_not_correct():
    result, checks = run(serve_cell(), seed=17,
                         hooks={"batcher": _altered_token})
    assert not result["correct"], checks


def test_control_is_not_correct():
    """The reference at float8 chooses the tokens.  At published widths,
    3 layers (the dense one and two MoE layers of 8 held experts) and a
    vocabulary of 4,096: at toy widths the logits are too small for any
    gap to reach the limit."""
    _, _, traffic, limits, _ = serve_cell()
    cfg = dict(_load("configs/deepseek-v2-lite-ep8.json"),
               num_hidden_layers=3, vocab_size=4096, vocab=4096, max_len=64)
    drv = harness.load_module(BENCH / "drivers" / "split_serve.py",
                              "split_serve")
    seed = 13
    key_w = jax.random.split(harness.seed_key(seed))[0]
    reqs = drv.make_requests(traffic, 4.0)[:limits["sample_requests"]]
    for r in reqs:                 # any tokens: the control picks its own
        r.tokens = [1] * r.answer_len
    gaps = drv.reference_gaps(MODEL, cfg, traffic, key_w, reqs, seed,
                              limits["sample_requests"], control=True)
    assert gaps.max() > limits["served_logit_gap"], gaps.max()


def test_weights_are_the_programs_own_init():
    from repro.models import build_model
    cfg = tiny_cfg()
    key = jax.random.PRNGKey(3)
    prog = build_model(MODEL.program_arch(cfg)).init(key)
    ours = MODEL.init_params(key, cfg)
    assert jax.tree_util.tree_structure(prog) == \
        jax.tree_util.tree_structure(ours)
    for a, b in zip(jax.tree_util.tree_leaves(prog),
                    jax.tree_util.tree_leaves(ours)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("length", [20, 40])
def test_reference_logits_match_the_program(length):
    """Past the toy `original_max` of 16 and, at 40, across a query block
    of the reference's attention (its `Q_BLOCK` set to 16 here)."""
    from repro.models import build_model
    cfg = tiny_cfg(cut=99)                  # no wire inside the model
    key = jax.random.PRNGKey(4)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, length), 0, 256)
    want = build_model(MODEL.program_arch(cfg)).forward(
        MODEL.init_params(key, cfg), {"tokens": tokens})
    block, MODEL.Q_BLOCK = MODEL.Q_BLOCK, 16
    try:
        pos = jnp.broadcast_to(jnp.arange(length)[None], (2, length))
        got = MODEL.served_logits(key, cfg, tokens, pos,
                                  jax.lax.Precision.HIGHEST)
    finally:
        MODEL.Q_BLOCK = block
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
