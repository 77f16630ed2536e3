"""The correctness check, driven through `bench/run.py` at a size a CPU
test holds: a sound program passes, and the control and each fault
that a cell can have make `correct` come out false.  The look for a
chip is skipped (`require_chip=False`); everything else is a run."""
import importlib.util
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import faults, harness

BENCH = harness.BENCH_DIR


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run_main",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RUN = _run_module()


def _load(path):
    return json.loads((BENCH / path).read_text())


def vgg_cell():
    cfg = _load("configs/vgg16-cifar10.json")
    cfg.update(plan=[16, 16, "M", 32, "M"], fc_width=32, hw=8, n_classes=4)
    traffic = dict(_load("traffic/rr100.json"), n_clients=3, per_client=4)
    return ({"name": "vgg16.tiny", "chips": 1}, cfg, traffic,
            _load("limits/vgg16.rr100.json"), [])


def lm_cell():
    cfg = _load("configs/phi4-mini-3.8b-4L.json")
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=128, vocab=256, cut=1, dtype="float32")
    traffic = dict(_load("traffic/rr2_lm.json"), per_client=2, seq_len=16)
    return ({"name": "lm.tiny", "chips": 1}, cfg, traffic,
            _load("limits/phi4mini-4L.train_rr2.json"), [])


TRAINING = {"vgg16": vgg_cell, "phi4mini-4L": lm_cell}


def serve_cell():
    cfg = _load("configs/phi4-mini-3.8b.json")
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               head_dim=16, d_ff=128, vocab=256, cut=1, max_batch=2,
               max_len=64)
    traffic = dict(_load("traffic/poisson.json"), rate_per_s=8.0,
                   warm_s=0.5,
                   prompt={"median": 10, "sigma": 0.9, "min": 4, "max": 16,
                           "buckets": [8, 16]},
                   answer={"median": 6, "sigma": 0.5, "min": 4, "max": 8})
    limits = dict(_load("limits/phi4mini.serve_poisson.json"), min_tokens=8)
    return ({"name": "serve.tiny", "chips": 1}, cfg, traffic, limits, [])


def run(cell, seed=11, hooks=None, seconds=1.5):
    return RUN.main(["--workload", cell[0]["name"], "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", "0"],
                    cell=cell, hooks=hooks, require_chip=False)


def test_refuses_without_a_tpu():
    with pytest.raises(SystemExit) as e:
        RUN.main(["--workload", "vgg16.rr100", "--seed", "1", "--seconds",
                  "1"])
    assert e.value.code not in (0, None)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", TRAINING)
def test_training_sound_program_is_correct(cell):
    result, checks = run(TRAINING[cell](), seed=2**31 + 7)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("cell", TRAINING)
@pytest.mark.parametrize("fault", sorted(faults.TRAINING))
def test_training_fault_is_not_correct(cell, fault):
    result, checks = run(TRAINING[cell](),
                         hooks={"session": faults.TRAINING[fault]})
    assert not result["correct"], checks


@pytest.mark.parametrize("cell", TRAINING)
def test_training_control_is_not_correct(cell):
    """The reference in the precision below the configured one, put in
    the program's place."""
    _, cfg, traffic, limits, _ = TRAINING[cell]()
    drv = harness.load_module(BENCH / "drivers" / "split_train.py",
                              "split_train")
    model = harness.load_module(BENCH / "models" / f"{cfg['model']}.py",
                                cfg["model"])
    key_w, key_d = jax.random.split(harness.seed_key(5))
    pool = drv.make_pool(model, cfg, traffic, key_d)
    n = traffic["n_clients"]
    want = drv.reference_capture(model, cfg, key_w, pool, n)
    ctl = drv.reference_capture(model, cfg, key_w, pool, n, control=True)
    checks = harness.checks_of(drv.readings(ctl, want), limits)
    assert not all(c["ok"] for c in checks.values()), checks


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def test_serving_sound_program_is_correct():
    result, checks = run(serve_cell(), seed=2**33 + 3)
    assert result["correct"], checks


def _altered_token(bat):
    """Each step, one live tenant's sampled token is replaced by the
    next id, as if produced wrong; it decodes on from it."""
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


def test_serving_altered_token_is_not_correct():
    result, checks = run(serve_cell(), hooks={"batcher": _altered_token})
    assert not result["correct"], checks


def test_serving_control_is_not_correct():
    """The reference at float8 chooses the tokens.  At published widths
    and 8 layers (vocabulary 4,096): logits of the toy size are too small
    for any gap to reach the limit."""
    _, cfg, traffic, limits, _ = serve_cell()
    cfg = dict(_load("configs/phi4-mini-3.8b.json"), n_layers=8, cut=4,
               vocab=4096, max_len=64)
    drv = harness.load_module(BENCH / "drivers" / "split_serve.py",
                              "split_serve")
    model = harness.load_module(BENCH / "models" / "phi4_mini.py",
                                "phi4_mini")
    seed = 13
    key_w = jax.random.split(harness.seed_key(seed))[0]
    reqs = drv.make_requests(traffic, 4.0)[:limits["sample_requests"]]
    for r in reqs:                 # any tokens: the control picks its own
        r.tokens = [1] * r.answer_len
    gaps = drv.reference_gaps(model, cfg, traffic, key_w, reqs, seed,
                              limits["sample_requests"], control=True)
    assert gaps.max() > limits["served_logit_gap"], gaps.max()


# ---------------------------------------------------------------------------
# the reference's weights and forward pass against the program's
# ---------------------------------------------------------------------------

def _tiny_phi4():
    cfg = _load("configs/phi4-mini-3.8b.json")
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=128, vocab=256, cut=1, dtype="float32")
    return cfg


def test_phi4_weights_are_the_programs_own_init():
    from repro.models import build_model
    model = harness.load_module(BENCH / "models" / "phi4_mini.py",
                                "phi4_mini")
    cfg = _tiny_phi4()
    key = jax.random.PRNGKey(3)
    prog = build_model(model.program_arch(cfg)).init(key)
    ours = model.init_params(key, cfg)
    assert jax.tree_util.tree_structure(prog) == \
        jax.tree_util.tree_structure(ours)
    for a, b in zip(jax.tree_util.tree_leaves(prog),
                    jax.tree_util.tree_leaves(ours)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_phi4_reference_logits_match_the_program():
    from repro.models import build_model
    model = harness.load_module(BENCH / "models" / "phi4_mini.py",
                                "phi4_mini")
    cfg = dict(_tiny_phi4(), cut=99)         # no wire inside the model
    key = jax.random.PRNGKey(4)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 12), 0, 256)
    want = build_model(model.program_arch(cfg)).forward(
        model.init_params(key, cfg), {"tokens": tokens})[0]
    got = model.served_logits(key, cfg, tokens, jnp.arange(12)[None],
                              jax.lax.Precision.HIGHEST)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
