"""VGG-16 for CIFAR-10 (Simonyan & Zisserman 2014; the split-learning
paper's Table 1 model): the benchmark's weight generator, the program
model it hands to `repro.api.Plan`, and the plain reference.

The weight generator fixes the parameter layout both sides read: one
dict per entry of the layer plan (`{"conv": {"w", "b"}}`, `{}` for a
pool, then `{"fc1": ...}`, `{"fc2": ...}`), He/LeCun normal weights,
zero biases.  Data: class-conditional Gaussian images, a fixed random
template per class plus noise.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.lib import counts


def plan(cfg) -> list:
    return list(cfg["plan"]) + ["FC1", "FC2"]


def init_params(key, cfg, dtype=jnp.float32) -> list:
    """All layers' parameters from one key (call under jit)."""
    items = plan(cfg)
    keys = jax.random.split(key, len(items))
    layers, ch = [], cfg["in_ch"]
    normal = lambda k, shape, fan_in: (jax.random.normal(k, shape)
                                       / math.sqrt(fan_in)).astype(dtype)
    for k, item in zip(keys, items):
        if item == "M":
            layers.append({})
        elif item == "FC1":
            layers.append({"fc1": {
                "w": normal(k, (ch, cfg["fc_width"]), ch),
                "b": jnp.zeros((cfg["fc_width"],), dtype)}})
        elif item == "FC2":
            layers.append({"fc2": {
                "w": normal(k, (cfg["fc_width"], cfg["n_classes"]),
                            cfg["fc_width"]),
                "b": jnp.zeros((cfg["n_classes"],), dtype)}})
        else:
            layers.append({"conv": {
                "w": normal(k, (3, 3, ch, item), 9 * ch),
                "b": jnp.zeros((item,), dtype)}})
            ch = item
    return layers


def split(params, cfg):
    return params[:cfg["cut"]], params[cfg["cut"]:]


def make_inputs(key, cfg, traffic, shape_lead: tuple,
                dtype=jnp.float32) -> dict:
    """{"x": (*lead, hw, hw, in_ch), "labels": (*lead,)}: a per-class
    template plus 0.6 x unit noise, classes uniform."""
    kt, kl, kn = jax.random.split(key, 3)
    hw, ch, nc = cfg["hw"], cfg["in_ch"], cfg["n_classes"]
    templates = jax.random.normal(kt, (nc, hw, hw, ch))
    labels = jax.random.randint(kl, shape_lead, 0, nc)
    x = templates[labels] + 0.6 * jax.random.normal(
        kn, shape_lead + (hw, hw, ch))
    return {"x": x.astype(dtype), "labels": labels}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def program_model(cfg, init):
    """The program's SegModel over its own VGG layers; `init` is the
    benchmark's weight generator."""
    from repro.core import split as sp
    from repro.nn import convnets as C
    cnn = C.CNNConfig(name=cfg["name"], in_ch=cfg["in_ch"],
                      n_classes=cfg["n_classes"], plan=tuple(cfg["plan"]))
    items = C.vgg_plan(cnn)
    return sp.list_segmodel(
        n_segments=len(items), init=init,
        layer_apply=lambda p, i, x: C.vgg_layer_apply(p, items[i], x))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _layer(p, item, x, precision):
    if item == "M":
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")
    if item == "FC1":
        x = jnp.mean(x, axis=(1, 2))
        return jax.nn.relu(jnp.dot(x, p["fc1"]["w"], precision=precision)
                           + p["fc1"]["b"])
    if item == "FC2":
        return jnp.dot(x, p["fc2"]["w"], precision=precision) + p["fc2"]["b"]
    y = lax.conv_general_dilated(
        x, p["conv"]["w"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
    return jax.nn.relu(y + p["conv"]["b"])


def reference(cfg, control: bool = False):
    """(client_fwd, server_loss, parameter dtype) in plain jax.numpy: f32
    at `highest` precision, or for the control bf16 at default
    precision (the configuration states f32)."""
    items, cut = plan(cfg), cfg["cut"]
    precision = (lax.Precision.DEFAULT if control
                 else lax.Precision.HIGHEST)

    def client_fwd(pc, batch):
        x = batch["x"].astype(pc[0]["conv"]["w"].dtype)
        for p, item in zip(pc, items[:cut]):
            x = _layer(p, item, x, precision)
        return x

    def server_loss(ps, act, batch):
        x = act
        for p, item in zip(ps, items[cut:]):
            x = _layer(p, item, x, precision)
        lp = jax.nn.log_softmax(x.astype(jnp.float32), -1)
        return -jnp.take_along_axis(lp, batch["labels"][..., None],
                                    -1).mean()

    return client_fwd, server_loss, (jnp.bfloat16 if control
                                      else jnp.float32)


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def fwd_flops_per_sample(cfg, traffic=None) -> int:
    return counts.vgg_fwd_flops(cfg["plan"], hw=cfg["hw"],
                                in_ch=cfg["in_ch"], fc_width=cfg["fc_width"],
                                n_classes=cfg["n_classes"])


def cut_shape(cfg, traffic) -> tuple:
    """Shape of one client's cut activation."""
    batch = traffic["per_client"]
    hw, ch = cfg["hw"], cfg["in_ch"]
    for item in cfg["plan"][:cfg["cut"]]:
        if item == "M":
            hw //= 2
        else:
            ch = item
    return (batch, hw, hw, ch)
