"""Split-inference serving engine: prefill + decode across the cut.

The paper trains a model split at a layer boundary — client holds
embed + layers [0, cut), server holds the rest — so SERVING the trained
model has the same shape: the client never ships raw tokens upstream,
only the cut activation; the server never ships hidden state down, only
logits.  Both hops run through the training stack's `WireTransform`
middleware, so `wire="quantize_int8:physical"` makes the client->server
hop the PACKED int8 payload (int8 q + fp32 row scales) consumed by
`splitcat_linear_packed`, and the logits return leg rides the same
quantized wire.  `dequant(pack(x))` is bitwise `_fake_quant_int8(x)`,
so the physical wire generates token-for-token what the fake-quant wire
does — the compression is free at the protocol level.

Decode is a `lax.scan` over fused client->wire->server->wire->argmax
steps (ONE dispatch for the whole generation, not one per token);
prefill is ONE compiled teacher-forced forward per half that populates
both sides' caches (`LM.prefill_client` / `LM.prefill_server`).

Per-hop byte costs are metered with the training engine's `TurnCost`:
`decode_cost()` probes the step under `jax.eval_shape` (zero FLOPs) and
prices every `WireRecord` from the ACTUAL payload leaf dtypes.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.api.wire import WireStack, WireTape, parse_wire
from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core.accounting import TurnCost
from repro.core.split import record
from repro.core.wire_compress import (PackedInt8, as_dense,
                                      splitcat_linear_packed)
from repro.models import build_model
from repro.models.registry import supports_split_serving


def greedy_decode_scan(model, params, cache, first_token, steps: int):
    """Monolithic scan-based greedy decode: ONE compiled dispatch for
    `steps` tokens (the per-token Python loop in `launch.serve` exists
    only as the benchmark baseline).  Returns ((B, steps) tokens sampled
    AFTER first_token's logits, cache)."""
    def body(carry, _):
        tok, c = carry
        logits, c = model.decode_step(params, tok, c)
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        return (nxt, c), nxt

    (_, cache), toks = jax.lax.scan(body, (first_token, cache), None,
                                    length=steps)
    return jnp.swapaxes(toks[..., 0], 0, 1), cache


@dataclasses.dataclass(frozen=True)
class ServePlan:
    """Declarative split-serving config -> compiled `ServeSession`.

    arch        — arch id (see configs) or a built `ArchConfig`;
    cut         — flat layer index of the client/server boundary
                  (None = the arch's default training cut);
    wire        — wire middleware spec: the `parse_wire` grammar
                  ("quantize_int8:physical"), a transform sequence, or a
                  `WireStack`.  "" serves an fp32 wire;
    max_batch   — stacked batch rows (the `Batcher`'s slot count);
    max_len     — ring-cache length (prompt + generation budget);
    fused_entry — consume the packed up-wire payload directly in the
                  server's entry attention layer via the fused
                  dequant+matmul kernel (`splitcat_linear_packed`); the
                  rmsnorm folds into the per-row scales, so the fp32 cut
                  activation never materializes for the entry matmuls.
                  Numerically allclose (not bitwise) to the unfused
                  order of operations, hence opt-in;
    reduced     — shrink a string `arch` via `cfg.reduced()` (CPU runs).
    """
    arch: Any
    cut: int | None = None
    wire: Any = ""
    max_batch: int = 1
    max_len: int = 256
    fused_entry: bool = False
    reduced: bool = False

    def config(self) -> ArchConfig:
        if isinstance(self.arch, ArchConfig):
            return self.arch
        cfg = get_config(self.arch)
        return cfg.reduced(vocab=256) if self.reduced else cfg

    def session(self, key_or_params) -> "ServeSession":
        """Build the compiled session — pass a PRNGKey to init fresh
        params or a trained full-model param tree to split and serve."""
        return ServeSession(self, key_or_params)


class ServeSession:
    """One compiled split-serving run: holds the split params, the wire
    stack, the jitted prefill / fused-step / scan-decode closures, and
    (after `prefill`) both sides' live caches."""

    def __init__(self, plan: ServePlan, key_or_params):
        self.plan = plan
        self.cfg = plan.config()
        ok, why = supports_split_serving(self.cfg)
        if not ok:
            raise ValueError(f"{self.cfg.name}: {why}")
        self.model = build_model(self.cfg)
        n_layers = self.model.flat_layers()
        self.cut = plan.cut if plan.cut is not None else min(
            self.cfg.default_cut, max(1, n_layers // 2))
        if not 0 < self.cut < n_layers:
            raise ValueError(f"cut {self.cut} outside (0, {n_layers})")
        self.stack = WireStack(parse_wire(plan.wire))
        if isinstance(key_or_params, dict):
            self.client_params, self.server_params = self.model.split_params(
                key_or_params, self.cut)
        else:
            self.client_params, self.server_params = self._init_split(
                key_or_params)
        self._fused = (self._fused_entry_weights()
                       if plan.fused_entry else None)
        if plan.fused_entry and self._fused is None:
            raise ValueError(
                "fused_entry needs a physical int8 wire and a plain "
                "rmsnorm+attention block at the server entry")
        self._cc = self._sc = None
        self._build_jits()

    def _init_split(self, key):
        """Fresh params, built and split in ONE compiled program: the
        full tree never exists beside its two halves, so the init peak
        is the halves plus one leaf's temporaries (a 32-layer model's
        eager init-then-slice peaks near twice its weights).  A tied
        head leaves the program once, as the client's embedding, and
        the server shares that buffer exactly as `split_params` does."""
        model, cut = self.model, self.cut

        def init_split(k):
            client, server = model.split_params(model.init(k), cut)
            server.pop("tied_head", None)
            return client, server

        client, server = jax.jit(init_split)(key)
        if "head" not in server:
            server["tied_head"] = client["embed"]
        return client, server

    # ---- the fused packed-wire server entry --------------------------------

    def _fused_entry_weights(self):
        """Precompute the folded entry weights, or None if the server's
        first block isn't a plain scanned rmsnorm+GQA layer (or the wire
        isn't physically packed).

        Algebra: the payload encodes x = q * s (per-row scale).  The
        entry computes rmsnorm(x) @ W_qkv; with rmsnorm gain g and
        eps = 1e-6 (layers.rmsnorm_apply):

            rmsnorm(q*s) = q * s_eff * g,
            s_eff = s * rsqrt(s^2 * mean(q^2) + eps)

        so QKV = (q @ (g[:, None] * [Wq|Wk|Wv])) * s_eff + b — exactly
        the q8 kernel's contract (scale folds into the accumulator,
        bias added after).  The int8 q feeds the MXU directly."""
        if not self.stack.physical:
            return None
        groups = self.model._groups_for_range(self.cut, "server")
        g0 = groups[0]
        if g0.layers_per_repeat != 1:
            return None
        spec = g0.specs[0]
        if spec.mixer != "attn" or spec.norm != "rmsnorm":
            return None
        stacked = self.server_params["groups"][0]["0"]
        p0 = jax.tree_util.tree_map(lambda a: a[0], stacked)
        m = p0["mixer"]
        w_cat = jnp.concatenate([m["wq"]["w"], m["wk"]["w"], m["wv"]["w"]],
                                axis=1)
        w_cat = p0["norm1"]["scale"][:, None] * w_cat
        b_cat = (jnp.concatenate([m["wq"]["b"], m["wk"]["b"], m["wv"]["b"]])
                 if "b" in m["wq"] else None)
        widths = (m["wq"]["w"].shape[1], m["wk"]["w"].shape[1],
                  m["wv"]["w"].shape[1])
        return {"spec": spec, "group": g0, "w_cat": w_cat, "b_cat": b_cat,
                "widths": widths, "p0": p0}

    def _fused_server_decode(self, sp, payload: PackedInt8, caches):
        """Server decode step consuming the PACKED payload: entry QKV
        through the fused dequant+matmul kernel, then the regular path
        for the rest of the trunk."""
        from repro.nn import attention as A
        from repro.nn import transformer as T
        from repro.models.lm import group_decode
        fe = self._fused
        spec, g0 = fe["spec"], fe["group"]
        qf = payload.q.astype(jnp.float32)
        ms = jnp.mean(qf * qf, axis=-1, keepdims=True)
        s_eff = (payload.scale * jax.lax.rsqrt(
            payload.scale.astype(jnp.float32) ** 2 * ms + 1e-6)
        ).astype(jnp.float32)
        qkv_flat = splitcat_linear_packed(
            [PackedInt8(payload.q, s_eff, payload.orig_dtype)],
            fe["w_cat"], fe["b_cat"], out_dtype=payload.orig_dtype)
        wq, wk, _ = fe["widths"]
        qkv = (qkv_flat[..., :wq], qkv_flat[..., wq:wq + wk],
               qkv_flat[..., wq + wk:])

        x = as_dense(payload)                       # residual stream only
        c_stacked = caches[0]["0"]
        c0 = jax.tree_util.tree_map(lambda a: a[0], c_stacked)
        y, nc0 = A.gqa_decode(fe["p0"]["mixer"], spec.attn, x, c0, qkv=qkv)
        h = x + y
        if spec.mlp != "none":
            h = h + T._mlp_apply(fe["p0"]["mlp"], spec,
                                 T._norm_apply(fe["p0"]["norm2"], spec, h))

        # rest of the entry group's repeats, then the remaining groups
        new_caches = []
        if g0.n_repeat > 1:
            rest_p = {"0": jax.tree_util.tree_map(
                lambda a: a[1:], sp["groups"][0]["0"])}
            rest_c = {"0": jax.tree_util.tree_map(
                lambda a: a[1:], c_stacked)}
            g_rest = dataclasses.replace(g0, n_repeat=g0.n_repeat - 1)
            h, nc_rest = group_decode(rest_p, g_rest, h, rest_c)
            merged = jax.tree_util.tree_map(
                lambda one, rest: jnp.concatenate([one[None], rest], axis=0),
                nc0, nc_rest["0"])
        else:
            merged = jax.tree_util.tree_map(lambda a: a[None], nc0)
        new_caches.append({"0": merged})

        groups = self.model._groups_for_range(self.cut, "server")
        for g, gp, c in zip(groups[1:], sp["groups"][1:], caches[1:]):
            h, nc = group_decode(gp, g, h, c)
            new_caches.append(nc)
        return self.model.server_head(sp, h), new_caches

    # ---- core step / prefill (pure; wire tape threaded through) ------------

    def _prefill_fn(self, cp, sp, batch, wires):
        B = batch["tokens"].shape[0]
        cc, sc = self.model.init_cache_split(B, self.plan.max_len, self.cut)
        act, cc = self.model.prefill_client(cp, batch, self.cut, cc)
        act = record(wires, "prefill_act", act, "up")
        logits, sc = self.model.prefill_server(sp, as_dense(act), self.cut,
                                               sc)
        last = record(wires, "prefill_logits", logits[:, -1:], "down")
        tok0 = jnp.argmax(as_dense(last)[:, -1], axis=-1)[:, None]
        return tok0, cc, sc

    def _step_fn(self, cp, sp, tok, cc, sc, wires):
        """One fused decode step: client half -> up wire -> server half
        -> down wire -> client-side argmax."""
        act, cc = self.model.decode_step_client(cp, tok, self.cut, cc)
        act = record(wires, "cut_act", act, "up")
        if self._fused is not None and isinstance(act, PackedInt8):
            logits, sc = self._fused_server_decode(sp, act, sc)
        else:
            logits, sc = self.model.decode_step_server(sp, as_dense(act),
                                                       self.cut, sc)
        logits = record(wires, "logits", logits, "down")
        nxt = jnp.argmax(as_dense(logits)[:, -1], axis=-1)[:, None]
        return nxt, cc, sc

    def _build_jits(self):
        stack = self.stack

        def prefill(cp, sp, batch):
            return self._prefill_fn(cp, sp, batch, WireTape(stack))

        def step(cp, sp, tok, cc, sc):
            return self._step_fn(cp, sp, tok, cc, sc, WireTape(stack))

        def decode(cp, sp, tok0, cc, sc, steps):
            def body(carry, _):
                tok, c_c, c_s = carry
                nxt, c_c, c_s = self._step_fn(cp, sp, tok, c_c, c_s,
                                              WireTape(stack))
                return (nxt, c_c, c_s), nxt

            (_, cc, sc), toks = jax.lax.scan(body, (tok0, cc, sc), None,
                                             length=steps)
            return jnp.swapaxes(toks[..., 0], 0, 1), cc, sc

        self._jit_prefill = jax.jit(prefill)
        self._jit_step = jax.jit(step)
        self._jit_decode = jax.jit(decode, static_argnames="steps")

    # ---- stateful serving API ----------------------------------------------

    def prefill(self, prompts, extra: dict | None = None):
        """One compiled teacher-forced forward per half.  prompts:
        (B, prompt_len) int tokens; extra carries modality inputs
        (e.g. {"patch_embeds": ...} for a VLM).  Returns the first
        sampled token (B, 1) and arms the session's caches."""
        batch = {"tokens": prompts}
        if extra:
            batch.update(extra)
        tok0, self._cc, self._sc = self._jit_prefill(
            self.client_params, self.server_params, batch)
        return tok0

    def decode_step(self, tok):
        """One token for every row: the client->server hop is the wire
        payload (packed int8 when the stack is physical)."""
        nxt, self._cc, self._sc = self._jit_step(
            self.client_params, self.server_params, tok, self._cc, self._sc)
        return nxt

    def decode(self, tok0, steps: int):
        """`steps` tokens in ONE compiled `lax.scan` dispatch."""
        toks, self._cc, self._sc = self._jit_decode(
            self.client_params, self.server_params, tok0, self._cc,
            self._sc, steps)
        return toks

    def generate(self, prompts, max_new: int, extra: dict | None = None):
        """prefill + scan decode -> (B, max_new) generated tokens."""
        tok0 = self.prefill(prompts, extra)
        if max_new <= 1:
            return tok0[:, :max_new]
        rest = self.decode(tok0, max_new - 1)
        return jnp.concatenate([tok0, rest], axis=1)

    # ---- metering ----------------------------------------------------------

    def decode_cost(self, batch: int | None = None) -> TurnCost:
        """Static wire cost of ONE decode step, probed under
        `jax.eval_shape` (no FLOP spent).  `bytes_up + bytes_down` is
        the per-generated-token wire traffic; with a physical stack the
        bytes are derived from the packed payload's actual leaf dtypes."""
        B = batch or self.plan.max_batch
        cc, sc = self.model.init_cache_split(B, self.plan.max_len, self.cut)
        tok = jnp.zeros((B, 1), jnp.int32)
        wires = WireTape(self.stack)
        jax.eval_shape(
            lambda cp, sp: self._step_fn(cp, sp, tok, cc, sc, wires)[0],
            self.client_params, self.server_params)
        return TurnCost(wires=tuple(wires), flops=0.0, sync_bytes=0)

    def prefill_cost(self, batch: int, prompt_len: int,
                     extra: dict | None = None) -> TurnCost:
        b = {"tokens": jnp.zeros((batch, prompt_len), jnp.int32)}
        if extra:
            b.update(extra)
        wires = WireTape(self.stack)
        jax.eval_shape(
            lambda cp, sp: self._prefill_fn(cp, sp, b, wires)[0],
            self.client_params, self.server_params)
        return TurnCost(wires=tuple(wires), flops=0.0, sync_bytes=0)

    def bytes_per_token(self) -> int:
        c = self.decode_cost(batch=1)
        return c.bytes_up + c.bytes_down
