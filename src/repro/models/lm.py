"""Decoder-only language models (dense / MoE / SSM / hybrid / VLM backbones)
assembled from ArchConfig.

Model params:
    {"embed": ..., "groups": [g0, g1, ...], "final_norm": ...,
     ["head"]: ..., ["vision_proj"]: ...}

Each group is a *scan unit*: a homogeneous stack of one BlockSpec, or a
composite super-block (tuple of BlockSpecs — hybrid layer patterns)
repeated n times.  Scanning keeps HLO size depth-independent, which is
what makes the 88-layer × 512-device dry-runs compile quickly.

Split learning hooks: `split_params(params, cut)` slices the stacked
group arrays at a flat layer index — the client owns embed + layers
[0, cut), the server owns the rest; `apply_client` / `apply_server` run
the two sides with only the cut activation in between.
"""
from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.nn import attention as A
from repro.nn import layers as L
from repro.nn import module as nn
from repro.nn import moe as M
from repro.nn import rglru as R
from repro.nn import ssm as S
from repro.nn import transformer as T


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    specs: tuple                  # tuple[BlockSpec]; len>1 = composite
    n_repeat: int

    @property
    def layers_per_repeat(self) -> int:
        return len(self.specs)

    @property
    def n_layers(self) -> int:
        return self.n_repeat * len(self.specs)


def _attn_cfg(cfg: ArchConfig, *, window=None) -> A.AttnConfig:
    if cfg.attn_kind == "mla":
        return A.AttnConfig(
            d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
            kind="mla", q_lora_rank=cfg.q_lora_rank,
            kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
            yarn=cfg.yarn, window=window, dtype=cfg.dtype)
    if cfg.yarn is not None:
        raise ValueError(f"{cfg.name}: YaRN is implemented for MLA only")
    return A.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias,
        rope_fraction=cfg.rope_fraction, rope_theta=cfg.rope_theta,
        window=window, dtype=cfg.dtype)


def _block_spec(cfg: ArchConfig, kind: str, *, window=None,
                moe_layer=False) -> T.BlockSpec:
    common = dict(d_model=cfg.d_model, norm=cfg.norm, dtype=cfg.dtype)
    if kind in ("attn", "mla"):
        attn = _attn_cfg(cfg, window=window)
        if moe_layer:
            moe = M.MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                              n_experts=cfg.n_experts, top_k=cfg.top_k,
                              n_shared=cfg.n_shared, held=cfg.experts_held,
                              expert_offset=cfg.expert_offset,
                              norm_topk_prob=cfg.norm_topk_prob,
                              routed_scaling_factor=cfg.routed_scaling_factor,
                              dtype=cfg.dtype)
            return T.BlockSpec(mixer=kind, mlp="moe", attn=attn, moe=moe,
                               **common)
        d_ff = cfg.dense_d_ff or cfg.d_ff
        return T.BlockSpec(mixer=kind, mlp=cfg.mlp if cfg.mlp != "none"
                           else "swiglu", d_ff=d_ff, attn=attn, **common)
    if kind == "mamba2":
        ssm = S.SSMConfig(d_model=cfg.d_model,
                          d_inner=cfg.ssm_expand * cfg.d_model,
                          head_dim=cfg.ssm_head_dim, d_state=cfg.ssm_state,
                          n_groups=cfg.ssm_groups, chunk=cfg.ssm_chunk,
                          dtype=cfg.dtype)
        return T.BlockSpec(mixer="mamba2", mlp="none", ssm=ssm, **common)
    if kind == "rglru":
        rg = R.RGLRUConfig(d_model=cfg.d_model,
                           lru_width=cfg.lru_width or cfg.d_model,
                           dtype=cfg.dtype)
        return T.BlockSpec(mixer="rglru", mlp=cfg.mlp, d_ff=cfg.d_ff,
                           rglru=rg, **common)
    raise ValueError(kind)


def make_groups(cfg: ArchConfig, *, long_context: bool = False) -> list[GroupSpec]:
    window = cfg.window
    if long_context and cfg.long_window:
        window = cfg.long_window
    if cfg.family == "ssm":
        return [GroupSpec((_block_spec(cfg, "mamba2"),), cfg.n_layers)]
    if cfg.pattern:                                   # hybrid
        per = len(cfg.pattern)
        n_full, rem = divmod(cfg.n_layers, per)
        specs = tuple(
            _block_spec(cfg, k if k != "attn" else "attn",
                        window=window if k == "attn" else None)
            for k in cfg.pattern)
        groups = [GroupSpec(specs, n_full)]
        if rem:
            groups.append(GroupSpec(specs[:rem], 1))
        return groups
    kind = "mla" if cfg.attn_kind == "mla" else "attn"
    if cfg.n_experts:
        groups = []
        if cfg.first_dense:
            groups.append(GroupSpec(
                (_block_spec(cfg, kind, window=window),), cfg.first_dense))
        groups.append(GroupSpec(
            (_block_spec(cfg, kind, window=window, moe_layer=True),),
            cfg.n_layers - cfg.first_dense))
        return groups
    return [GroupSpec((_block_spec(cfg, kind, window=window),),
                      cfg.n_layers)]


# ---------------------------------------------------------------------------
# Groups: init / apply / cache / decode
# ---------------------------------------------------------------------------

def group_init(key, g: GroupSpec):
    if g.layers_per_repeat == 1:
        return {"0": T.stack_init(key, g.specs[0], g.n_repeat)}
    ks = nn.split_keys(key, g.layers_per_repeat)
    return {str(i): T.stack_init(ks[i], spec, g.n_repeat)
            for i, spec in enumerate(g.specs)}


def group_apply(params, g: GroupSpec, x, *, remat: bool = False):
    if g.layers_per_repeat == 1:
        return T.stack_apply(params["0"], g.specs[0], x, remat=remat)

    def body(h, layer_params):
        for i, spec in enumerate(g.specs):
            def one(p, hh, spec=spec):
                return T.block_apply(p, spec, hh)
            f = jax.checkpoint(one) if remat else one
            h = f(layer_params[str(i)], h)
        return h, None

    out, _ = jax.lax.scan(body, x, params)
    return out


def group_init_cache(g: GroupSpec, batch: int, max_len: int):
    return {str(i): T.stack_init_cache(spec, g.n_repeat, batch, max_len)
            for i, spec in enumerate(g.specs)}


def group_decode(params, g: GroupSpec, x, caches):
    def body(h, pc):
        layer_params, cache = pc
        new_cache = {}
        for i, spec in enumerate(g.specs):
            h, new_cache[str(i)] = T.block_decode(
                layer_params[str(i)], spec, h, cache[str(i)])
        return h, new_cache

    if g.layers_per_repeat == 1:
        def body1(h, pc):
            lp, c = pc
            h, nc = T.block_decode(lp, g.specs[0], h, c)
            return h, nc
        out, new = jax.lax.scan(body1, x, (params["0"], caches["0"]))
        return out, {"0": new}
    out, new = jax.lax.scan(body, x, (params, caches))
    return out, new


def group_prefill(params, g: GroupSpec, x, caches):
    """Full-sequence forward through one scan group that also populates
    its decode caches (the compiled-prefill analogue of group_decode)."""
    if g.layers_per_repeat == 1:
        def body1(h, pc):
            lp, c = pc
            h, nc = T.block_prefill(lp, g.specs[0], h, c)
            return h, nc
        out, new = jax.lax.scan(body1, x, (params["0"], caches["0"]))
        return out, {"0": new}

    def body(h, pc):
        layer_params, cache = pc
        new_cache = {}
        for i, spec in enumerate(g.specs):
            h, new_cache[str(i)] = T.block_prefill(
                layer_params[str(i)], spec, h, cache[str(i)])
        return h, new_cache

    out, new = jax.lax.scan(body, x, (params, caches))
    return out, new


def per_slot_pos(caches, batch: int):
    """Broadcast every scalar `pos` cursor leaf in a cache tree to a
    per-row vector with a trailing (batch,) axis — the layout the
    multi-tenant serving batcher uses so each stacked slot advances its
    own position independently (see gqa_decode).  Recurrent caches
    (mamba2/rglru) carry no cursor and pass through unchanged."""
    def walk(t):
        if isinstance(t, dict):
            return {k: (jnp.broadcast_to(v[..., None], v.shape + (batch,))
                        if k == "pos" else walk(v))
                    for k, v in t.items()}
        if isinstance(t, list):
            return [walk(v) for v in t]
        return t
    return walk(caches)


# ---------------------------------------------------------------------------
# The LM
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ArchConfig
    groups: tuple                 # tuple[GroupSpec]

    # ---- init ----
    def init(self, key):
        ks = nn.key_iter(key)
        p = {"embed": L.embedding_init(next(ks), self.cfg.vocab,
                                       self.cfg.d_model, dtype=self.cfg.dtype),
             "groups": [group_init(next(ks), g) for g in self.groups],
             "final_norm": (L.rmsnorm_init(next(ks), self.cfg.d_model,
                                           dtype=self.cfg.dtype)
                            if self.cfg.norm == "rmsnorm" else
                            L.layernorm_init(next(ks), self.cfg.d_model,
                                             dtype=self.cfg.dtype))}
        if not self.cfg.tie_embeddings:
            p["head"] = L.dense_init(next(ks), self.cfg.d_model,
                                     self.cfg.vocab, dtype=self.cfg.dtype)
        if self.cfg.family == "vlm":
            p["vision_proj"] = L.dense_init(next(ks), self.cfg.vision_dim,
                                            self.cfg.d_model, bias=True,
                                            dtype=self.cfg.dtype)
        return p

    # ---- embedding / head ----
    def embed(self, params, batch):
        x = L.embedding_apply(params["embed"], batch["tokens"])
        if self.cfg.family == "vlm":
            vis = L.dense_apply(params["vision_proj"],
                                batch["patch_embeds"].astype(self.cfg.dtype))
            x = jnp.concatenate([vis, x], axis=1)
        return x

    def head(self, params, x):
        x = (L.rmsnorm_apply(params["final_norm"], x)
             if self.cfg.norm == "rmsnorm"
             else L.layernorm_apply(params["final_norm"], x))
        if self.cfg.tie_embeddings:
            return L.embedding_attend(params["embed"], x)
        return L.dense_apply(params["head"], x)

    # ---- full forward ----
    def forward(self, params, batch, *, remat: bool = False):
        x = self.embed(params, batch)
        for g, gp in zip(self.groups, params["groups"]):
            x = group_apply(gp, g, x, remat=remat)
        logits = self.head(params, x)
        if self.cfg.family == "vlm":
            logits = logits[:, self.cfg.n_patches:]    # text positions only
        return logits

    def loss(self, params, batch, *, remat: bool = False):
        logits = self.forward(params, batch, remat=remat)
        labels = batch["labels"]
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(lp, labels[..., None], -1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is not None:
            return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return nll.mean()

    # ---- decode ----
    def init_cache(self, batch: int, max_len: int):
        return [group_init_cache(g, batch, max_len) for g in self.groups]

    def prefill(self, params, batch, caches):
        """ONE compiled teacher-forced forward that populates `caches`
        (replaces the O(prompt_len) decode_step dispatch loop).  Returns
        (logits (B, S, V), caches) — logits[:, -1] feeds the first
        sampled token."""
        x = self.embed(params, batch)
        new_caches = []
        for g, gp, c in zip(self.groups, params["groups"], caches):
            x, nc = group_prefill(gp, g, x, c)
            new_caches.append(nc)
        logits = self.head(params, x)
        if self.cfg.family == "vlm":
            logits = logits[:, self.cfg.n_patches:]
        return logits, new_caches

    def decode_step(self, params, tokens, caches):
        """tokens: (B, 1) -> logits (B, 1, V), new caches."""
        x = L.embedding_apply(params["embed"], tokens)
        new_caches = []
        for g, gp, c in zip(self.groups, params["groups"], caches):
            x, nc = group_decode(gp, g, x, c)
            new_caches.append(nc)
        return self.head(params, x), new_caches

    # ---- split learning ----
    def flat_layers(self) -> int:
        return sum(g.n_layers for g in self.groups)

    def split_params(self, params, cut: int):
        """Client: embed (+vision_proj) + layers [0, cut).
        Server: layers [cut, L) + final norm + head."""
        client = {"embed": params["embed"]}
        if "vision_proj" in params:
            client["vision_proj"] = params["vision_proj"]
        server = {"final_norm": params["final_norm"]}
        if "head" in params:
            server["head"] = params["head"]
        else:
            # tied head: the server needs the output table; in the real
            # protocol this is the U-shaped configuration instead.  For the
            # vanilla split we give the server a copy of the table — noted
            # as label-side, not raw-data, exposure.
            server["tied_head"] = params["embed"]
        cg, sg = [], []
        seen = 0
        for g, gp in zip(self.groups, params["groups"]):
            lo, hi = seen, seen + g.n_layers
            seen = hi
            if hi <= cut:
                cg.append(gp)
            elif lo >= cut:
                sg.append(gp)
            else:
                k = cut - lo
                assert k % g.layers_per_repeat == 0, \
                    f"cut {cut} splits a composite super-block"
                r = k // g.layers_per_repeat
                cg.append(jax.tree_util.tree_map(lambda a: a[:r], gp))
                sg.append(jax.tree_util.tree_map(lambda a: a[r:], gp))
        client["groups"] = cg
        server["groups"] = sg
        return client, server

    def _groups_for_range(self, cut: int, side: str) -> list[GroupSpec]:
        out, seen = [], 0
        for g in self.groups:
            lo, hi = seen, seen + g.n_layers
            seen = hi
            if side == "client":
                if hi <= cut:
                    out.append(g)
                elif lo < cut:
                    out.append(dataclasses.replace(
                        g, n_repeat=(cut - lo) // g.layers_per_repeat))
            else:
                if lo >= cut:
                    out.append(g)
                elif hi > cut:
                    out.append(dataclasses.replace(
                        g, n_repeat=(hi - cut) // g.layers_per_repeat))
        return out

    def apply_client(self, client_params, batch, cut: int, *,
                     remat: bool = False):
        x = self.embed(client_params, batch)
        for g, gp in zip(self._groups_for_range(cut, "client"),
                         client_params["groups"]):
            x = group_apply(gp, g, x, remat=remat)
        return x

    def server_head(self, server_params, x):
        """Final norm + unembedding on the server side of a split."""
        x = (L.rmsnorm_apply(server_params["final_norm"], x)
             if self.cfg.norm == "rmsnorm"
             else L.layernorm_apply(server_params["final_norm"], x))
        if "head" in server_params:
            return L.dense_apply(server_params["head"], x)
        return L.embedding_attend(server_params["tied_head"], x)

    def apply_server(self, server_params, act, cut: int, *,
                     remat: bool = False):
        x = act
        for g, gp in zip(self._groups_for_range(cut, "server"),
                         server_params["groups"]):
            x = group_apply(gp, g, x, remat=remat)
        return self.server_head(server_params, x)

    # ---- split serving (each half owns its own caches) ----
    def init_cache_split(self, batch: int, max_len: int, cut: int):
        """(client_caches, server_caches) for the layer ranges [0, cut)
        and [cut, L) — each side's decode runs against only its own
        caches, so no KV state ever crosses the wire."""
        client = [group_init_cache(g, batch, max_len)
                  for g in self._groups_for_range(cut, "client")]
        server = [group_init_cache(g, batch, max_len)
                  for g in self._groups_for_range(cut, "server")]
        return client, server

    def prefill_client(self, client_params, batch, cut: int, caches):
        """Compiled teacher-forced client half: embed + layers [0, cut).
        Returns (cut activation (B, S, D), caches)."""
        x = self.embed(client_params, batch)
        new_caches = []
        for g, gp, c in zip(self._groups_for_range(cut, "client"),
                            client_params["groups"], caches):
            x, nc = group_prefill(gp, g, x, c)
            new_caches.append(nc)
        return x, new_caches

    def prefill_server(self, server_params, act, cut: int, caches):
        """Compiled teacher-forced server half over the cut activation.
        Returns (logits (B, S, V), caches)."""
        x = act
        new_caches = []
        for g, gp, c in zip(self._groups_for_range(cut, "server"),
                            server_params["groups"], caches):
            x, nc = group_prefill(gp, g, x, c)
            new_caches.append(nc)
        logits = self.server_head(server_params, x)
        if self.cfg.family == "vlm":
            logits = logits[:, self.cfg.n_patches:]
        return logits, new_caches

    def decode_step_client(self, client_params, tokens, cut: int, caches):
        """tokens (B, 1) -> (cut activation (B, 1, D), caches).  Token
        embedding only — a VLM's patches entered at prefill time."""
        x = L.embedding_apply(client_params["embed"], tokens)
        new_caches = []
        for g, gp, c in zip(self._groups_for_range(cut, "client"),
                            client_params["groups"], caches):
            x, nc = group_decode(gp, g, x, c)
            new_caches.append(nc)
        return x, new_caches

    def decode_step_server(self, server_params, act, cut: int, caches):
        """act (B, 1, D) -> (logits (B, 1, V), caches)."""
        x = act
        new_caches = []
        for g, gp, c in zip(self._groups_for_range(cut, "server"),
                            server_params["groups"], caches):
            x, nc = group_decode(gp, g, x, c)
            new_caches.append(nc)
        return self.server_head(server_params, x), new_caches


def build_lm(cfg: ArchConfig, *, long_context: bool = False) -> LM:
    return LM(cfg=cfg, groups=tuple(make_groups(cfg,
                                                long_context=long_context)))
