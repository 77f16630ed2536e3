"""Multi-tenant continuous batching over ONE split-serving session.

Each tenant is an independent client (its own prompt, its own B=1 client
half and caches — raw tokens never leave the tenant).  The server holds
ONE stacked cache with `plan.max_batch` slots and a PER-ROW position
cursor (`models.lm.per_slot_pos`), so every slot advances independently:
a tenant joining mid-flight prefills into its slot while the others keep
decoding — no barrier, no re-padding of anyone else's state.

Per step the batcher:
  1. runs every active tenant's jitted B=1 client step (the wire stack
     applies per tenant — each quantizes ITS OWN activation);
  2. concatenates the payloads along the batch axis
     (`wire_compress.stack_packed` — bitwise the per-tenant payloads,
     because quantization is per last-axis row);
  3. runs ONE batched server step over the stacked payload, which also
     takes every slot's greedy token from the down-wire logits;
  4. reads the step's tokens on the host in one transfer and hands each
     tenant its own token, already on the device as its next input.

Vacant slots ride along as zero payloads: every op in the server trunk
is batch-row-independent, so garbage rows cannot perturb live rows (the
parity suite checks batched == solo slot-for-slot, token-exact).

Wire bytes are metered analytically per ACTIVE tenant from the
`eval_shape` TurnCost probes — vacant-slot padding is free on a real
wire and is not billed.

Host spans `repro.batcher.*` name the parts of `join` and `step` in any
profiler capture (they record nothing while no profiler session is
active); `host_reads` counts the device values read on the host: one
per join and one per step with a live tenant (`steps`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.wire_compress import PackedInt8, as_dense, stack_packed
from repro.models.lm import per_slot_pos
from repro.serve.split_infer import ServeSession


@dataclasses.dataclass
class Tenant:
    """One client stream multiplexed into the batch."""
    slot: int
    max_new: int
    tokens: list                  # generated tokens (ints), tok0 first
    cache: object                 # B=1 client-side caches
    cur: object                   # (1, 1) current token
    done: bool = False
    serial: int = 0               # order of admission (the spans' `tenant`)


class Batcher:
    """Continuous batching: `join` prefills a tenant into a free slot,
    `step` advances every live tenant one token, tenants leave on EOS or
    their `max_new` budget (slot immediately reusable)."""

    def __init__(self, session: ServeSession, eos_id: int | None = None):
        self.session = session
        self.eos_id = eos_id
        self.max_batch = session.plan.max_batch
        self.tenants: dict[int, Tenant] = {}
        self.finished: list[Tenant] = []
        self.bytes_up = 0
        self.bytes_down = 0
        self.tokens_generated = 0
        self.host_reads = 0                   # device values read on the host
        self.joined = 0                       # tenants admitted so far
        self.steps = 0                        # steps with a live tenant

        model, cut, plan = session.model, session.cut, session.plan
        _, sc = model.init_cache_split(self.max_batch, plan.max_len, cut)
        self._sc = per_slot_pos(sc, self.max_batch)
        self._pad_part = None                 # built lazily from shapes
        dc = session.decode_cost(batch=1)
        self._decode_up = dc.bytes_up
        self._decode_down = dc.bytes_down

        stack = session.stack

        def client_step(cp, tok, cc):
            act, cc = model.decode_step_client(cp, tok, cut, cc)
            return stack.apply(act, "cut_act", "up"), cc

        def server_step(sp, payload, sc):
            if session._fused is not None and isinstance(payload,
                                                         PackedInt8):
                logits, sc = session._fused_server_decode(sp, payload, sc)
            else:
                logits, sc = model.decode_step_server(sp, as_dense(payload),
                                                      cut, sc)
            logits = stack.apply(logits, "logits", "down")
            toks = jnp.argmax(as_dense(logits)[:, -1], axis=-1
                              ).astype(jnp.int32)
            # every slot's token again as its own (1, 1) output: the
            # tenant's next `cur`, with no dispatch on the host
            curs = tuple(toks[b][None, None] for b in range(toks.shape[0]))
            return toks, curs, sc

        def scatter(full, one, b):
            """Write a tenant's B=1 server cache into stacked slot `b`.
            Tensor leaves are (n, 1, ...) into (n, B, ...); the per-row
            `pos` cursor is the ndim-smaller case: (n,) into (n, B)."""
            def put(f, o):
                return f.at[:, b].set(o[:, 0] if o.ndim == f.ndim else o)
            return jax.tree_util.tree_map(put, full, one)

        self._jit_client = jax.jit(client_step)
        self._jit_server = jax.jit(server_step)
        self._jit_scatter = jax.jit(scatter, static_argnames="b")

    # ---- admission ---------------------------------------------------------

    def free_slots(self) -> list[int]:
        return [b for b in range(self.max_batch) if b not in self.tenants]

    def join(self, prompt, max_new: int, extra: dict | None = None) -> int:
        """Prefill one tenant (B=1 compiled forward per half) and seat it
        in a free slot.  prompt: (prompt_len,) or (1, prompt_len)."""
        free = self.free_slots()
        if not free:
            raise RuntimeError("batch full — no free slot")
        b, serial = free[0], self.joined
        self.joined += 1
        with TraceAnnotation("repro.batcher.join", tenant=serial, slot=b):
            prompt = jnp.asarray(prompt)
            if prompt.ndim == 1:
                prompt = prompt[None]
            sess = self.session
            batch = {"tokens": prompt}
            if extra:
                batch.update(extra)
            with TraceAnnotation("repro.batcher.prefill"):
                tok0, cc, sc1 = sess._jit_prefill(sess.client_params,
                                                  sess.server_params, batch)
            with TraceAnnotation("repro.batcher.scatter"):
                self._sc = self._jit_scatter(self._sc, sc1, b)
            with TraceAnnotation("repro.batcher.price"):
                pc = sess.prefill_cost(1, prompt.shape[1], extra)
            self.bytes_up += pc.bytes_up
            self.bytes_down += pc.bytes_down
            self.tokens_generated += 1
            with TraceAnnotation("repro.batcher.first_token"):
                first = int(tok0[0, 0])
            self.host_reads += 1
            t = Tenant(slot=b, max_new=max_new, tokens=[first], cache=cc,
                       cur=tok0, serial=serial)
            self.tenants[b] = t
            self._maybe_finish(t)
        return b

    # ---- the batched step --------------------------------------------------

    def _part(self, b):
        t = self.tenants.get(b)
        if t is not None and not t.done:
            with TraceAnnotation("repro.batcher.client", tenant=t.serial,
                                 slot=b):
                act, t.cache = self._jit_client(self.session.client_params,
                                                t.cur, t.cache)
            return act
        if self._pad_part is None:
            d = self.session.cfg.d_model
            zero = jnp.zeros((1, 1, d), self.session.cfg.dtype)
            self._pad_part = self.session.stack.apply(zero, "cut_act", "up")
        return self._pad_part

    def step(self) -> dict[int, int]:
        """Advance every live tenant one token.  Returns {slot: token}
        for the tokens sampled this step."""
        live = [b for b, t in self.tenants.items() if not t.done]
        if not live:
            return {}
        with TraceAnnotation("repro.batcher.step", live=len(live)):
            parts = [self._part(b) for b in range(self.max_batch)]
            with TraceAnnotation("repro.batcher.stack"):
                payload = stack_packed(parts, axis=0)
            with TraceAnnotation("repro.batcher.server"):
                toks, curs, self._sc = self._jit_server(
                    self.session.server_params, payload, self._sc)
            with TraceAnnotation("repro.batcher.tokens"):
                toks = np.asarray(toks)
                self.host_reads += 1
                self.steps += 1
                out = {}
                for b in live:
                    t = self.tenants[b]
                    tok = int(toks[b])
                    t.tokens.append(tok)
                    t.cur = curs[b]
                    out[b] = tok
                    self.bytes_up += self._decode_up
                    self.bytes_down += self._decode_down
                    self.tokens_generated += 1
                    self._maybe_finish(t)
        return out

    def _maybe_finish(self, t: Tenant):
        if len(t.tokens) >= t.max_new or (self.eos_id is not None
                                          and t.tokens[-1] == self.eos_id):
            t.done = True
            self.tenants.pop(t.slot, None)
            self.finished.append(t)

    def run(self, max_steps: int = 10_000) -> list[Tenant]:
        """Step until every seated tenant finishes; returns and clears
        the finished list (join/run can then continue — the slots are
        free)."""
        for _ in range(max_steps):
            if not self.step():
                break
        done, self.finished = self.finished, []
        return done

    # ---- metering ----------------------------------------------------------

    @property
    def bytes_per_token(self) -> float:
        return ((self.bytes_up + self.bytes_down)
                / max(self.tokens_generated, 1))
