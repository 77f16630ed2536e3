"""Jit'd public wrappers for the Pallas kernels + the dispatch switch.

Kernel dispatch is a three-way mode, resolved per call:

    pallas — real Pallas lowering on a TPU.  Asking for it on a host
             with no TPU raises: a chip run never silently measures the
             interpreter;
    interp — `pallas_call(interpret=True)`: the SAME kernel bodies
             executed through the Pallas interpreter (what CPU/CI runs —
             the kernel code path stays exercised without an accelerator);
    ref    — the pure-jnp oracles in `kernels.ref` (debugging baseline).

Precedence: an explicit `interpret=` argument > the `REPRO_KERNELS`
env var (pallas|interp|ref) > auto (pallas on TPU, interp elsewhere).
GQA head expansion for flash attention happens here, not in the kernel
(the kernel requires equal head counts).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.splitcat_linear import (splitcat_linear_pallas,
                                           splitcat_linear_q8_pallas)
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.wire_quant import (wire_dequant_pallas, wire_quant_pallas,
                                      wire_roundtrip)

KERNEL_MODES = ("pallas", "interp", "ref")


def _has_accelerator() -> bool:
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def kernel_mode() -> str:
    """Resolve the ambient kernel dispatch mode (see module docstring).
    Read per call so tests/nightly lanes can flip `REPRO_KERNELS`
    without reimporting."""
    mode = os.environ.get("REPRO_KERNELS", "").strip().lower()
    if mode:
        if mode not in KERNEL_MODES:
            raise ValueError(
                f"REPRO_KERNELS={mode!r}: must be one of {KERNEL_MODES}")
        if mode == "pallas" and not _has_accelerator():
            raise RuntimeError(
                "REPRO_KERNELS=pallas needs a TPU, and jax sees none; "
                "use REPRO_KERNELS=interp (or leave it unset) on a CPU host")
        return mode
    return "pallas" if _has_accelerator() else "interp"


def _resolve(interpret: bool | None) -> str:
    if interpret is not None:
        return "interp" if interpret else "pallas"
    return kernel_mode()


# ---------------------------------------------------------------------------
# jit'd pallas entry points (static interpret flag)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _rmsnorm_jit(x, scale, *, eps, interpret):
    return rmsnorm_pallas(x, scale, eps=eps, interpret=interpret)


def rmsnorm(x, scale, *, eps: float = 1e-6, interpret: bool | None = None):
    mode = _resolve(interpret)
    if mode == "ref":
        return ref.rmsnorm_ref(x, scale, eps=eps)
    return _rmsnorm_jit(x, scale, eps=eps, interpret=(mode == "interp"))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _splitcat_jit(parts, w, b, *, interpret):
    return splitcat_linear_pallas(list(parts), w, b, interpret=interpret)


def splitcat_linear(parts, w, b=None, *, interpret: bool | None = None):
    mode = _resolve(interpret)
    if mode == "ref":
        return ref.splitcat_linear_ref(list(parts), w, b)
    return _splitcat_jit(list(parts), w, b, interpret=(mode == "interp"))


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _splitcat_q8_jit(qs, scales, w, b, *, out_dtype, interpret):
    return splitcat_linear_q8_pallas(list(qs), list(scales), w, b,
                                     out_dtype=out_dtype,
                                     interpret=interpret)


def splitcat_linear_q8(qs, scales, w, b=None, *, out_dtype=jnp.float32,
                       interpret: bool | None = None):
    """Fused dequant+concat+matmul over packed int8 modality payloads —
    the server entry layer consuming the physical wire directly."""
    mode = _resolve(interpret)
    if mode == "ref":
        return ref.splitcat_linear_q8_ref(list(qs), list(scales), w, b,
                                          out_dtype=out_dtype)
    return _splitcat_q8_jit(list(qs), list(scales), w, b,
                            out_dtype=jnp.dtype(out_dtype),
                            interpret=(mode == "interp"))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _wire_quant_jit(x, *, interpret):
    return wire_quant_pallas(x, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _wire_dequant_jit(q, scale, *, dtype, interpret):
    return wire_dequant_pallas(q, scale, dtype, interpret=interpret)


def wire_quantize(x, *, interpret: bool | None = None):
    """Fused per-row absmax quantize + int8 pack: x -> (q, row scales).
    Scalar (0-d) payloads — possible in the param trees the handoff and
    baseline wires quantize — are packed as one-element rows and keep
    their logical () shape."""
    if jnp.ndim(x) == 0:
        q, s = wire_quantize(x[None], interpret=interpret)
        return q[0], s[0]
    mode = _resolve(interpret)
    if mode == "ref":
        return ref.wire_quant_ref(x)
    return _wire_quant_jit(x, interpret=(mode == "interp"))


def wire_dequantize(q, scale, dtype=jnp.float32, *,
                    interpret: bool | None = None):
    if jnp.ndim(q) == 0:
        return wire_dequantize(q[None], scale[None], dtype,
                               interpret=interpret)[0]
    mode = _resolve(interpret)
    if mode == "ref":
        return ref.wire_dequant_ref(q, scale, dtype)
    return _wire_dequant_jit(q, scale, dtype=jnp.dtype(dtype),
                             interpret=(mode == "interp"))


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def _flash_jit(q, k, v, *, causal, window, block_q, block_kv, interpret):
    H, K = q.shape[2], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv, interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, block_q: int = 128,
                    block_kv: int = 128, interpret: bool | None = None):
    """q: (B,S,H,D); k,v: (B,S,K,D) with H % K == 0 (GQA expanded here)."""
    mode = _resolve(interpret)
    if mode == "ref":
        H, K = q.shape[2], k.shape[2]
        if K != H:
            k = jnp.repeat(k, H // K, axis=2)
            v = jnp.repeat(v, H // K, axis=2)
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_jit(q, k, v, causal=causal, window=window, block_q=block_q,
                      block_kv=block_kv, interpret=(mode == "interp"))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(x, dt, A, Bm, Cm, *, chunk, interpret):
    return ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                           interpret=interpret)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 64,
             interpret: bool | None = None):
    mode = _resolve(interpret)
    if mode == "ref":
        return ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    return _ssd_jit(x, dt, A, Bm, Cm, chunk=chunk,
                    interpret=(mode == "interp"))


__all__ = ["KERNEL_MODES", "kernel_mode", "rmsnorm", "splitcat_linear",
           "splitcat_linear_q8", "wire_quantize", "wire_dequantize",
           "wire_roundtrip", "flash_attention", "ssd_scan"]
