"""Pallas backend: fused TPU kernels behind the registry signatures.

Kernel modules are imported lazily inside each adapter — they import
``repro.ops.interpret`` for the autodetect flag, so a top-level import
here would be circular. Each adapter matches its reference twin's
signature exactly; ``interpret=None`` flows down to the kernels and
resolves per platform (compiled on TPU, interpret elsewhere).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.ops import registry
from repro.sharding.rules import unpartitioned

Array = jax.Array


def _kernel(op: str, mode: str):
    """Register ``fn`` as the (op, mode) pallas implementation, made
    runnable under a mesh (a Mosaic call cannot be auto-partitioned)."""
    def deco(fn):
        registry.register(op, mode, "pallas")(unpartitioned(fn))
        return fn
    return deco


@_kernel("softmax", "sole")
def sole_softmax_pallas(x, *, axis: int = -1, mask=None, exp_bits: int = 4,
                        input_scale=None, interpret: Optional[bool] = None,
                        block_rows: int = 256):
    """E2Softmax kernel; masked entries produce exact 0 (reference
    semantics). ``input_scale`` snaps logits to an int8 grid pre-kernel,
    mirroring the reference ``e2softmax``."""
    if axis not in (-1, x.ndim - 1):
        raise ValueError("pallas e2softmax normalizes the last axis only")
    from repro.kernels.e2softmax import e2softmax_pallas
    if input_scale is not None:
        x = jnp.clip(jnp.round(x / input_scale), -128, 127) * input_scale
    return e2softmax_pallas(x, exp_bits=exp_bits, mask=mask,
                            block_rows=block_rows, interpret=interpret)


@_kernel("layernorm", "sole")
def sole_layernorm_pallas(x, gamma, beta, *, params=None,
                          interpret: Optional[bool] = None, **kw):
    from repro.kernels.ailayernorm import ailayernorm_pallas
    return ailayernorm_pallas(x, gamma, beta, params=params,
                              interpret=interpret)


@_kernel("rmsnorm", "sole")
def sole_rmsnorm_pallas(x, gamma, *, params=None,
                        interpret: Optional[bool] = None, **kw):
    from repro.kernels.ailayernorm import airmsnorm_pallas
    return airmsnorm_pallas(x, gamma, params=params, interpret=interpret)


@_kernel("residual_layernorm", "sole")
def sole_residual_layernorm_pallas(x, r, gamma, beta=None, *, params=None,
                                   interpret: Optional[bool] = None, **kw):
    from repro.kernels.ailayernorm import fused_add_norm_pallas
    return fused_add_norm_pallas(x, r, gamma, beta, params=params,
                                 rms=False, interpret=interpret)


@_kernel("residual_rmsnorm", "sole")
def sole_residual_rmsnorm_pallas(x, r, gamma, beta=None, *, params=None,
                                 interpret: Optional[bool] = None, **kw):
    from repro.kernels.ailayernorm import fused_add_norm_pallas
    return fused_add_norm_pallas(x, r, gamma, None, params=params,
                                 rms=True, interpret=interpret)


@_kernel("residual_layernorm_q", "sole")
def sole_residual_layernorm_q_pallas(x, r, gamma, beta=None, *, params=None,
                                     interpret: Optional[bool] = None, **kw):
    """Fused residual-add + AILayerNorm + quantize-out: returns
    ``(x + r, (int8 codes, per-row scale))`` for the next W8A8 matmul."""
    from repro.kernels.ailayernorm import fused_add_norm_quant_pallas
    return fused_add_norm_quant_pallas(x, r, gamma, beta, params=params,
                                       rms=False, interpret=interpret)


@_kernel("residual_rmsnorm_q", "sole")
def sole_residual_rmsnorm_q_pallas(x, r, gamma, beta=None, *, params=None,
                                   interpret: Optional[bool] = None, **kw):
    from repro.kernels.ailayernorm import fused_add_norm_quant_pallas
    return fused_add_norm_quant_pallas(x, r, gamma, None, params=params,
                                       rms=True, interpret=interpret)


@_kernel("matmul", "w8a8")
def w8a8_matmul_pallas(x, w, *, n_contract: int = 1,
                       interpret: Optional[bool] = None, **kw):
    """int8 x int8 through the blocked MXU kernel. Contraction axes are
    contiguous (activation trailing, weight leading), so both sides
    flatten to 2D; scales apply per output element afterwards, exactly
    as the reference twin does — the int32 accumulation is exact, so
    the two backends agree bit-for-bit."""
    from repro.kernels.int8_matmul import int8_matmul_pallas
    q, sx = x
    qw, sw = w["q"], w["s"]
    batch = q.shape[:q.ndim - n_contract]
    out_dims = qw.shape[n_contract:]
    kdim = 1
    for d in qw.shape[:n_contract]:
        kdim *= d
    ncols = 1
    for d in out_dims:
        ncols *= d
    nrows = 1
    for d in batch:
        nrows *= d
    acc = int8_matmul_pallas(q.reshape(nrows, kdim),
                             qw.reshape(kdim, ncols),
                             interpret=interpret)
    acc = acc.reshape(batch + out_dims)
    sx = sx.reshape(sx.shape[:-n_contract] + (1,) * len(out_dims))
    return acc.astype(jnp.float32) * sx.astype(jnp.float32) \
        * sw.reshape(sw.shape[n_contract:])


def _flash_attention(sole: bool):
    def fn(q, k, v, *, causal: bool = True, exp_bits: int = 4,
           int8_scale: Optional[float] = None, block: int = 128,
           interpret: Optional[bool] = None, exact_corr: bool = False):
        """Fused attention in model layout: q (B,S,H,hd), k/v
        (B,T,KV,hd) -> (B,S,H,hd). GQA broadcast + the (batch*heads)
        layout fold happen here, so the kernel sees its native
        single-head (BH, S, hd) layout."""
        from repro.kernels.flash_e2softmax import flash_e2softmax_pallas
        b, s, h, hd = q.shape
        t, kv = k.shape[1], k.shape[2]
        if kv != h:
            k = jnp.repeat(k, h // kv, axis=2)
            v = jnp.repeat(v, h // kv, axis=2)
        qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd)
        kf = jnp.moveaxis(k, 2, 1).reshape(b * h, t, hd)
        vf = jnp.moveaxis(v, 2, 1).reshape(b * h, t, hd)
        out = flash_e2softmax_pallas(
            qf, kf, vf, causal=causal, sole=sole, exp_bits=exp_bits,
            int8_scale=int8_scale, block_q=block, block_k=block,
            interpret=interpret, exact_corr=exact_corr)
        out = out.reshape(b, h, s, hd)
        return jnp.moveaxis(out, 1, 2).astype(q.dtype)
    return fn


_kernel("flash_attention", "exact")(
    _flash_attention(sole=False))
_kernel("flash_attention", "sole")(
    _flash_attention(sole=True))


def _paged_attention(sole: bool):
    def fn(q, pool_k, pool_v, tables, q_start, kv_len, *, causal: bool,
           exp_bits: int = 4, int8_scale: Optional[float] = None,
           kv_scale: Optional[float] = None, quant_pv: bool = False,
           kv_head_map=None, interpret: Optional[bool] = None, **kw):
        """Streams pages through the scalar-prefetch paged flash kernel —
        SOLE's online softmax in the serving hot loop. Layouts match the
        reference twin: q (B, C, H, hd) -> (B, C, H, hd). ``kv_head_map``
        (per-q-head pool KV-head index) overrides the contiguous-GQA
        default — required inside shard_map when q heads are sharded but
        the KV pool stays replicated."""
        from repro.kernels.flash_e2softmax import flash_e2softmax_paged
        meta = jnp.stack([q_start.astype(jnp.int32),
                          kv_len.astype(jnp.int32)], 1)
        ctx = flash_e2softmax_paged(
            jnp.moveaxis(q, 1, 2), pool_k, pool_v, tables, meta,
            causal=causal, sole=sole, exp_bits=exp_bits,
            int8_scale=int8_scale, kv_scale=kv_scale, quant_pv=quant_pv,
            kv_head_map=kv_head_map, interpret=interpret)
        return jnp.moveaxis(ctx, 1, 2).astype(q.dtype)
    return fn


_kernel("paged_attention", "exact")(
    _paged_attention(sole=False))
_kernel("paged_attention", "sole")(
    _paged_attention(sole=True))
