"""Reference backend: pure-jnp implementations of every registered op.

This is the oracle every Pallas kernel is tested against, and the
execution path XLA traces when no kernel applies (CPU, unsupported
mode, or ``ops_backend="reference"``). The mode dispatch that used to
live in ``core.nonlin`` folds into the registry here; the approximation
*math* stays where it was — ``core.sole`` (the paper), ``core.baselines``
(Softermax, I-BERT) — this module only adapts signatures and registers.

Signatures (shared with the pallas backend):

  softmax(x, *, axis=-1, mask=None, ...)
  layernorm(x, gamma, beta, ...)          rmsnorm(x, gamma, ...)
  residual_layernorm(x, r, gamma, beta, ...) -> (x + r, norm(x + r))
  residual_rmsnorm(x, r, gamma, ...)         -> (x + r, norm(x + r))
  flash_attention(q, k, v, *, causal, ...)        model layout (B,S,H,hd)
  paged_attention(q, pool_k, pool_v, tables, q_start, kv_len, *, ...)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.baselines.ibert import i_layernorm, i_softmax
from repro.core.baselines.softermax import softermax
from repro.core.sole.ailayernorm import ailayernorm, airmsnorm
from repro.core.sole.e2softmax import e2softmax
from repro.ops import registry

Array = jax.Array


# -- softmax ------------------------------------------------------------------


@registry.register("softmax", "exact", "reference")
def exact_softmax(x, *, axis=-1, mask=None, **kw):
    if mask is not None:
        x = jnp.where(mask, x, jnp.finfo(jnp.float32).min)
    out = jax.nn.softmax(x.astype(jnp.float32), axis=axis)
    if mask is not None:
        out = jnp.where(mask, out, 0.0)
    return out


registry.register("softmax", "sole", "reference")(e2softmax)
registry.register("softmax", "softermax", "reference")(softermax)
registry.register("softmax", "ibert", "reference")(i_softmax)


# -- norms --------------------------------------------------------------------


@registry.register("layernorm", "exact", "reference")
def exact_layernorm(x, gamma, beta, *, eps=1e-5, **kw):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


@registry.register("rmsnorm", "exact", "reference")
def exact_rmsnorm(x, gamma, *, eps=1e-6, **kw):
    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * gamma


@registry.register("layernorm", "sole", "reference")
def sole_layernorm(x, gamma, beta, **kw):
    return ailayernorm(x, gamma, beta, **kw)


@registry.register("rmsnorm", "sole", "reference")
def sole_rmsnorm(x, gamma, **kw):
    return airmsnorm(x, gamma, **kw)


@registry.register("layernorm", "ibert", "reference")
def ibert_layernorm(x, gamma, beta, **kw):
    return i_layernorm(x, gamma, beta)


@registry.register("rmsnorm", "ibert", "reference")
def ibert_rmsnorm(x, gamma, **kw):
    # I-BERT has no RMSNorm; reuse its LN path with beta=0, mean kept.
    return i_layernorm(x, gamma, jnp.zeros_like(gamma))


# -- fused residual + norm (reference = the unfused three-op round trip) ------


def _residual_norm(norm_mode: str, kind: str):
    def fn(x, r, gamma, beta=None, **kw):
        s = x + r
        if kind == "layernorm":
            out = registry.resolve("layernorm", norm_mode, "reference")(
                s, gamma, beta, **kw)
        else:
            out = registry.resolve("rmsnorm", norm_mode, "reference")(
                s, gamma, **kw)
        return s, out
    return fn


for _mode in registry.NORM_MODES:
    registry.register("residual_layernorm", _mode, "reference")(
        _residual_norm(_mode, "layernorm"))
    registry.register("residual_rmsnorm", _mode, "reference")(
        _residual_norm(_mode, "rmsnorm"))


# -- quantized matmul (SOLE W8A8 serving pipeline) ----------------------------
#
# Shape contract shared with the pallas backend: the activation's
# trailing ``n_contract`` axes contract against the weight's *leading*
# ``n_contract`` axes (every serve-path weight stores its contraction
# first — see sharding.rules.QUANT_WEIGHT_SPEC), so both per-channel
# weight scales (leading size-1 dims) and per-token activation scales
# (trailing size-1 dims) apply once, after the reduction.


def _wscale(w, n_contract: int):
    """Per-channel scale reshaped to the output dims it broadcasts over."""
    return w["s"].reshape(w["s"].shape[n_contract:])


@registry.register("matmul", "exact", "reference")
def exact_matmul(x, w, *, n_contract: int = 1, **kw):
    """Plain tensordot in the incoming dtypes (the fp oracle)."""
    return jnp.tensordot(x, w, n_contract)


@registry.register("matmul", "w8a16", "reference")
def w8a16_matmul(x, w, *, n_contract: int = 1, **kw):
    """int8 weights x fp activations: contract the raw codes, apply the
    per-channel scale once after (it is constant along the contraction)
    — the dequantized weight is never materialized."""
    out = jnp.tensordot(x, w["q"].astype(x.dtype), n_contract)
    return out * _wscale(w, n_contract).astype(out.dtype)


@registry.register("matmul", "w8a8", "reference")
def w8a8_matmul(x, w, *, n_contract: int = 1, **kw):
    """int8 x int8 with exact int32 accumulation.

    ``x`` is a QAct pair ``(codes, per-row scale)`` from
    ``core.sole.quant.quantize_act`` or a ``residual_*_q`` op. The int32
    dot is order-independent, so w8a8 outputs are invariant across
    decode horizons, verify chunk widths, and mesh shapes.
    """
    q, sx = x
    acc = jnp.tensordot(q, w["q"], n_contract,
                        preferred_element_type=jnp.int32)
    n_out = w["q"].ndim - n_contract
    sx = sx.reshape(sx.shape[:-n_contract] + (1,) * n_out)
    return acc.astype(jnp.float32) * sx.astype(jnp.float32) \
        * _wscale(w, n_contract)


# -- residual + norm + quantize-out (feeds the next w8a8 matmul) --------------


def _residual_norm_q(norm_mode: str, kind: str):
    base = _residual_norm(norm_mode, kind)

    def fn(x, r, gamma, beta=None, **kw):
        from repro.core.sole.quant import quantize_act
        s, out = base(x, r, gamma, beta, **kw)
        return s, quantize_act(jnp.asarray(out, jnp.float32))
    return fn


for _mode in registry.NORM_MODES:
    registry.register("residual_layernorm_q", _mode, "reference")(
        _residual_norm_q(_mode, "layernorm"))
    registry.register("residual_rmsnorm_q", _mode, "reference")(
        _residual_norm_q(_mode, "rmsnorm"))


# -- attention ----------------------------------------------------------------


def _repeat_kv(k: Array, n_heads: int) -> Array:
    kvh = k.shape[2]
    if kvh == n_heads:
        return k
    return jnp.repeat(k, n_heads // kvh, axis=2)


def snap_logits(d: Array, int8_scale: Optional[float]) -> Array:
    """int8-grid snap of post-max logits (paper: 8-bit softmax inputs)."""
    if int8_scale is None:
        return d
    q = jnp.clip(jnp.round(d / int8_scale), -127, 0)
    return q * int8_scale


def _flash_attention_ref(sole: bool):
    def fn(q, k, v, *, causal: bool = True, exp_bits: int = 4,
           int8_scale: Optional[float] = None, **kw):
        """q (B,S,H,hd), k/v (B,T,KV,hd) -> (B,S,H,hd) fp32."""
        from repro.ops import oracles as K
        b, s, h, hd = q.shape
        t = k.shape[1]
        k = _repeat_kv(k, h)
        v = _repeat_kv(v, h)
        qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd)
        kf = jnp.moveaxis(k, 2, 1).reshape(b * h, t, hd)
        vf = jnp.moveaxis(v, 2, 1).reshape(b * h, t, hd)
        out = K.flash_e2softmax_ref(qf, kf, vf, causal=causal, sole=sole,
                                    exp_bits=exp_bits, int8_scale=int8_scale)
        return jnp.moveaxis(out.reshape(b, h, s, hd), 1, 2).astype(q.dtype)
    return fn


registry.register("flash_attention", "exact", "reference")(
    _flash_attention_ref(sole=False))
registry.register("flash_attention", "sole", "reference")(
    _flash_attention_ref(sole=True))


def _paged_attention_ref(mode: str):
    def fn(q, pool_k, pool_v, tables, q_start, kv_len, *,
           causal: bool, exp_bits: int = 4,
           int8_scale: Optional[float] = None,
           kv_scale: Optional[float] = None, kv_head_map=None,
           quant_pv: bool = False, **kw):
        """Gather pages to a contiguous cache, reuse the two-pass softmax
        path — the oracle for paged-vs-dense equivalence tests and the
        fallback for softmax modes the paged kernel does not implement.

        q: (B, C, H, hd); pool_k/pool_v: (N, KV, bs, hd); tables (B, NB);
        q_start/kv_len: (B,). Returns (B, C, H, hd) in q.dtype.
        ``kv_head_map`` (per-q-head pool KV-head index) overrides the
        contiguous-GQA repeat — used inside shard_map when q heads are
        sharded but the KV pool stays replicated.

        ``quant_pv`` (W8A8 pipeline): the P·V contraction consumes the
        *raw* int8 V codes — E2Softmax's probs are exact powers of two,
        so the dot models the hardware shift-accumulate — and the single
        ``kv_scale`` dequantize applies per row after the reduction.
        Because ``kv_scale`` is a power of two, the result is bit-exact
        vs the scale-then-dot order.
        """
        from repro.serve.kv_cache import gather_kv
        b, c, h, hd = q.shape
        k = gather_kv(pool_k, tables)                   # (B, T, KV, hd)
        v = gather_kv(pool_v, tables)
        pv_scale = None
        if kv_scale is not None:                        # int8 page pools
            k = k.astype(q.dtype) * jnp.asarray(kv_scale, q.dtype)
            if quant_pv:
                pv_scale = jnp.asarray(kv_scale, jnp.float32)
                v = v.astype(q.dtype)
            else:
                v = v.astype(q.dtype) * jnp.asarray(kv_scale, q.dtype)
        t = k.shape[1]
        if kv_head_map is not None:
            kf = jnp.take(k.astype(q.dtype), kv_head_map, axis=2)
            vf = jnp.take(v.astype(q.dtype), kv_head_map, axis=2)
        else:
            kf = _repeat_kv(k.astype(q.dtype), h)
            vf = _repeat_kv(v.astype(q.dtype), h)
        qs = q * (hd ** -0.5)
        logits = jnp.einsum("bchd,bthd->bhct", qs, kf).astype(jnp.float32)
        cols = jnp.arange(t)[None, None, None, :]
        mask = cols < kv_len[:, None, None, None]
        if causal:
            rows = q_start[:, None] + jnp.arange(c)[None]   # (B, C)
            mask = mask & (rows[:, None, :, None] >= cols)
        mask = jnp.broadcast_to(mask, logits.shape)
        if mode == "sole":
            m = jnp.max(jnp.where(mask, logits, -jnp.inf), -1, keepdims=True)
            m = jnp.maximum(m, -1e30)
            probs = e2softmax(snap_logits(logits - m, int8_scale),
                              mask=mask, exp_bits=exp_bits)
        else:
            probs = registry.resolve("softmax", mode, "reference")(
                logits, mask=mask)
        ctx = jnp.einsum("bhct,bthd->bchd", probs.astype(q.dtype), vf)
        if pv_scale is not None:
            ctx = ctx * pv_scale.astype(ctx.dtype)
        return ctx
    return fn


for _mode in registry.SOFTMAX_MODES:
    registry.register("paged_attention", _mode, "reference")(
        _paged_attention_ref(_mode))
