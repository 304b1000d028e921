"""Shared model components: params, embeddings, RoPE/M-RoPE, norms, MLPs,
and GQA attention (dense / blocked-online, full / sliding-window / cross),
with the SOLE technique integrated as the softmax/norm implementation.

Everything is pure-functional jnp. Parameters are built as :class:`Param`
leaves carrying logical sharding axes; :func:`split_params` separates the
value tree (used by jit'd steps) from the axes tree (used for shardings).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import ops
from repro.configs.base import ArchConfig
from repro.core.sole.e2softmax import aldivision, log2exp
from repro.core.sole.quant import is_qtensor, quantize_act
from repro.sharding.rules import constrain

Array = jax.Array

# int8 logit grid for E2Softmax inputs: exp(-12) is below the 4-bit log2
# resolution, so [-12, 0] covers the useful post-max range (DESIGN.md §2).
LOGIT_INT8_SCALE = 12.0 / 127.0


@dataclasses.dataclass
class Param:
    value: Any
    axes: Tuple[Optional[str], ...]


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), p.axes),
    lambda axes, ch: Param(ch[0], axes),
)


def is_param(x) -> bool:
    return isinstance(x, Param)


def stack_layer_params(tree):
    """Mark vmap-stacked per-layer params with the leading 'layers' axis."""
    return jax.tree.map(lambda p: Param(p.value, ("layers",) + p.axes),
                        tree, is_leaf=is_param)


def split_params(tree):
    vals = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)
    return vals, axes


def shapes_of(tree):
    return jax.tree.map(lambda v: tuple(v.shape), tree)


def _init(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def make_param(key, shape, axes, scale=0.02) -> Param:
    return Param(_init(key, shape, scale), axes)


def zeros_param(shape, axes) -> Param:
    return Param(jnp.zeros(shape, jnp.float32), axes)


def ones_param(shape, axes) -> Param:
    return Param(jnp.ones(shape, jnp.float32), axes)


def cast(x: Array, cfg: ArchConfig) -> Array:
    return x.astype(jnp.dtype(cfg.dtype))


def qmatmul(x, w, cfg: ArchConfig, n_contract: int = 1) -> Array:
    """Matmul against an int8 weight leaf (``{"q", "s"}`` dict from
    sharding.rules.quantize_params).

    ``x`` is either a plain activation — consumed as-is at w8a16, or
    quantized per-token on the fly at w8a8 — or an ``(int8 codes,
    per-token scale)`` pair surfaced by a ``residual_*_q`` norm, which
    feeds the w8a8 matmul directly with no fp round trip. The on-the-fly
    and fused activation paths are bit-identical by construction (the
    reference ``residual_*_q`` *is* norm-then-``quantize_act``).
    Returns fp32; call sites cast to the model dtype.
    """
    if isinstance(x, tuple):
        return ops.matmul_fn("w8a8", cfg)(x, w, n_contract=n_contract)
    if cfg.quant.acts:
        qx = quantize_act(jnp.asarray(x, jnp.float32), n_contract)
        return ops.matmul_fn("w8a8", cfg)(qx, w, n_contract=n_contract)
    return ops.matmul_fn("w8a16", cfg)(x, w, n_contract=n_contract)


# -- norms ------------------------------------------------------------------


def init_norm(cfg: ArchConfig) -> Dict[str, Param]:
    d = cfg.d_model
    if cfg.norm_kind == "layernorm":
        return {"g": ones_param((d,), ("embed",)),
                "b": zeros_param((d,), ("embed",))}
    return {"g": ones_param((d,), ("embed",))}


def _norm_mode(cfg: ArchConfig, phase: str) -> str:
    return cfg.train_norm_mode if phase == "train" else cfg.norm_mode


def apply_norm(x: Array, p, cfg: ArchConfig, phase: str) -> Array:
    mode = _norm_mode(cfg, phase)
    if cfg.norm_kind == "layernorm":
        out = ops.layernorm_fn(mode, cfg)(x, p["g"], p["b"])
    else:
        out = ops.rmsnorm_fn(mode, cfg)(x, p["g"])
    return cast(out, cfg)


def apply_residual_norm(x: Array, r: Array, p, cfg: ArchConfig,
                        phase: str,
                        quant_out: bool = False) -> Tuple[Array, Array]:
    """Fused ``x + r`` followed by norm: returns (new residual stream,
    normalized output), both cast to the model dtype.

    In SOLE mode with the pallas backend this is one VMEM-resident
    kernel (residual add + PTF quantize + AILayerNorm statistics +
    affine); otherwise it falls back to the unfused reference
    composition, bit-identical to writing ``x = x + r; apply_norm(x)``.

    With ``quant_out`` (and w8a8 active) the ``residual_*_q`` twin runs
    instead: the normalized output leaves as an ``(int8 codes, per-token
    scale)`` pair that the next :func:`qmatmul` consumes directly.
    """
    mode = _norm_mode(cfg, phase)
    if quant_out and cfg.quant.acts:
        fn = ops.residual_norm_q_fn(cfg.norm_kind, mode, cfg)
        if cfg.norm_kind == "layernorm":
            s, out = fn(x, r, p["g"], p["b"])
        else:
            s, out = fn(x, r, p["g"])
        return cast(s, cfg), out
    fn = ops.residual_norm_fn(cfg.norm_kind, mode, cfg)
    if cfg.norm_kind == "layernorm":
        s, out = fn(x, r, p["g"], p["b"])
    else:
        s, out = fn(x, r, p["g"])
    return cast(s, cfg), cast(out, cfg)


# -- embeddings / head -------------------------------------------------------


def init_embed(key, cfg: ArchConfig) -> Dict[str, Param]:
    k1, k2 = jax.random.split(key)
    v, d = cfg.padded_vocab, cfg.d_model
    return {
        "table": make_param(k1, (v, d), ("vocab", "embed")),
        "head": make_param(k2, (d, v), ("embed", "vocab"),
                           scale=cfg.d_model ** -0.5),
    }


def embed_tokens(p, tokens: Array, cfg: ArchConfig) -> Array:
    x = jnp.take(cast(p["table"], cfg), tokens, axis=0)
    return constrain(x, "batch", "seq", "embed")


def lm_logits(p, x: Array, cfg: ArchConfig) -> Array:
    if is_qtensor(p["head"]):
        logits = qmatmul(x, p["head"], cfg)
    else:
        logits = jnp.einsum("...d,dv->...v", x, cast(p["head"], cfg))
    return constrain(logits.astype(jnp.float32), "batch", "seq", "vocab")


# -- RoPE / M-RoPE ------------------------------------------------------------


def rope_freqs(cfg: ArchConfig) -> Array:
    half = cfg.head_dim // 2
    return cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


def apply_rope(x: Array, positions: Array, cfg: ArchConfig) -> Array:
    """x: (..., S, H, head_dim); positions: broadcastable to (..., S)."""
    freqs = rope_freqs(cfg)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (...,S,half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def mrope_sections(cfg: ArchConfig) -> Tuple[int, int, int]:
    half = cfg.head_dim // 2
    t = half // 4
    h = (half - t) // 2
    return (t, h, half - t - h)


def apply_mrope(x: Array, positions: Array, cfg: ArchConfig) -> Array:
    """M-RoPE (qwen2-vl): positions (3, ..., S) -> per-section angles."""
    freqs = rope_freqs(cfg)                                     # (half,)
    secs = mrope_sections(cfg)
    ang3 = positions[..., None].astype(jnp.float32) * freqs     # (3,...,S,half)
    parts, start = [], 0
    for i, s in enumerate(secs):
        parts.append(ang3[i][..., start:start + s])
        start += s
    ang = jnp.concatenate(parts, -1)                            # (...,S,half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# -- MLP ----------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind in ("swiglu", "geglu"):
        return {
            "gate": make_param(ks[0], (d, f), ("embed", "ff")),
            "up": make_param(ks[1], (d, f), ("embed", "ff")),
            "down": make_param(ks[2], (f, d), ("ff", "embed")),
        }
    return {
        "up": make_param(ks[0], (d, f), ("embed", "ff")),
        "down": make_param(ks[1], (f, d), ("ff", "embed")),
    }


def apply_mlp(x: Array, p, cfg: ArchConfig) -> Array:
    kind = cfg.mlp_kind
    if is_qtensor(p["up"]):
        mm = lambda a, w: cast(qmatmul(a, w, cfg), cfg)
    else:
        mm = lambda a, w: a @ cast(w, cfg)
    if kind == "swiglu":
        h = jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"])
    elif kind == "geglu":
        h = jax.nn.gelu(mm(x, p["gate"])) * mm(x, p["up"])
    elif kind == "gelu":
        h = jax.nn.gelu(mm(x, p["up"]))
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(mm(x, p["up"])))
    else:
        raise ValueError(kind)
    h = constrain(h, "batch", "seq", "ff")
    return mm(h, p["down"])


# -- attention ----------------------------------------------------------------


def init_attention(key, cfg: ArchConfig):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": make_param(ks[0], (d, h, hd), ("embed", "heads", "head_dim")),
        "wk": make_param(ks[1], (d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": make_param(ks[2], (d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": make_param(ks[3], (h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_param((h, hd), ("heads", "head_dim"))
        p["bk"] = zeros_param((kv, hd), ("kv_heads", "head_dim"))
        p["bv"] = zeros_param((kv, hd), ("kv_heads", "head_dim"))
    return p


def _project_qkv(p, x: Array, cfg: ArchConfig):
    if is_qtensor(p["wq"]):
        q = cast(qmatmul(x, p["wq"], cfg), cfg)
        k = cast(qmatmul(x, p["wk"], cfg), cfg)
        v = cast(qmatmul(x, p["wv"], cfg), cfg)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], cfg))
        k = jnp.einsum("bsd,dhk->bshk", x, cast(p["wk"], cfg))
        v = jnp.einsum("bsd,dhk->bshk", x, cast(p["wv"], cfg))
    if cfg.qkv_bias:
        q = q + cast(p["bq"], cfg)
        k = k + cast(p["bk"], cfg)
        v = v + cast(p["bv"], cfg)
    return q, k, v


def _wo_proj(ctx: Array, p, cfg: ArchConfig) -> Array:
    """Output projection ctx (B,S,H,hd) @ wo (H,hd,D) -> (B,S,D)."""
    if is_qtensor(p["wo"]):
        return cast(qmatmul(ctx, p["wo"], cfg, n_contract=2), cfg)
    return jnp.einsum("bshk,hkd->bsd", ctx, cast(p["wo"], cfg))


def _softmax_mode(cfg: ArchConfig, phase: str) -> str:
    return cfg.train_softmax_mode if phase == "train" else cfg.softmax_mode


def _snap_logits(d: Array, cfg: ArchConfig) -> Array:
    """int8-grid snap of post-max logits (paper: 8-bit softmax inputs)."""
    return ops.snap_logits(d, LOGIT_INT8_SCALE if cfg.logit_int8 else None)


def _mask(q_pos: Array, k_pos: Array, cfg: ArchConfig, causal: bool) -> Array:
    """(..., S_q, S_k) boolean mask from positions."""
    m = k_pos[..., None, :] < 2**29  # padded keys carry pos = 2**30
    m = jnp.broadcast_to(m, q_pos.shape + k_pos.shape[-1:])
    if causal:
        m = m & (q_pos[..., :, None] >= k_pos[..., None, :])
    if cfg.window:
        m = m & ((q_pos[..., :, None] - k_pos[..., None, :]) < cfg.window)
    return m


def _repeat_kv(k: Array, n_heads: int) -> Array:
    """GQA: broadcast KV heads to full head count.

    Keeps the head axis shardable over the model axis (per-device slice =
    local Q heads' worth); avoids the (kv, group) reshape which defeats
    SPMD head sharding for kv % mesh != 0.
    """
    kvh = k.shape[2]
    if kvh == n_heads:
        return k
    return jnp.repeat(k, n_heads // kvh, axis=2)


def attend_dense(q, k, v, q_pos, k_pos, cfg: ArchConfig, phase: str,
                 causal: bool = True) -> Array:
    """Materialized-logits attention. q:(B,S,H,hd) k/v:(B,T,KV,hd)."""
    b, s, h, hd = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    qs = q * (hd ** -0.5)
    logits = jnp.einsum("bshd,bthd->bhst", qs, k).astype(jnp.float32)
    mask = _mask(q_pos, k_pos, cfg, causal)          # (s, t) or (b, s, t)
    if mask.ndim == 3:                               # per-lane positions
        mask = mask[:, None]
    mask = jnp.broadcast_to(mask, logits.shape)
    mode = _softmax_mode(cfg, phase)
    if mode == "sole":
        m = jnp.max(jnp.where(mask, logits, -jnp.inf), -1, keepdims=True)
        m = jnp.maximum(m, -1e30)
        logits = _snap_logits(logits - m, cfg)
        probs = ops.softmax_fn("sole", cfg)(logits, mask=mask, exp_bits=cfg.exp_bits)
    else:
        probs = ops.softmax_fn(mode, cfg)(logits, mask=mask)
    probs = probs.astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def attend_blocked(q, k, v, q_pos, k_pos, cfg: ArchConfig, phase: str,
                   causal: bool = True) -> Array:
    """Online-normalized blocked attention (flash-style single pass),
    tiled over both Q and KV.

    For SOLE mode this *is* the paper's E2Softmax two-stage dataflow fused
    with the P@V contraction: per-block 4-bit exponent codes 2^{-k} weight
    V immediately; the running sum is rescaled by the quantized Correction
    2^{-Log2Exp(dm)}; the final ALDivision factor (a per-row power of two
    times {0.818, 0.568}) is applied once at the end — the O(S^2) stage-1
    output never exists in memory (DESIGN.md §7.1).
    """
    b, s, h, hd = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    t = k.shape[1]
    blk = min(cfg.attn_block, t)
    padk = (-t) % blk
    if padk:
        k = jnp.pad(k, ((0, 0), (0, padk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, padk), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, padk), constant_values=2**30)
    nkb = (t + padk) // blk
    qblk = min(cfg.attn_block, s)
    padq = (-s) % qblk
    if padq:
        q = jnp.pad(q, ((0, 0), (0, padq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, padq))
    nqb = (s + padq) // qblk

    kb = jnp.moveaxis(k.reshape(b, nkb, blk, h, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nkb, blk, h, hd), 1, 0)
    pb = k_pos.reshape(nkb, blk)
    mode = _softmax_mode(cfg, phase)
    sole = mode == "sole"
    neg = jnp.float32(-1e30)
    ln2e = jnp.float32(1.4426950408889634)

    def _online_chunk(qc, qp, kb_l, vb_l, pb_l):
        # qc: (b, qblk, h, hd), qp: (qblk,)
        qs = (qc * (hd ** -0.5)).astype(jnp.float32)

        def step(carry, inp):
            m_run, s_run, acc = carry
            kc, vc, pc = inp
            logits = jnp.einsum("bshd,bthd->bhst", qs, kc).astype(jnp.float32)
            mask = jnp.broadcast_to(_mask(qp, pc, cfg, causal), logits.shape)
            logits = jnp.where(mask, logits, neg)
            m_blk = jnp.max(logits, -1)
            m_new = jnp.maximum(m_run, m_blk)
            if sole:
                d = _snap_logits(logits - m_new[..., None], cfg)
                kcode = log2exp(d, exp_bits=cfg.exp_bits)
                w = jnp.where(mask, jnp.exp2(-kcode.astype(jnp.float32)), 0.0)
                sub = log2exp(m_run - m_new, exp_bits=cfg.exp_bits + 2)
                corr = jnp.exp2(-sub.astype(jnp.float32))
            else:
                w = jnp.where(mask, jnp.exp2((logits - m_new[..., None]) * ln2e), 0.0)
                corr = jnp.exp2((m_run - m_new) * ln2e)
            s_new = s_run * corr + jnp.sum(w, -1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhst,bthd->bhsd", w, vc.astype(jnp.float32))
            return (m_new, s_new, acc_new), None

        m0 = jnp.full((b, h, qblk), neg, jnp.float32)
        s0 = jnp.zeros((b, h, qblk), jnp.float32)
        a0 = jnp.zeros((b, h, qblk, hd), jnp.float32)
        (_, s_f, acc), _ = jax.lax.scan(step, (m0, s0, a0),
                                        (kb_l, vb_l, pb_l))
        s_f = jnp.maximum(s_f, 2.0 ** -30)
        if sole:
            # ALDivision with k_y = 0: per-row 2^{-(k_s+1)} (1.636 - q).
            scale = aldivision(jnp.zeros_like(s_f, jnp.int32), s_f)
        else:
            scale = 1.0 / s_f
        return (acc * scale[..., None]).astype(q.dtype)  # (b, h, qblk, hd)

    def q_chunk(qc, qp):
        return _online_chunk(qc, qp, kb, vb, pb)

    qb = jnp.moveaxis(q.reshape(b, nqb, qblk, h, hd), 1, 0)
    qpb = q_pos.reshape(nqb, qblk)

    if cfg.window and causal and (t + padk) > cfg.window + blk:
        # SWA-aware skipping (§Perf hillclimb C): a q chunk starting at
        # q0 only sees keys in [q0 - window + 1, q0 + qblk) — slice that
        # static-size band out of K/V instead of scanning all of it.
        span = cfg.window + qblk
        span = ((span + blk - 1) // blk) * blk
        span = min(span, t + padk)
        kfull, vfull = k, v

        def q_chunk_windowed(qc, qp, i):
            q0 = i * qblk
            start = jnp.clip(q0 + qblk - span, 0, (t + padk) - span)
            ks = jax.lax.dynamic_slice_in_dim(kfull, start, span, 1)
            vs = jax.lax.dynamic_slice_in_dim(vfull, start, span, 1)
            ps = jax.lax.dynamic_slice_in_dim(k_pos, start, span, 0)
            nkb_l = span // blk
            kb_l = jnp.moveaxis(ks.reshape(b, nkb_l, blk, h, hd), 1, 0)
            vb_l = jnp.moveaxis(vs.reshape(b, nkb_l, blk, h, hd), 1, 0)
            pb_l = ps.reshape(nkb_l, blk)
            return _online_chunk(qc, qp, kb_l, vb_l, pb_l)

        idxs = jnp.arange(nqb)
        ctx = jax.lax.map(lambda args: q_chunk_windowed(*args),
                          (qb, qpb, idxs))
    else:
        ctx = jax.lax.map(lambda args: q_chunk(*args), (qb, qpb))
    ctx = jnp.moveaxis(ctx, 0, 2)              # (b, h, nqb, qblk, hd)
    ctx = jnp.moveaxis(ctx.reshape(b, h, nqb * qblk, hd), 1, 2)
    return ctx[:, :s] if padq else ctx


def apply_attention(p, x: Array, positions: Array, cfg: ArchConfig,
                    phase: str, causal: Optional[bool] = None) -> Array:
    """Self-attention over x (B,S,D) at ``positions`` (S,)."""
    causal = cfg.causal if causal is None else causal
    q, k, v = _project_qkv(p, x, cfg)
    if cfg.pos_kind == "rope":
        q = apply_rope(q, positions, cfg)
        k = apply_rope(k, positions, cfg)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    s = x.shape[1]
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "blocked" if s >= 8192 else "dense"
    fn = attend_blocked if impl == "blocked" else attend_dense
    ctx = fn(q, k, v, positions, positions, cfg, phase, causal=causal)
    ctx = constrain(ctx, "batch", "seq", "heads", "head_dim")
    out = _wo_proj(ctx, p, cfg)
    return constrain(out, "batch", "seq", "embed")


def apply_attention_mrope(p, x, positions3, cfg: ArchConfig, phase: str):
    """qwen2-vl self-attention with M-RoPE positions (3, B, S)."""
    q, k, v = _project_qkv(p, x, cfg)
    q = apply_mrope(q, positions3, cfg)
    k = apply_mrope(k, positions3, cfg)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    seq = positions3[0]                      # temporal axis orders causality
    s = x.shape[1]
    impl = cfg.attn_impl
    if impl == "auto":
        impl = "blocked" if s >= 8192 else "dense"
    # causal in the flattened order (temporal positions are nondecreasing).
    flat_pos = jnp.arange(s)
    fn = attend_blocked if impl == "blocked" else attend_dense
    ctx = fn(q, k, v, flat_pos, flat_pos, cfg, phase, causal=True)
    out = _wo_proj(ctx, p, cfg)
    return constrain(out, "batch", "seq", "embed")


def apply_cross_attention(p, x, enc_kv, cfg: ArchConfig, phase: str,
                          k_pos: Optional[Array] = None):
    """Cross-attention: queries from x, keys/values precomputed (B,T,KV,hd)x2.

    ``k_pos`` marks padded encoder positions with 2**30 (masked out).
    """
    if is_qtensor(p["wq"]):
        q = cast(qmatmul(x, p["wq"], cfg), cfg)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], cfg))
    if cfg.qkv_bias:
        q = q + cast(p["bq"], cfg)
    k, v = enc_kv
    s, t = x.shape[1], k.shape[1]
    if k_pos is None:
        k_pos = jnp.arange(t)
    q_pos = jnp.arange(s)
    if k_pos.ndim == 2:       # per-lane encoder validity (paged serving)
        q_pos = jnp.broadcast_to(q_pos, (x.shape[0], s))
    ctx = attend_dense(q, k, v, q_pos, k_pos, cfg, phase, causal=False)
    out = _wo_proj(ctx, p, cfg)
    return constrain(out, "batch", "seq", "embed")


def cross_kv(p, enc_out: Array, cfg: ArchConfig):
    if is_qtensor(p["wk"]):
        k = cast(qmatmul(enc_out, p["wk"], cfg), cfg)
        v = cast(qmatmul(enc_out, p["wv"], cfg), cfg)
    else:
        k = jnp.einsum("btd,dhk->bthk", enc_out, cast(p["wk"], cfg))
        v = jnp.einsum("btd,dhk->bthk", enc_out, cast(p["wv"], cfg))
    if cfg.qkv_bias:
        k = k + cast(p["bk"], cfg)
        v = v + cast(p["bv"], cfg)
    return k, v


# -- decode-time attention (KV cache) ----------------------------------------


def _heads_sharded(cfg: ArchConfig) -> bool:
    """True if the head axis actually shards on the active mesh."""
    from repro.sharding.rules import active_rules
    rules = active_rules()
    if rules is None:
        return False
    return (rules.dim_spec("heads", cfg.n_heads) is not None
            or rules.dim_spec("kv_heads", cfg.n_kv_heads) is not None)


def decode_attend_stacked(p, x1: Array, ck: Array, cv: Array, cpos: Array,
                          layer_idx: Array, pos: Array, cfg: ArchConfig,
                          rope: bool = True, positions3=None, slot=None
                          ) -> Tuple[Array, Array, Array]:
    """One-token attention against stacked *dot-layout-native* caches:

        ck: (L, B, KV, hd, T)   — K^T layout, the QK dot consumes it raw
        cv: (L, B, KV, T, hd)   — the PV dot layout

    The caches are READ-ONLY here (no aliasing copies in the layer scan);
    the current token's (k, v) is attended explicitly as a T+1-th column
    and returned so the caller batches all layers' slice-writes after the
    scan (§Perf hillclimb A). The grouped einsum avoids materializing the
    GQA head-repeat (kv_heads x g reads) when heads are mesh-replicated.

    Returns (attn_out, k_col (B,KV,hd,1), v_row (B,KV,1,hd)).
    """
    q, k, v = _project_qkv(p, x1, cfg)
    if cfg.pos_kind == "rope" and rope:
        rp = pos[:, None] if pos.ndim else pos[None]
        q = apply_rope(q, rp, cfg)
        k = apply_rope(k, rp, cfg)
    elif cfg.pos_kind == "mrope" and positions3 is not None:
        q = apply_mrope(q, positions3, cfg)
        k = apply_mrope(k, positions3, cfg)
    t = ck.shape[-1]
    kl = kv_dequant(jax.lax.dynamic_index_in_dim(ck, layer_idx, 0, False),
                    cfg)                                  # (B,KV,hd,T)
    vl = kv_dequant(jax.lax.dynamic_index_in_dim(cv, layer_idx, 0, False),
                    cfg)                                  # (B,KV,T,hd)
    b, _, h, hd = q.shape
    kvh = kl.shape[1]
    g = h // kvh
    # cache validity: previously-written positions, in-window, and NOT the
    # current slot (its content is stale; the live token is column T+1).
    # ``pos``/``cpos`` may carry a per-lane batch dim (left-padded dense
    # batches); everything is computed at (1|B, T) and broadcast.
    cpos2 = cpos if cpos.ndim == 2 else cpos[None]        # (1|B, T)
    pos2 = (pos if pos.ndim else pos[None])[:, None]      # (1|B, 1)
    valid = cpos2 <= pos2
    if cfg.window:
        valid &= (pos2 - cpos2) < cfg.window
    if slot is None:      # legacy: physical column == logical position
        slot = jnp.mod(pos, t) if cfg.window else jnp.minimum(pos, t - 1)
    slot2 = (slot if slot.ndim else slot[None])[:, None]
    valid &= jnp.arange(t)[None] != slot2
    mode = _softmax_mode(cfg, phase="serve")
    qg = (q * (hd ** -0.5)).reshape(b, kvh, g, hd)
    kc = k.reshape(b, kvh, 1, hd)                         # current token
    vc = v.reshape(b, kvh, 1, hd)
    logits_c = jnp.einsum("bkgd,bkdt->bkgt", qg, kl,
                          preferred_element_type=jnp.float32)
    logit_s = jnp.einsum("bkgd,bkxd->bkgx", qg, kc.astype(qg.dtype),
                         preferred_element_type=jnp.float32)
    logits = jnp.concatenate([logits_c, logit_s], axis=-1)  # (B,KV,g,T+1)
    mask = jnp.concatenate(
        [jnp.broadcast_to(valid[:, None, None, :], (b, kvh, g, t)),
         jnp.ones((b, kvh, g, 1), bool)], axis=-1)
    if mode == "sole":
        m = jnp.max(jnp.where(mask, logits, -jnp.inf), -1, keepdims=True)
        m = jnp.maximum(m, -1e30)
        probs = ops.softmax_fn("sole", cfg)(_snap_logits(logits - m, cfg), mask=mask,
                                   exp_bits=cfg.exp_bits)
    else:
        probs = ops.softmax_fn(mode, cfg)(logits, mask=mask)
    probs = probs.astype(q.dtype)
    ctx = jnp.einsum("bkgt,bktd->bkgd", probs[..., :t], vl)
    ctx = ctx + probs[..., t:] * vc
    ctx = ctx.reshape(b, 1, h, hd)
    out = _wo_proj(ctx, p, cfg)
    k_col = jnp.moveaxis(kv_quant(k, cfg), 1, 3)          # (B,KV,hd,1)
    v_row = jnp.moveaxis(kv_quant(v, cfg), 1, 2)          # (B,KV,1,hd)
    return out, k_col, v_row


def write_kv_columns(ck: Array, cv: Array, k_cols: Array, v_rows: Array,
                     slot: Array) -> Tuple[Array, Array]:
    """Batch all layers' decode writes: k_cols (L,B,KV,hd,1),
    v_rows (L,B,KV,1,hd) into the stacked caches at the ring slot."""
    zero = jnp.zeros((), slot.dtype)
    ck = jax.lax.dynamic_update_slice(
        ck, k_cols.astype(ck.dtype), (zero, zero, zero, zero, slot))
    cv = jax.lax.dynamic_update_slice(
        cv, v_rows.astype(cv.dtype), (zero, zero, zero, slot, zero))
    return ck, cv


def pack_prefill_cache(k: Array, v: Array, positions: Array, t: int,
                       cfg: ArchConfig):
    """Per-layer prefill K/V (B,S,KV,hd) -> dot-native ring buffers.

    ``positions`` is (S,) shared or (B, S) per-lane (left-padded dense
    batches mark pad slots with 2**30); the stored ring mirrors its rank.
    """
    s = k.shape[1]
    kk = k[:, -t:] if s >= t else jnp.pad(
        k, ((0, 0), (0, t - s), (0, 0), (0, 0)))
    vv = v[:, -t:] if s >= t else jnp.pad(
        v, ((0, 0), (0, t - s), (0, 0), (0, 0)))
    pp = positions[..., -t:] if s >= t else jnp.pad(
        positions, [(0, 0)] * (positions.ndim - 1) + [(0, t - s)],
        constant_values=2**30)
    if cfg.window:
        shift = jnp.mod(s, t) if s >= t else 0
        kk = jnp.roll(kk, shift, axis=1)
        vv = jnp.roll(vv, shift, axis=1)
        pp = jnp.roll(pp, shift, axis=-1)
    kq = jnp.transpose(kv_quant(kk, cfg), (0, 2, 3, 1))   # (B,KV,hd,T)
    vq = jnp.transpose(kv_quant(vv, cfg), (0, 2, 1, 3))   # (B,KV,T,hd)
    return kq, vq, pp.astype(jnp.int32)


def decode_attend(p, x1: Array, cache: Dict[str, Array], pos: Array,
                  cfg: ArchConfig, rope: bool = True,
                  positions3=None) -> Tuple[Array, Dict[str, Array]]:
    """One-token self-attention against a (B, T, KV, hd) cache.

    ``pos`` is the current absolute position (scalar int32). For windowed
    models the cache is a rolling buffer of size min(T, window).
    """
    q, k, v = _project_qkv(p, x1, cfg)
    if cfg.pos_kind == "rope" and rope:
        q = apply_rope(q, pos[None], cfg)
        k = apply_rope(k, pos[None], cfg)
    elif cfg.pos_kind == "mrope" and positions3 is not None:
        q = apply_mrope(q, positions3, cfg)
        k = apply_mrope(k, positions3, cfg)
    t = cache["k"].shape[1]
    slot = jnp.mod(pos, t) if cfg.window else jnp.minimum(pos, t - 1)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, 1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, 1)
    # positions stored in the cache
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], pos[None].astype(jnp.int32), slot, 0)
    b, _, h, hd = q.shape
    kf = _repeat_kv(cast(ck, cfg), h)
    vf = _repeat_kv(cast(cv, cfg), h)
    qs = q * (hd ** -0.5)
    logits = jnp.einsum("bshd,bthd->bhst", qs, kf).astype(jnp.float32)
    valid = cpos <= pos
    if cfg.window:
        valid &= (pos - cpos) < cfg.window
    mask = jnp.broadcast_to(valid[None, None, None, :], logits.shape)
    mode = _softmax_mode(cfg, phase="serve")
    if mode == "sole":
        m = jnp.max(jnp.where(mask, logits, -jnp.inf), -1, keepdims=True)
        m = jnp.maximum(m, -1e30)
        probs = ops.softmax_fn("sole", cfg)(_snap_logits(logits - m, cfg), mask=mask,
                                   exp_bits=cfg.exp_bits)
    else:
        probs = ops.softmax_fn(mode, cfg)(logits, mask=mask)
    ctx = jnp.einsum("bhst,bthd->bshd", probs.astype(q.dtype), vf)
    out = _wo_proj(ctx, p, cfg)
    return out, {"k": ck, "v": cv, "pos": cpos}


KV_INT8_SCALE = 1.0 / 16.0  # calibration-provided symmetric scale


def kv_store_dtype(cfg: ArchConfig):
    if cfg.kv_cache_dtype == "int8":
        return jnp.int8
    return jnp.dtype(cfg.dtype)


def kv_quant(x: Array, cfg: ArchConfig) -> Array:
    if cfg.kv_cache_dtype == "int8":
        return jnp.clip(jnp.round(x.astype(jnp.float32) / KV_INT8_SCALE),
                        -127, 127).astype(jnp.int8)
    return x.astype(jnp.dtype(cfg.dtype))


def kv_dequant(x: Array, cfg: ArchConfig) -> Array:
    if cfg.kv_cache_dtype == "int8":
        return x.astype(jnp.dtype(cfg.dtype)) * jnp.asarray(
            KV_INT8_SCALE, jnp.dtype(cfg.dtype))
    return x


def init_kv_cache(cfg: ArchConfig, batch: int, length: int,
                  dtype=None) -> Dict[str, Array]:
    t = min(length, cfg.window) if cfg.window else length
    dt = dtype or kv_store_dtype(cfg)
    return {
        "k": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((batch, t, cfg.n_kv_heads, cfg.head_dim), dt),
        "pos": jnp.full((t,), 2**30, jnp.int32),
    }


KV_CACHE_AXES = {"k": ("batch", "seq", "kv_heads", "head_dim"),
                 "v": ("batch", "seq", "kv_heads", "head_dim"),
                 "pos": (None,)}


# -- paged attention (block-paged KV pool; see serve/kv_cache.py) -------------


def _paged_kv_scale(cfg: ArchConfig):
    return KV_INT8_SCALE if cfg.kv_cache_dtype == "int8" else None


def paged_attend(q: Array, pool_k: Array, pool_v: Array, tables: Array,
                 q_start: Array, kv_len: Array, cfg: ArchConfig, *,
                 causal: bool, backend: Optional[str] = None) -> Array:
    """Attention for C chunk queries per sequence against paged KV.

    q: (B, C, H, hd); pool_k/pool_v: (N, KV, bs, hd) one layer's pool
    (the chunk's own K/V already written); tables: (B, NB) page tables;
    q_start/kv_len: (B,) absolute position of q row 0 / valid key count.

    The implementation resolves through the ``repro.ops`` registry:
    ``pallas`` streams pages through the scalar-prefetch flash kernel
    (SOLE's online-softmax in the serving hot loop); ``reference``
    gathers pages to a contiguous cache and reuses the two-pass softmax
    path — the oracle for paged-vs-dense equivalence tests and the
    fallback for softmax modes the kernel does not implement.
    ``backend=None`` resolves from ``cfg.ops_backend``.
    """
    mode = _softmax_mode(cfg, phase="serve")
    sole = mode == "sole"
    fn = ops.paged_attention_fn(mode, cfg, backend)
    kv_scale = _paged_kv_scale(cfg)
    kw = dict(causal=causal, exp_bits=cfg.exp_bits,
              int8_scale=(LOGIT_INT8_SCALE if sole and cfg.logit_int8
                          else None),
              kv_scale=kv_scale,
              # w8a8: keep V as raw int8 codes through the PV contraction
              # and fold kv_scale into the final per-row output scale —
              # bit-exact (the scale is a power of two) and int8-dot-able.
              quant_pv=bool(cfg.quant.acts and kv_scale is not None))
    from repro.sharding.rules import active_rules
    rules = active_rules()
    plan = None if rules is None else _paged_tp_plan(
        rules, q.shape[2], pool_k.shape[1])
    if plan is None:
        return fn(q, pool_k, pool_v, tables, q_start, kv_len, **kw)
    return _paged_attend_tp(fn, q, pool_k, pool_v, tables, q_start, kv_len,
                            rules, plan, kw)


def _paged_tp_plan(rules, h: int, kvh: int):
    """Tensor-parallel plan for paged attention under the active rules.

    Returns ``(axes, kv_sharded)`` — the mesh axis (or axis tuple)
    sharding the q-heads dim, and whether the pool's kv_heads dim shards
    the same way — or None when heads fall back to replicated (the
    divisibility fallback, e.g. qwen2's 14 heads on an 8-way axis) or
    the axis product is 1 (nothing to split).
    """
    ax = rules.dim_spec("heads", h)
    if ax is None:
        return None
    names = ax if isinstance(ax, tuple) else (ax,)
    if math.prod(rules.axis_sizes[a] for a in names) == 1:
        return None
    return ax, rules.dim_spec("kv_heads", kvh) == ax


def _paged_attend_tp(fn, q, pool_k, pool_v, tables, q_start, kv_len,
                     rules, plan, kw):
    """Run paged attention under shard_map with q heads split over the
    model axis.

    Two pool regimes (satellite of the divisibility-fallback rules):

    * matched — kv_heads shards the same axis; each shard holds its own
      contiguous KV block and the local GQA map is ``arange(Hloc)//g``.
    * replicated KV — kv_heads doesn't divide the axis (GQA with few KV
      heads): the pool is full on every shard and local q head ``i`` on
      shard ``s`` reads *global* KV head ``(s*Hloc + i)//g``.

    Page tables and per-seq metadata stay host-global (replicated);
    the kernel output is resharded back onto the heads axis, so the
    surrounding GSPMD program sees an ordinary sharded activation.
    """
    from repro.sharding.rules import SHARD_MAP_NOCHECK, shard_map
    axes, kv_sharded = plan
    h, kvh = q.shape[2], pool_k.shape[1]
    g = max(h // max(kvh, 1), 1)
    names = axes if isinstance(axes, tuple) else (axes,)
    sizes = [rules.axis_sizes[a] for a in names]

    def body(q, pk, pv, tbl, qs, kl):
        hloc = q.shape[2]
        if kv_sharded:
            kvmap = jnp.arange(hloc, dtype=jnp.int32) // g
        else:
            shard = jnp.int32(0)
            for a, n in zip(names, sizes):
                shard = shard * n + jax.lax.axis_index(a)
            kvmap = (shard * hloc
                     + jnp.arange(hloc, dtype=jnp.int32)) // g
        return fn(q, pk, pv, tbl, qs, kl, kv_head_map=kvmap, **kw)

    from jax.sharding import PartitionSpec as P
    qspec = P(None, None, axes, None)
    kvspec = P(None, axes if kv_sharded else None, None, None)
    wrapped = shard_map(body, mesh=rules.mesh,
                        in_specs=(qspec, kvspec, kvspec, P(), P(), P()),
                        out_specs=qspec, **SHARD_MAP_NOCHECK)
    return wrapped(q, pool_k, pool_v, tables, q_start, kv_len)
