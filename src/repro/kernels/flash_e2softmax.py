"""Pallas TPU kernel: fused Flash-E2Softmax attention (beyond-paper §7.1).

The paper streams Softmax through a two-stage ASIC unit with 4-bit
intermediate buffers; on TPU the same online normalization fuses the
entire E2Softmax *into* the QK^T -> P@V pipeline:

  * grid (batch*heads, q_blocks, kv_blocks), kv innermost;
  * VMEM scratch carries the running (max, sum, acc) per q tile — the
    O(S^2) stage-1 output never exists anywhere;
  * the running sum is rescaled by the *quantized* correction
    2^{-Log2Exp(dm)} exactly as the hardware Correction path does;
  * ALDivision's per-row factor 2^{-(k_s+1)} (1.636 - q) is applied once
    on the final accumulator;
  * causal q-block/kv-block pairs that are fully masked are *skipped*
    (pl.when), halving compute vs the XLA scan formulation — the ASIC's
    "don't stream masked elements" trick, block-granular.

MXU alignment: block_q = block_k = 128+ and head_dim a multiple of 128
(64 is still fine on v5e via lane packing). bf16 inputs, fp32 accumulate.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sole.e2softmax import ALDIV_BIAS, INV_LN2_SHIFT_APPROX
from repro.ops.interpret import resolve_interpret

NEG = -1e30
LOG2E = 1.4426950408889634


def _online_update(logits, mask, m_prev, *, sole: bool, exp_bits: int,
                   int8_scale: Optional[float], exact_corr: bool):
    """One online-softmax block update shared by all kernel variants.

    Returns (m_new, w, corr): the new running max, the (masked) block
    weights, and the rescale factor for the running (sum, acc) — either
    the paper's quantized Correction 2^{-Log2Exp(dm)} or the fp32
    exact rescale (exact_corr).
    """
    m_new = jnp.maximum(m_prev, jnp.max(logits, -1))
    dm = logits - m_new[..., None]
    if sole:
        if int8_scale is not None:
            dm = jnp.clip(jnp.round(dm / int8_scale), -127, 0) * int8_scale
        kcode = jnp.clip(jnp.round(-dm * INV_LN2_SHIFT_APPROX),
                         0.0, float(2 ** exp_bits - 1))
        w = jnp.where(mask, jnp.exp2(-kcode), 0.0)
        if exact_corr:
            # beyond-paper: fp32 rescale (free on TPU — the running
            # accumulator is fp32 VMEM anyway); recovers two-pass
            # accuracy while keeping 4-bit w codes.
            corr = jnp.exp2((m_prev - m_new) * LOG2E)
        else:
            # paper Alg.1: quantized Correction 2^{-Log2Exp(dm)}
            sub = jnp.clip(
                jnp.round(-(m_prev - m_new) * INV_LN2_SHIFT_APPROX),
                0.0, float(2 ** (exp_bits + 2) - 1))
            corr = jnp.exp2(-sub)
    else:
        w = jnp.where(mask, jnp.exp2(dm * LOG2E), 0.0)
        corr = jnp.exp2((m_prev - m_new) * LOG2E)
    return m_new, w, corr


def _final_scale(s, *, sole: bool):
    """Per-row output scale: ALDivision (sole) or exact 1/s."""
    s = jnp.maximum(s, 2.0 ** -30)
    if sole:
        mant, expo = jnp.frexp(s)
        factor = jnp.where(mant >= 0.75, ALDIV_BIAS - 0.5, ALDIV_BIAS)
        return jnp.exp2(-expo.astype(jnp.float32)) * factor
    return 1.0 / s


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, s_ref, acc_ref, *,
            causal: bool, sole: bool, exp_bits: int,
            int8_scale: Optional[float], kv_len: int, scale: float,
            exact_corr: bool):
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bk = k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level causal skip: block fully masked iff every q row < every
    # k col, i.e. iq*bq + bq - 1 < ik*bk.
    run = jnp.asarray(True)
    if causal:
        run = (iq * bq + bq - 1) >= (ik * bk)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < kv_len
        if causal:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (rows >= cols)
        logits = jnp.where(mask, logits, NEG)
        m_new, w, corr = _online_update(
            logits, mask, m_ref[...], sole=sole, exp_bits=exp_bits,
            int8_scale=int8_scale, exact_corr=exact_corr)
        m_ref[...] = m_new
        s_ref[...] = s_ref[...] * corr + jnp.sum(w, -1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(
                            w, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(ik == nk - 1)
    def _final():
        scale_out = _final_scale(s_ref[...], sole=sole)
        o_ref[0] = acc_ref[...] * scale_out[:, None]


@functools.partial(jax.jit, static_argnames=(
    "causal", "sole", "exp_bits", "int8_scale", "block_q", "block_k",
    "interpret", "exact_corr"))
def flash_e2softmax_pallas(q, k, v, *, causal: bool = True,
                           sole: bool = True, exp_bits: int = 4,
                           int8_scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: Optional[bool] = None,
                           exact_corr: bool = False):
    """Fused attention. q,k,v: (BH, S, d) (fold batch*heads outside)."""
    interpret = resolve_interpret(interpret)
    bh, s, d = q.shape
    t = k.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, t)
    if causal and bq != bk:
        bk = bq = min(bq, bk)
    pad_q = (-s) % bq
    pad_k = (-t) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    nq = (s + pad_q) // bq
    nk = (t + pad_k) // bk
    kern = functools.partial(
        _kernel, causal=causal, sole=sole, exp_bits=exp_bits,
        int8_scale=int8_scale, kv_len=t, scale=d ** -0.5,
        exact_corr=exact_corr)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((bh, s + pad_q, d), jnp.float32),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :s] if pad_q else out


# -- paged variants (serve path: KV lives in a block-paged pool) --------------

# VMEM that one grid step of the paged kernel may hold in its blocks and
# scratch: three quarters of the v5e's 16 MiB default scoped limit, the
# rest left to the body's temporaries.
PAGED_VMEM_BUDGET = 12 * 2 ** 20


def _vmem_bytes(shape, dtype) -> int:
    """VMEM bytes of an array whose last two dims are padded to the
    native tile: 128 lanes by 8 sublanes of 32 bits (16 bf16, 32 int8)."""
    item = jnp.dtype(dtype).itemsize
    *lead, rows, cols = shape
    sub = 32 // item
    return (math.prod(lead) * -(-rows // sub) * sub * -(-cols // 128) * 128
            * item)


def _step_vmem(hb, c, kvh, bs, d, q_dtype, kv_dtype) -> int:
    """Blocks and scratch of one paged grid step: ``hb`` q heads of ``c``
    rows each, against one page of ``kvh`` KV heads."""
    blk = (hb, c, d)
    return (2 * _vmem_bytes(blk, q_dtype)                 # q, two buffers
            + 3 * _vmem_bytes(blk, jnp.float32)           # out (two), acc
            + 4 * _vmem_bytes((kvh, bs, d), kv_dtype)     # K, V (two each)
            + 2 * _vmem_bytes((hb, c), jnp.float32))      # m, s


def paged_head_block(h: int, kvh: int, c: int, d: int, bs: int, q_dtype,
                     kv_dtype) -> int:
    """Q heads that one grid step of the paged kernel folds: the largest
    divisor of ``h`` whose blocks and scratch, beside a page of all
    ``kvh`` KV heads, fit ``PAGED_VMEM_BUDGET``."""
    for hb in range(h, 1, -1):
        if h % hb == 0 and _step_vmem(hb, c, kvh, bs, d, q_dtype,
                                      kv_dtype) <= PAGED_VMEM_BUDGET:
            return hb
    return 1


def _paged_kernel(meta_ref, table_ref, kvmap_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, s_ref, acc_ref, *, causal: bool, sole: bool,
                  exp_bits: int, int8_scale: Optional[float],
                  exact_corr: bool, scale: float, block_size: int,
                  num_blocks: int, kv_scale: Optional[float],
                  quant_pv: bool):
    """Gather-by-page-table flash attention, a block of heads at once.

    Grid (B, H // hb, NB). A step holds ``hb`` q heads of one sequence and
    one page of every KV head: the k/v BlockSpec index maps read the page
    id from the scalar-prefetched ``table_ref``, so each (b, j) step DMAs
    one ``(KV, block_size, d)`` tile, and the pool is never gathered into
    a contiguous cache. ``meta_ref[b] = (q_start, kv_len)``: absolute
    position of q row 0 and the number of valid keys (entries past
    kv_len are masked; their table slots point at the null page 0, so
    dead steps repeat one block index and fetch nothing).
    ``kvmap_ref[h]`` maps q head ``h`` to its pool KV head: the
    contiguous GQA grouping ``h // (H // KV)`` by default, but under
    tensor parallelism the q heads a shard holds need not start at pool
    head 0 (sharded Q over a *replicated* KV pool when ``kv_heads`` is
    not divisible by the model axis), so the map is explicit and
    scalar-prefetched. Each head's two contractions are its own
    (C, d) x (d, block_size) and (C, block_size) x (block_size, d) dots,
    so folded heads never mix; the online update runs on all heads' rows
    at once, elementwise as before.
    """
    b, hi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    hb, c = q_ref.shape[1], q_ref.shape[2]
    bs = block_size
    q_start = meta_ref[b, 0]
    kv_len = meta_ref[b, 1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (j * bs) < kv_len
    if causal:
        # block fully masked iff every key col is beyond the last q row.
        run &= (j * bs) <= (q_start + c - 1)

    # int8 page pools dequantize by kv_scale in-kernel. quant_pv (W8A8):
    # P·V accumulates the raw int8 V codes — E2Softmax's probs are powers
    # of two, so this is the hardware shift-accumulate — and kv_scale (a
    # power of two, so bit-exact to distribute) moves into the final
    # per-row output scale.
    v_scale = None if quant_pv else kv_scale

    def page(ref, i, sc):                              # (bs, d) f32
        x = ref[0, kvmap_ref[hi * hb + i]].astype(jnp.float32)
        return x if sc is None else x * sc

    @pl.when(run)
    def _body():
        logits = jnp.stack([jax.lax.dot_general(
            q_ref[0, i].astype(jnp.float32) * scale, page(k_ref, i, kv_scale),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            for i in range(hb)])                       # (hb, c, bs)
        cols = j * bs + jax.lax.broadcasted_iota(jnp.int32, (hb, c, bs), 2)
        mask = cols < kv_len
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (hb, c, bs), 1)
            mask = mask & (rows >= cols)
        logits = jnp.where(mask, logits, NEG)
        m_new, w, corr = _online_update(
            logits, mask, m_ref[...], sole=sole, exp_bits=exp_bits,
            int8_scale=int8_scale, exact_corr=exact_corr)
        m_ref[...] = m_new
        s_ref[...] = s_ref[...] * corr + jnp.sum(w, -1)
        pv = jnp.stack([jax.lax.dot_general(
            w[i], page(v_ref, i, v_scale), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for i in range(hb)])
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv

    @pl.when(j == num_blocks - 1)
    def _final():
        scale_out = _final_scale(s_ref[...], sole=sole)
        if quant_pv and kv_scale is not None:
            scale_out = scale_out * kv_scale
        o_ref[0] = acc_ref[...] * scale_out[..., None]


@functools.partial(jax.jit, static_argnames=(
    "causal", "sole", "exp_bits", "int8_scale", "exact_corr", "interpret",
    "kv_scale", "quant_pv"))
def flash_e2softmax_paged(q, k_pool, v_pool, tables, meta, *,
                          kv_head_map=None,
                          causal: bool = True, sole: bool = True,
                          exp_bits: int = 4,
                          int8_scale: Optional[float] = None,
                          exact_corr: bool = False,
                          interpret: Optional[bool] = None,
                          kv_scale: Optional[float] = None,
                          quant_pv: bool = False):
    """Fused attention over a block-paged KV pool.

    Args:
      q: (B, H, C, d) — C query tokens per sequence (a prefill chunk, or
        C=1 for decode). GQA is handled inside the kernel (no head
        repeat is materialized in HBM).
      k_pool, v_pool: (N, KV, block_size, d) — the shared page pool,
        head-major so one page of every KV head is one contiguous
        (KV, block_size, d) tile (Mosaic needs a block's last two dims
        to equal the array's or be (8, 128)-aligned).
      tables: (B, NB) int32 per-sequence page tables; unused slots must
        hold 0 (the reserved null page) so gathers stay in bounds.
      meta: (B, 2) int32 rows (q_start, kv_len) — absolute position of
        q row 0, and number of valid keys (kv_len includes the chunk
        itself, which the caller writes to the pool before attending).
      kv_head_map: optional (H,) int32 mapping q head -> pool KV head.
        Defaults to the contiguous GQA grouping ``h // (H // KV)``.
        Tensor-parallel callers pass an explicit map when this shard's
        q heads attend a KV pool slice that does not start at its own
        head 0 — the replicated-KV fallback for ``kv_heads`` not
        divisible by the model axis (see models/layers.paged_attend).

    Each grid step folds ``paged_head_block`` q heads: all of them,
    unless a long chunk's blocks would overflow VMEM. Returns
    (B, H, C, d) float32.
    """
    interpret = resolve_interpret(interpret)
    bsz, h, c, d = q.shape
    _, kvh, bs, _ = k_pool.shape
    nb = tables.shape[1]
    if kv_head_map is None:
        kv_head_map = jnp.arange(h, dtype=jnp.int32) // max(h // kvh, 1)
    hb = paged_head_block(h, kvh, c, d, bs, q.dtype, k_pool.dtype)

    def q_map(b, hi, j, *_):
        return b, hi, 0, 0

    def kv_map(b, hi, j, meta, tbl, kvm):
        return tbl[b, j], 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, h // hb, nb),
        in_specs=[pl.BlockSpec((1, hb, c, d), q_map),
                  pl.BlockSpec((1, kvh, bs, d), kv_map),
                  pl.BlockSpec((1, kvh, bs, d), kv_map)],
        out_specs=pl.BlockSpec((1, hb, c, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hb, c), jnp.float32),
            pltpu.VMEM((hb, c), jnp.float32),
            pltpu.VMEM((hb, c, d), jnp.float32),
        ],
    )
    kern = functools.partial(
        _paged_kernel, causal=causal, sole=sole, exp_bits=exp_bits,
        int8_scale=int8_scale, exact_corr=exact_corr, scale=d ** -0.5,
        block_size=bs, num_blocks=nb, kv_scale=kv_scale,
        quant_pv=quant_pv)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((bsz, h, c, d), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(meta.astype(jnp.int32), tables.astype(jnp.int32),
      kv_head_map.astype(jnp.int32), q, k_pool, v_pool)


def flash_e2softmax_paged_decode(q, k_pool, v_pool, tables, ctx_lens, *,
                                 kv_head_map=None,
                                 sole: bool = True, exp_bits: int = 4,
                                 int8_scale: Optional[float] = None,
                                 exact_corr: bool = False,
                                 interpret: Optional[bool] = None,
                                 kv_scale: Optional[float] = None,
                                 quant_pv: bool = False):
    """Single-query decode fast path over the paged pool.

    q: (B, H, d) — the one live query per sequence; ctx_lens (B,) counts
    valid keys *including* the current token (written before the call).
    A lone trailing query needs no causal iota work — masking reduces to
    ``col < ctx_len`` — so the kernel runs with causal=False.
    """
    meta = jnp.stack(
        [jnp.zeros_like(ctx_lens, jnp.int32), ctx_lens.astype(jnp.int32)], 1)
    out = flash_e2softmax_paged(
        q[:, :, None], k_pool, v_pool, tables, meta, causal=False,
        kv_head_map=kv_head_map, sole=sole, exp_bits=exp_bits,
        int8_scale=int8_scale, exact_corr=exact_corr, interpret=interpret,
        kv_scale=kv_scale, quant_pv=quant_pv)
    return out[:, :, 0]
