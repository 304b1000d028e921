"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret
autodetects to the Python kernel bodies on CPU).

Kernel entry points resolve through the ``repro.ops`` registry — the
legacy ``repro.kernels.ops`` wrappers are gone. The raw-kernel parity
tests below import kernel modules directly (``# repro: noqa RPR001``):
they exist precisely to pin the layer *below* the registry.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs.base import ARCH_NAMES, get_config
from repro.core.sole.quant import calibrate_ptf
from repro.ops import oracles as K

e2softmax_op = ops.softmax_fn("sole", backend="pallas")
ailayernorm_op = ops.layernorm_fn("sole", backend="pallas")


def flash_attention_op(q, k, v, *, sole=True, **kw):
    return ops.flash_attention_fn("sole" if sole else "exact",
                                  backend="pallas")(q, k, v, **kw)


@pytest.mark.parametrize("shape", [(4, 64), (3, 5, 130), (1, 1000), (7, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("exp_bits", [4, 6])
def test_e2softmax_kernel_matches_ref(rng, shape, dtype, exp_bits):
    x = jnp.asarray(rng.normal(0, 3, shape)).astype(dtype)
    out = e2softmax_op(x, exp_bits=exp_bits)
    ref = K.e2softmax_ref(x, exp_bits=exp_bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("shape", [(8, 64), (2, 12, 256), (5, 896)])
def test_ailayernorm_kernel_matches_ref(rng, shape):
    c = shape[-1]
    x = jnp.asarray(rng.normal(0.5, 2, shape).astype(np.float32))
    g = jnp.asarray(rng.normal(1, 0.1, c).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.1, c).astype(np.float32))
    p = calibrate_ptf(x, unsigned=True)
    out = ailayernorm_op(x, g, b, params=p)
    xi = p.quantize(x) - p.zero_point
    ref = K.ailayernorm_ref(xi, p.alpha, g, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_exact_mode_matches_softmax(rng, causal, dtype):
    B, S, H, hd = 2, 80, 2, 32
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, S, H, hd))).astype(dtype)
               for _ in range(3))
    out = flash_attention_op(q, k, v, causal=causal, sole=False, block=32)
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * H, S, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * H, S, hd)
    ref = K.flash_e2softmax_ref(qf, kf, vf, causal=causal, sole=False)
    ref = jnp.moveaxis(ref.reshape(B, H, S, hd), 1, 2)
    tol = 5e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_sole_single_block_bit_exact(rng):
    """With one kv block the online pipeline reduces to the two-pass ref."""
    B, S, H, hd = 2, 96, 2, 32
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, S, H, hd)).astype(np.float32))
               for _ in range(3))
    out = flash_attention_op(q, k, v, causal=True, sole=True, block=96)
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, hd)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * H, S, hd)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * H, S, hd)
    ref = K.flash_e2softmax_ref(qf, kf, vf, causal=True, sole=True)
    ref = jnp.moveaxis(ref.reshape(B, H, S, hd), 1, 2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("kv_heads", [1, 2])
@pytest.mark.parametrize("block", [32, 48])
def test_flash_sole_multiblock_close(rng, kv_heads, block):
    B, S, H, hd = 2, 96, 4, 16
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, S, kv_heads, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, S, kv_heads, hd)).astype(np.float32))
    out = flash_attention_op(q, k, v, causal=True, sole=True, block=block)
    kr = jnp.repeat(k, H // kv_heads, 2)
    vr = jnp.repeat(v, H // kv_heads, 2)
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, hd)
    kf = jnp.moveaxis(kr, 2, 1).reshape(B * H, S, hd)
    vf = jnp.moveaxis(vr, 2, 1).reshape(B * H, S, hd)
    ref = K.flash_e2softmax_ref(qf, kf, vf, causal=True, sole=True)
    ref = jnp.moveaxis(ref.reshape(B, H, S, hd), 1, 2)
    # online quantized corrections deviate elementwise; mean stays tight
    assert float(jnp.mean(jnp.abs(out - ref))) < 0.02


@pytest.mark.parametrize("shape", [(40, 96), (96, 40), (57, 57), (33, 70)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_rectangular_and_ragged_shapes(rng, shape, causal):
    """Parity on S != T and non-multiple-of-block shapes (exact mode)."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_pallas)
    s, t = shape
    bh, hd = 4, 16
    q = jnp.asarray(rng.normal(0, 1, (bh, s, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (bh, t, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (bh, t, hd)).astype(np.float32))
    out = flash_e2softmax_pallas(q, k, v, causal=causal, sole=False,
                                 block_q=16, block_k=16)
    ref = K.flash_e2softmax_ref(q, k, v, causal=causal, sole=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_sole_ragged_single_block_bit_exact(rng):
    """Non-multiple shape padded into one block still reduces to the
    two-pass reference exactly (padding is fully masked)."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_pallas)
    bh, s, hd = 4, 57, 16
    q, k, v = (jnp.asarray(rng.normal(0, 1, (bh, s, hd)).astype(np.float32))
               for _ in range(3))
    out = flash_e2softmax_pallas(q, k, v, causal=True, sole=True,
                                 block_q=64, block_k=64)
    ref = K.flash_e2softmax_ref(q, k, v, causal=True, sole=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def _page_pool(rng, n, bs, kv, hd):
    """Head-major (n, KV, bs, hd) K and V pools."""
    kp = jnp.asarray(rng.normal(0, 1, (n, kv, bs, hd)).astype(np.float32))
    vp = jnp.asarray(rng.normal(0, 1, (n, kv, bs, hd)).astype(np.float32))
    return kp, vp


def _gather(pool, table, t):
    """Host-side oracle gather: pages -> contiguous (t, KV, hd)."""
    pages = np.concatenate(
        [np.asarray(pool)[p].transpose(1, 0, 2) for p in table], 0)
    return pages[:t]


@pytest.mark.parametrize("ctx", [5, 11, 16])
def test_paged_decode_matches_gathered_ref(rng, ctx):
    """flash_e2softmax_paged_decode == gather + two-pass ref (exact)."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_paged_decode)
    n, bs, kv, hd, h, b = 12, 4, 2, 16, 4, 3
    kp, vp = _page_pool(rng, n, bs, kv, hd)
    tables = np.array([[3, 1, 6, 2], [5, 2, 7, 9], [10, 4, 8, 11]], np.int32)
    ctxs = np.minimum(np.array([ctx, ctx + 1, ctx - 1]), bs * 4)
    q = jnp.asarray(rng.normal(0, 1, (b, h, hd)).astype(np.float32))
    out = flash_e2softmax_paged_decode(q, kp, vp, jnp.asarray(tables),
                                       jnp.asarray(ctxs), sole=False)
    for i in range(b):
        kk = _gather(kp, tables[i], ctxs[i])
        vv = _gather(vp, tables[i], ctxs[i])
        for hh in range(h):
            g = h // kv
            ref = K.flash_e2softmax_ref(
                np.asarray(q)[i, hh][None, None], kk[None, :, hh // g],
                vv[None, :, hh // g], causal=False, sole=False)
            np.testing.assert_allclose(np.asarray(out)[i, hh],
                                       np.asarray(ref)[0, 0],
                                       rtol=1e-5, atol=1e-5)


def test_paged_decode_sole_single_page_bit_exact(rng):
    """Context inside one page: the online paged pipeline reduces to the
    two-pass E2Softmax reference exactly."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_paged_decode)
    n, bs, kv, hd, h = 8, 16, 2, 16, 4
    kp, vp = _page_pool(rng, n, bs, kv, hd)
    tables = np.array([[3, 0], [5, 0]], np.int32)
    ctxs = np.array([9, 14], np.int32)
    q = jnp.asarray(rng.normal(0, 1, (2, h, hd)).astype(np.float32))
    out = flash_e2softmax_paged_decode(q, kp, vp, jnp.asarray(tables),
                                       jnp.asarray(ctxs), sole=True)
    for i in range(2):
        kk = _gather(kp, tables[i], ctxs[i])
        vv = _gather(vp, tables[i], ctxs[i])
        for hh in range(h):
            g = h // kv
            ref = K.flash_e2softmax_ref(
                np.asarray(q)[i, hh][None, None], kk[None, :, hh // g],
                vv[None, :, hh // g], causal=False, sole=True)
            np.testing.assert_array_equal(np.asarray(out)[i, hh],
                                          np.asarray(ref)[0, 0])


def test_paged_prefill_chunk_matches_gathered_ref(rng):
    """Causal chunk attention through page tables == contiguous ref with
    the chunk's rows offset by q_start (exact mode)."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_paged)
    n, bs, kv, hd, h, c, q0 = 12, 4, 2, 16, 4, 8, 6
    kp, vp = _page_pool(rng, n, bs, kv, hd)
    table = np.array([[3, 1, 6, 2]], np.int32)
    kv_len = q0 + c
    q = jnp.asarray(rng.normal(0, 1, (1, h, c, hd)).astype(np.float32))
    meta = jnp.asarray(np.array([[q0, kv_len]], np.int32))
    out = flash_e2softmax_paged(q, kp, vp, jnp.asarray(table), meta,
                                causal=True, sole=False)
    kk = _gather(kp, table[0], kv_len)
    vv = _gather(vp, table[0], kv_len)
    for hh in range(h):
        g = h // kv
        # full causal attention over kv_len rows; our chunk is the last c.
        qq = np.zeros((kv_len, hd), np.float32)
        qq[q0:] = np.asarray(q)[0, hh]
        ref = K.flash_e2softmax_ref(qq[None], kk[None, :, hh // g],
                                    vv[None, :, hh // g],
                                    causal=True, sole=False)
        np.testing.assert_allclose(np.asarray(out)[0, hh],
                                   np.asarray(ref)[0, q0:],
                                   rtol=1e-5, atol=1e-5)


def test_paged_int8_pool_dequant(rng):
    """int8 page pools dequantize inside the kernel via kv_scale."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_paged_decode)
    from repro.models.layers import KV_INT8_SCALE
    n, bs, kv, hd, h = 8, 8, 2, 16, 4
    kp, vp = _page_pool(rng, n, bs, kv, hd)
    kq = jnp.clip(jnp.round(kp / KV_INT8_SCALE), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vp / KV_INT8_SCALE), -127, 127).astype(jnp.int8)
    tables = np.array([[3, 1]], np.int32)
    ctxs = np.array([13], np.int32)
    q = jnp.asarray(rng.normal(0, 1, (1, h, hd)).astype(np.float32))
    out_q = flash_e2softmax_paged_decode(
        q, kq, vq, jnp.asarray(tables), jnp.asarray(ctxs), sole=False,
        kv_scale=KV_INT8_SCALE)
    out_f = flash_e2softmax_paged_decode(
        q, kq.astype(jnp.float32) * KV_INT8_SCALE,
        vq.astype(jnp.float32) * KV_INT8_SCALE,
        jnp.asarray(tables), jnp.asarray(ctxs), sole=False)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_f),
                               rtol=1e-6, atol=1e-6)


# Folded paged kernel: one grid step holds every head of a lane (or a
# VMEM-sized block of them) and one page of every KV head.
# Lanes: ends mid-page, null (kv_len 0), whole pages, near the table end.
FOLD_LENS = (37, 0, 32, 59)
# a tensor-parallel shard's 12 q heads of 24 (8 a group) over a replicated
# 3-head pool: the map starts mid-group and spans two KV heads.
MID_GROUP_MAP = tuple((12 + i) // 8 for i in range(12))


def _folded_inputs(rng, h, kvh, d, c, *, int8=False, bs=16, nb=4):
    b = len(FOLD_LENS)
    n = 1 + b * nb
    kp, vp = _page_pool(rng, n, bs, kvh, d)
    if int8:
        from repro.models.layers import KV_INT8_SCALE
        kp, vp = (jnp.clip(jnp.round(x / KV_INT8_SCALE), -127,
                           127).astype(jnp.int8) for x in (kp, vp))
    pages = rng.permutation(np.arange(1, n))
    tables = np.zeros((b, nb), np.int32)
    for i, kv_len in enumerate(FOLD_LENS):
        live = -(-kv_len // bs)
        tables[i, :live] = pages[i * nb:i * nb + live]
    lens = np.asarray(FOLD_LENS, np.int32)
    meta = np.stack([np.maximum(lens - c, 0), lens], 1).astype(np.int32)
    q = jnp.asarray(rng.normal(0, 1, (b, h, c, d)).astype(np.float32))
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(meta)


@pytest.mark.parametrize("h,kvh,d,c,kvmap,pool", [
    (32, 32, 64, 1, None, "f32"),
    (32, 32, 64, 16, None, "f32"),
    (14, 2, 64, 1, None, "f32"),
    (14, 2, 64, 16, None, "f32"),
    (8, 2, 128, 1, None, "f32"),
    (8, 2, 128, 16, None, "f32"),
    (12, 3, 64, 1, MID_GROUP_MAP, "f32"),
    (12, 3, 64, 16, MID_GROUP_MAP, "f32"),
    (14, 2, 64, 1, None, "int8"),
    (14, 2, 64, 16, None, "int8"),
    (14, 2, 64, 1, None, "int8_pv"),
], ids=["mha-decode", "mha-c16", "gqa14-decode", "gqa14-c16",
        "gqa8-d128-decode", "gqa8-d128-c16", "map-mid-group-decode",
        "map-mid-group-c16", "int8-decode", "int8-c16", "int8-quant-pv"])
def test_paged_folded_matches_gathered_ref(rng, h, kvh, d, c, kvmap, pool):
    """Head-folded paged kernel == gathered two-pass reference (exact
    mode) per head, for MHA, GQA, an explicit head map that starts mid
    group (the tensor-parallel replicated-KV fallback), decode and a
    causal chunk, f32 and int8 pools; null lanes give zeros."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_paged)
    from repro.models.layers import KV_INT8_SCALE
    from repro.ops import registry
    q, kp, vp, tables, meta = _folded_inputs(rng, h, kvh, d, c,
                                             int8=pool != "f32")
    kw = dict(causal=c > 1)
    if pool != "f32":
        kw.update(kv_scale=KV_INT8_SCALE, quant_pv=pool == "int8_pv")
    if kvmap is not None:
        kw["kv_head_map"] = jnp.asarray(kvmap, jnp.int32)
    out = np.asarray(flash_e2softmax_paged(q, kp, vp, tables, meta,
                                           sole=False, **kw))
    ref = registry.resolve("paged_attention", "exact", "reference")(
        jnp.moveaxis(q, 1, 2), kp, vp, tables, meta[:, 0], meta[:, 1], **kw)
    ref = np.moveaxis(np.asarray(ref), 1, 2)
    live = np.asarray(FOLD_LENS) > 0
    np.testing.assert_allclose(out[live], ref[live], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(out[~live], 0.0)


@pytest.mark.parametrize("h,kvh,c", [(8, 2, 1), (8, 2, 16), (6, 6, 1)],
                         ids=["gqa-decode", "gqa-c16", "mha-decode"])
def test_paged_folded_heads_never_mix(rng, h, kvh, c):
    """An H-head call is bit-identical to H single-head calls stacked,
    each against its own KV head: folding changes which grid step does
    the work, not the arithmetic (SOLE mode, quantized correction)."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        flash_e2softmax_paged)
    q, kp, vp, tables, meta = _folded_inputs(rng, h, kvh, 16, c)
    kw = dict(causal=c > 1, sole=True)
    out = np.asarray(flash_e2softmax_paged(q, kp, vp, tables, meta, **kw))
    g = h // kvh
    one = np.concatenate([np.asarray(flash_e2softmax_paged(
        q[:, i:i + 1], kp[:, i // g:i // g + 1], vp[:, i // g:i // g + 1],
        tables, meta, **kw)) for i in range(h)], 1)
    np.testing.assert_array_equal(out, one)


@pytest.mark.parametrize("h,kvh,hb,kvmap", [
    (8, 2, 4, None), (4, 1, 2, None), (6, 6, 2, None),
    (12, 3, 4, MID_GROUP_MAP)],
    ids=["whole-groups", "part-group", "mha", "map-mid-group"])
def test_paged_split_heads_match_folded(rng, monkeypatch, h, kvh, hb, kvmap):
    """When VMEM forces a split, grid axis 1 walks blocks of ``hb`` heads
    (whole GQA groups, part of one group, single MHA heads, or a map that
    starts mid-group): each head reads its own KV head of the page, and
    the result is bit-identical to the all-heads step."""
    from repro.kernels import flash_e2softmax as fe  # repro: noqa RPR001
    q, kp, vp, tables, meta = _folded_inputs(rng, h, kvh, 16, 16)
    kw = dict(causal=True, sole=True)
    if kvmap is not None:
        kw["kv_head_map"] = jnp.asarray(kvmap, jnp.int32)
    paged = fe.flash_e2softmax_paged.__wrapped__     # untraced: no jit cache
    folded = np.asarray(paged(q, kp, vp, tables, meta, **kw))
    monkeypatch.setattr(fe, "PAGED_VMEM_BUDGET", fe._step_vmem(
        hb, 16, kvh, 16, 16, q.dtype, kp.dtype))
    assert fe.paged_head_block(h, kvh, 16, 16, 16, q.dtype, kp.dtype) == hb
    split = np.asarray(paged(q, kp, vp, tables, meta, **kw))
    np.testing.assert_array_equal(split, folded)


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_paged_head_block_folds_every_head_at_decode(name):
    """At decode (C=1) every shipped config's heads fold into one grid
    step, for bf16 and int8 pools."""
    from repro.kernels.flash_e2softmax import (  # repro: noqa RPR001
        paged_head_block)
    cfg = get_config(name)
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    for q_dtype in (jnp.bfloat16, jnp.float32):
        for kv_dtype in (q_dtype, jnp.int8):
            assert paged_head_block(h, kvh, 1, d, 16, q_dtype,
                                    kv_dtype) == h


@pytest.mark.parametrize("h,kvh,d,c", [
    (48, 8, 128, 256), (32, 8, 128, 256), (32, 32, 64, 256),
    (14, 2, 64, 256), (16, 1, 256, 256), (14, 2, 64, 1024)])
def test_paged_head_block_splits_within_budget(h, kvh, d, c):
    """Long chunks split the heads: the largest divisor of H whose blocks
    and scratch, beside a page of every KV head, stay within the VMEM
    budget. Minitron's published 48/8 heads at a 256-row chunk must
    split."""
    from repro.kernels import flash_e2softmax as fe  # repro: noqa RPR001

    def vmem(n):
        return fe._step_vmem(n, c, kvh, 16, d, jnp.bfloat16, jnp.bfloat16)
    hb = fe.paged_head_block(h, kvh, c, d, 16, jnp.bfloat16, jnp.bfloat16)
    assert h % hb == 0 and vmem(hb) <= fe.PAGED_VMEM_BUDGET
    if hb < h:      # the next larger divisor of H would not fit
        bigger = next(n for n in range(hb + 1, h + 1) if h % n == 0)
        assert vmem(bigger) > fe.PAGED_VMEM_BUDGET
    if (h, kvh, c) == (48, 8, 256):
        assert hb < h


def test_flash_exact_corr_beyond_paper(rng):
    """exact_corr (fp32 rescale) should not be worse than quantized corr."""
    B, S, H, hd = 2, 128, 2, 32
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, S, H, hd)).astype(np.float32))
               for _ in range(3))
    exact = flash_attention_op(q, k, v, causal=True, sole=False, block=128)
    a = flash_attention_op(q, k, v, causal=True, sole=True, block=32)
    b = flash_attention_op(q, k, v, causal=True, sole=True, block=32,
                           exact_corr=True)
    err_a = float(jnp.mean(jnp.abs(a - exact)))
    err_b = float(jnp.mean(jnp.abs(b - exact)))
    assert err_b <= err_a * 1.05
