"""W8A8 serving pipeline: registry cells for the quantized matmul and
quant-out norm ops, quantize/dequantize round-trip bounds, fused
PTF-codes-out parity, reference↔pallas w8a8 bit-identity, and
serve-level stability — decode horizons, speculative decoding, a 1x2
mesh, the ``--quantize off`` bit-for-bit pin, and the dense engine's
left-pad masking regression.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs.base import QuantConfig, get_config
from repro.core.sole.quant import (dequantize_weight, is_qtensor,
                                   quantize_act, quantize_weight)
from repro.models import api
from repro.serve.engine import Engine, PagedEngine, Request
from repro.sharding import rules as R


@pytest.fixture(scope="module")
def lm():
    cfg = get_config("qwen2_0_5b").smoke()
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    exact = dataclasses.replace(cfg, softmax_mode="exact",
                                norm_mode="exact", logit_int8=False)
    return cfg, exact, params


def _q8(cfg):
    return dataclasses.replace(cfg, quant=QuantConfig(mode="w8a8"))


def _mixed_requests(cfg, n, rng, new=8):
    """Deliberately mixed prompt lengths: the dense engine left-pads
    these into one batch, exercising the per-lane pad masking."""
    lens = (9, 14, 11, 16)
    return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        size=lens[i % len(lens)])
                    .astype(np.int32), max_new_tokens=new)
            for i in range(n)]


def _paged(cfg, params, **kw):
    kw.setdefault("num_blocks", 40)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("max_running", 4)
    kw.setdefault("decode_batch", 4)
    return PagedEngine(cfg, params, **kw)


# -- registry cells -----------------------------------------------------------


def test_matmul_cells_resolve_or_raise():
    """Every matmul mode has a reference impl; the pallas backend only
    carries the int8 kernel (exact/w8a16 demand a clean raise)."""
    for mode in ops.MATMUL_MODES:
        assert callable(ops.resolve("matmul", mode, "reference"))
    assert callable(ops.resolve("matmul", "w8a8", "pallas"))
    for mode in ("exact", "w8a16"):
        with pytest.raises(NotImplementedError):
            ops.resolve("matmul", mode, "pallas")


def test_residual_norm_q_cells():
    """The quant-out residual-norm twins cover every norm mode on
    reference; pallas fuses the SOLE cell only. ``auto`` falls back to
    reference for the rest instead of changing the mode; a config that
    forces pallas there raises."""
    for kind in ("layernorm", "rmsnorm"):
        for mode in ops.NORM_MODES:
            assert ops.is_registered(f"residual_{kind}_q", mode,
                                     "reference")
        assert ops.is_registered(f"residual_{kind}_q", "sole", "pallas")
        assert callable(ops.residual_norm_q_fn(kind, "exact"))
    cfg = dataclasses.replace(get_config("qwen2_0_5b").smoke(),
                              ops_backend="pallas")
    assert ops.backend_for(cfg, "residual_layernorm_q", "sole") == "pallas"
    with pytest.raises(NotImplementedError, match="no 'pallas' backend"):
        ops.backend_for(cfg, "residual_layernorm_q", "exact")
    auto = dataclasses.replace(cfg, ops_backend="auto")
    assert ops.backend_for(auto, "residual_layernorm_q", "exact") \
        == "reference"


# -- quantize / dequantize round trips ----------------------------------------


@pytest.mark.parametrize("shape,nc", [((64, 33), 1), ((4, 16, 24), 1),
                                      ((3, 7, 5, 11), 2)])
def test_weight_round_trip_bound(rng, shape, nc):
    """Per-channel symmetric int8: round-trip error <= half a step of
    each output channel's scale."""
    w = jnp.asarray(rng.normal(0, 0.1, shape).astype(np.float32))
    qw = quantize_weight(w, n_contract=nc)
    assert is_qtensor(qw) and qw["q"].dtype == jnp.int8
    err = np.abs(np.asarray(dequantize_weight(qw) - w))
    amax = np.max(np.abs(np.asarray(w)), axis=tuple(range(nc)),
                  keepdims=True)
    assert np.all(err <= amax / 127 * 0.5 + 1e-7)


def test_act_round_trip_bound(rng):
    x = jnp.asarray(rng.normal(0, 2, (5, 37)).astype(np.float32))
    q, s = quantize_act(x)
    assert q.dtype == jnp.int8 and s.shape == (5, 1)
    err = np.abs(np.asarray(q.astype(jnp.float32) * s - x))
    assert np.all(err <= np.asarray(s) / 2 + 1e-7)


def test_quantize_params_covers_projections_and_is_idempotent(lm):
    cfg, _, params = lm
    qp = R.quantize_params(params)
    attn = qp["layers"]["attn"]
    for name in ("wq", "wk", "wv", "wo"):
        assert is_qtensor(attn[name]), name
    for name in ("gate", "up", "down"):
        if name in qp["layers"]["mlp"]:
            assert is_qtensor(qp["layers"]["mlp"][name]), name
    # the embedding table stays fp32 (tied LM head reads it densely)
    assert not is_qtensor(qp["embed"])
    qp2 = R.quantize_params(qp)
    same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)), qp, qp2)
    assert all(jax.tree.leaves(same))
    assert R.param_bytes(qp) < 0.55 * R.param_bytes(params)


# -- fused residual + norm + quantize-out -------------------------------------


@pytest.mark.parametrize("kind", ["layernorm", "rmsnorm"])
@pytest.mark.parametrize("mode", ["exact", "sole", "ibert"])
def test_reference_quant_out_is_norm_then_quantize_bitwise(rng, kind,
                                                           mode):
    """The reference quant-out twin must be *bitwise* the two-step
    composition — so feeding codes forward is exactly on-the-fly
    activation quantization, never a numerics fork."""
    c = 130
    x = jnp.asarray(rng.normal(0.2, 1.5, (7, c)).astype(np.float32))
    r = jnp.asarray(rng.normal(0, 1, (7, c)).astype(np.float32))
    g = jnp.asarray(rng.normal(1, 0.1, c).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.1, c).astype(np.float32))
    args = (x, r, g) if kind == "rmsnorm" else (x, r, g, b)
    s, (qo, so) = ops.residual_norm_q_fn(kind, mode,
                                         backend="reference")(*args)
    s2, out = ops.residual_norm_fn(kind, mode, backend="reference")(*args)
    q2, so2 = quantize_act(jnp.asarray(out, jnp.float32))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(qo), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(so), np.asarray(so2))


@pytest.mark.parametrize("kind", ["layernorm", "rmsnorm"])
@pytest.mark.parametrize("shape", [(7, 257), (2, 9, 130)])
def test_pallas_quant_out_codes_match_reference(rng, kind, shape):
    """SOLE fused quant-out kernel: int8 codes bitwise identical to the
    reference twin; the per-row scale may differ by float-fusion ulps
    (same bound the serve path tolerates)."""
    c = shape[-1]
    x = jnp.asarray(rng.normal(0.2, 1.5, shape).astype(np.float32))
    r = jnp.asarray(rng.normal(0, 1, shape).astype(np.float32))
    g = jnp.asarray(rng.normal(1, 0.1, c).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.1, c).astype(np.float32))
    args = (x, r, g) if kind == "rmsnorm" else (x, r, g, b)
    s_ref, (q_ref, sc_ref) = ops.residual_norm_q_fn(
        kind, "sole", backend="reference")(*args)
    s_pal, (q_pal, sc_pal) = ops.residual_norm_q_fn(
        kind, "sole", backend="pallas")(*args)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(q_pal), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(sc_pal), np.asarray(sc_ref),
                               rtol=1e-6)


# -- w8a8 matmul backend parity -----------------------------------------------


@pytest.mark.parametrize("mkn", [(7, 130, 33), (64, 256, 128)])
def test_w8a8_matmul_backends_bit_identical(rng, mkn):
    """Reference and pallas share the exact int32 accumulation and the
    same scale-application order, so they must agree bit for bit —
    including ragged shapes that force the kernel's padded blocks."""
    m, kd, n = mkn
    qa = quantize_act(jnp.asarray(rng.normal(0, 1.5, (m, kd))
                                  .astype(np.float32)))
    qw = quantize_weight(jnp.asarray(rng.normal(0, 0.05, (kd, n))
                                     .astype(np.float32)))
    ref = ops.matmul_fn("w8a8", backend="reference")(qa, qw)
    pal = ops.matmul_fn("w8a8", backend="pallas")(qa, qw)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))


def test_w8a8_matmul_n_contract_2(rng):
    """The wo-projection shape: (B,S,H,hd) x (H,hd,D), contracting the
    two leading weight axes."""
    x = jnp.asarray(rng.normal(0, 1, (3, 5, 4, 6)).astype(np.float32))
    w = jnp.asarray(rng.normal(0, 0.1, (4, 6, 8)).astype(np.float32))
    qa = quantize_act(x, 2)
    qw = quantize_weight(w, n_contract=2)
    ref = ops.matmul_fn("w8a8", backend="reference")(qa, qw, n_contract=2)
    pal = ops.matmul_fn("w8a8", backend="pallas")(qa, qw, n_contract=2)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))
    # int8 matmul approximates the fp product to quantization error
    dense = jnp.tensordot(x, w, 2)
    assert float(jnp.max(jnp.abs(ref - dense))) < 0.1


def test_w8a16_matmul_matches_dequantized_dense(rng):
    x = jnp.asarray(rng.normal(0, 1, (5, 33)).astype(np.float32))
    qw = quantize_weight(jnp.asarray(rng.normal(0, 0.1, (33, 17))
                                     .astype(np.float32)))
    out = ops.matmul_fn("w8a16", backend="reference")(x, qw)
    want = x @ dequantize_weight(qw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# -- serve-level stability ----------------------------------------------------


def test_w8a8_serves_sole_mode_end_to_end(lm, rng):
    """The full SOLE + w8a8 stack (PTF codes out of AILayerNorm, log2
    probs against int8 KV pages) produces valid tokens on both
    engines."""
    cfg, _, params = lm
    reqs = _mixed_requests(cfg, 4, rng, new=6)
    for eng in (_paged(_q8(cfg), params), Engine(_q8(cfg), params,
                                                 batch_size=4,
                                                 max_len=32)):
        outs = eng.generate(reqs)
        assert len(outs) == 4
        assert all(len(o) == 6 for o in outs)
        assert all(0 <= t < cfg.padded_vocab for o in outs for t in o)


def test_w8a8_exact_outputs_horizon_invariant(lm, rng):
    """Exact int32 accumulation + per-row act scales make w8a8 decode
    invariant to the fused-dispatch width, like fp32 exact mode."""
    _, exact, params = lm
    reqs = _mixed_requests(exact, 4, rng)
    h1 = _paged(_q8(exact), params, decode_horizon=1).generate(reqs)
    h8 = _paged(_q8(exact), params, decode_horizon=8).generate(reqs)
    assert h1 == h8


def test_w8a8_spec_decode_outputs_identical(lm, rng):
    """Speculative decoding through the quantized verify path keeps the
    accept-prefix contract: output streams bitwise the plain run's."""
    from repro.serve.spec import DraftModelDrafter, SpecConfig
    _, exact, params = lm
    q8 = _q8(exact)
    reqs = _mixed_requests(exact, 4, rng)
    plain = _paged(q8, params).generate(reqs)
    spec = _paged(q8, params,
                  spec_config=SpecConfig(DraftModelDrafter(q8, params),
                                         max_k=4)).generate(reqs)
    assert spec == plain


def test_w8a8_mesh_1x2_matches_single_device():
    """w8a8 under tensor parallelism: per-channel weight scales shard
    with their channels and the int32 accumulation stays exact, so a
    1x2 mesh reproduces single-device outputs bit for bit."""
    from tests._mesh_helpers import run_with_devices
    code = """
import dataclasses
import numpy as np
import jax
from repro.configs.base import QuantConfig, get_config
from repro.launch.mesh import make_mesh, make_rules
from repro.models import api
from repro.serve.engine import PagedEngine, Request

cfg = dataclasses.replace(get_config("qwen2_0_5b").smoke(),
                          softmax_mode="exact", norm_mode="exact",
                          logit_int8=False,
                          quant=QuantConfig(mode="w8a8"))
params, axes = api.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, size=9 + 3 * i)
                .astype(np.int32), max_new_tokens=8) for i in range(3)]

def outs(rules, pa):
    eng = PagedEngine(cfg, params, num_blocks=40, block_size=8,
                      max_seq_len=64, max_running=4, decode_batch=4,
                      rules=rules, param_axes=pa)
    return eng.generate(reqs)

single = outs(None, None)
rules = make_rules(make_mesh((1, 2), ("data", "model")))
sharded = outs(rules, axes)
assert sharded == single, (single, sharded)
print("W8A8-MESH-OK")
"""
    assert "W8A8-MESH-OK" in run_with_devices(code, n_devices=2)


def test_quantize_off_is_bit_for_bit_fp_serving(lm, rng):
    """--quantize off is the default QuantConfig: engines must leave the
    param tree untouched (no int8 leaves) and produce outputs identical
    to a config that never mentions quantization."""
    _, exact, params = lm
    reqs = _mixed_requests(exact, 4, rng)
    off = dataclasses.replace(exact, quant=QuantConfig(mode="off"))
    assert off.quant == exact.quant  # off IS the default config
    eng_off = _paged(off, params)
    eng_def = _paged(exact, params)
    leaves = jax.tree.leaves(eng_off.params,
                             is_leaf=lambda x: is_qtensor(x))
    assert not any(is_qtensor(x) for x in leaves)
    assert not any(l.dtype == jnp.int8 for l in jax.tree.leaves(
        eng_off.params))
    assert eng_off.generate(reqs) == eng_def.generate(reqs)


# -- dense engine left-pad masking (regression) -------------------------------


def test_dense_mixed_length_batch_matches_solo(lm, rng):
    """Regression: the dense engine left-pads mixed-length batches; pad
    columns must be masked out of attention and positions must be
    per-lane logical, so a short prompt batched with longer ones
    matches its solo output exactly (exact mode = path-invariant)."""
    _, exact, params = lm
    eng = Engine(exact, params, batch_size=4, max_len=32)
    reqs = _mixed_requests(exact, 4, rng)
    batched = eng.generate(reqs)
    for r, out in zip(reqs, batched):
        assert eng.generate([r])[0] == out


@pytest.mark.parametrize("mode", ["off", "w8a8"])
def test_dense_matches_paged_on_mixed_lengths(lm, rng, mode):
    """Exact-mode dense==paged parity on a mixed-length batch — the
    claim the pre-fix engine could only make for equal-length prompts —
    in fp32 and through the quantized dataflow."""
    _, exact, params = lm
    cfg = exact if mode == "off" else _q8(exact)
    reqs = _mixed_requests(cfg, 4, rng)
    dense = Engine(cfg, params, batch_size=4, max_len=32).generate(reqs)
    paged = _paged(cfg, params).generate(reqs)
    assert dense == paged
