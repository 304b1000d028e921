"""repro.ops registry: resolution matrix + reference↔pallas parity.

Parity runs on deliberately ragged shapes — rows not a multiple of
``block_rows``, odd channel counts — so the row-padding and masking
paths of every kernel are exercised, not just the aligned fast path.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.configs.base import get_config
from repro.core.sole.quant import calibrate_ptf

RAGGED_SHAPES = [(7, 257), (3, 5, 130), (1, 999)]


# -- registry resolution ------------------------------------------------------


def test_every_combination_resolves_or_raises_cleanly():
    """(op, mode, backend) either yields a callable or raises the two
    documented error types — never an unrelated exception."""
    resolved = 0
    for op in ops.OPS:
        for mode in ops.MODES_BY_OP[op]:
            for backend in ops.BACKENDS:
                try:
                    fn = ops.resolve(op, mode, backend)
                except NotImplementedError:
                    continue
                assert callable(fn), (op, mode, backend)
                resolved += 1
    assert resolved >= 20  # every reference op + the sole/exact kernels


def test_reference_backend_is_total():
    """Every (op, mode) has a reference implementation."""
    for op in ops.OPS:
        for mode in ops.MODES_BY_OP[op]:
            assert ops.is_registered(op, mode, "reference"), (op, mode)


def test_unknown_names_raise_value_error():
    with pytest.raises(ValueError, match="unknown op"):
        ops.resolve("conv", "exact", "reference")
    with pytest.raises(ValueError, match="unknown mode"):
        ops.resolve("softmax", "banana", "reference")
    with pytest.raises(ValueError, match="unknown backend"):
        ops.resolve("softmax", "exact", "cuda")


def test_backend_for_falls_back_to_reference():
    """Only ``auto`` falls back: it keeps the mode and picks the
    reference engine for a combination with no kernel. A config that
    forces pallas for such a combination raises instead."""
    auto = get_config("qwen2_0_5b").smoke()
    assert ops.backend_for(auto, "softmax", "ibert") == "reference"
    assert ops.backend_for(auto, "layernorm", "exact") == "reference"
    cfg = dataclasses.replace(auto, ops_backend="pallas")
    assert ops.backend_for(cfg, "softmax", "sole") == "pallas"
    for op, mode in (("softmax", "ibert"), ("layernorm", "exact")):
        with pytest.raises(NotImplementedError, match="no 'pallas' backend"):
            ops.backend_for(cfg, op, mode)
    # explicit argument beats the config
    assert ops.backend_for(cfg, "softmax", "sole", "reference") == "reference"


def test_explicit_backend_is_strict():
    """An explicit backend= demand is never silently downgraded: a
    combination without that engine raises instead."""
    assert ops.backend_for(None, "softmax", "sole", "pallas") == "pallas"
    with pytest.raises(NotImplementedError, match="no 'pallas' backend"):
        ops.backend_for(None, "softmax", "ibert", "pallas")
    with pytest.raises(NotImplementedError, match="no 'pallas' backend"):
        ops.softmax_fn("ibert", backend="pallas")


def test_config_backend_default_is_auto():
    cfg = get_config("qwen2_0_5b")
    assert cfg.ops_backend == "auto"


# -- softmax parity -----------------------------------------------------------


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
@pytest.mark.parametrize("exp_bits", [4, 6])
def test_e2softmax_backends_agree(rng, shape, exp_bits):
    x = jnp.asarray(rng.normal(0, 3, shape).astype(np.float32))
    ref = ops.softmax_fn("sole", backend="reference")(x, exp_bits=exp_bits)
    pal = ops.softmax_fn("sole", backend="pallas")(x, exp_bits=exp_bits)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
def test_e2softmax_backends_agree_masked(rng, shape):
    """Masked entries contribute exactly zero in both backends."""
    x = jnp.asarray(rng.normal(0, 3, shape).astype(np.float32))
    mask = jnp.asarray(rng.random(shape) > 0.3)
    ref = ops.softmax_fn("sole", backend="reference")(x, mask=mask)
    pal = ops.softmax_fn("sole", backend="pallas")(x, mask=mask)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)
    assert float(jnp.max(jnp.abs(jnp.where(mask, 0.0, pal)))) == 0.0


# -- layernorm / rmsnorm parity ----------------------------------------------


@pytest.mark.parametrize("shape", [(7, 257), (2, 9, 130), (5, 999)])
def test_ailayernorm_backends_agree_fp32_activations(rng, shape):
    """The pallas wrapper is call-compatible with layernorm_fn('sole'):
    fp32 activations in, PTF centering inside."""
    c = shape[-1]
    x = jnp.asarray(rng.normal(0.5, 2, shape).astype(np.float32))
    g = jnp.asarray(rng.normal(1, 0.1, c).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.1, c).astype(np.float32))
    p = calibrate_ptf(x, unsigned=True)
    ref = ops.layernorm_fn("sole", backend="reference")(x, g, b, params=p)
    pal = ops.layernorm_fn("sole", backend="pallas")(x, g, b, params=p)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(7, 257), (3, 11, 66)])
def test_airmsnorm_backends_agree(rng, shape):
    c = shape[-1]
    x = jnp.asarray(rng.normal(0, 2, shape).astype(np.float32))
    g = jnp.asarray(rng.normal(1, 0.1, c).astype(np.float32))
    ref = ops.rmsnorm_fn("sole", backend="reference")(x, g)
    pal = ops.rmsnorm_fn("sole", backend="pallas")(x, g)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# -- fused residual + norm parity --------------------------------------------


@pytest.mark.parametrize("kind", ["layernorm", "rmsnorm"])
@pytest.mark.parametrize("shape", [(7, 257), (2, 9, 130), (1, 300, 66)])
def test_fused_add_norm_matches_unfused_reference(rng, kind, shape):
    """SOLE-mode fused add+norm == the unfused three-op reference path
    to fp32 tolerance (acceptance criterion)."""
    c = shape[-1]
    x = jnp.asarray(rng.normal(0.2, 1.5, shape).astype(np.float32))
    r = jnp.asarray(rng.normal(0, 1, shape).astype(np.float32))
    g = jnp.asarray(rng.normal(1, 0.1, c).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.1, c).astype(np.float32))
    args = (x, r, g) if kind == "rmsnorm" else (x, r, g, b)
    s_ref, o_ref = ops.residual_norm_fn(kind, "sole",
                                        backend="reference")(*args)
    s_pal, o_pal = ops.residual_norm_fn(kind, "sole",
                                        backend="pallas")(*args)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mode", ["exact", "sole", "ibert"])
def test_residual_norm_reference_equals_manual_composition(rng, mode):
    x = jnp.asarray(rng.normal(0, 1, (5, 130)).astype(np.float32))
    r = jnp.asarray(rng.normal(0, 1, (5, 130)).astype(np.float32))
    g = jnp.ones(130)
    b = jnp.zeros(130)
    s, out = ops.residual_norm_fn("layernorm", mode,
                                  backend="reference")(x, r, g, b)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(x + r))
    manual = ops.layernorm_fn(mode, backend="reference")(x + r, g, b)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(manual))


# -- attention parity ---------------------------------------------------------


@pytest.mark.parametrize("mode", ["exact", "sole"])
def test_flash_attention_backends_agree_ragged(rng, mode):
    """Ragged S (not a multiple of the block) through the registry."""
    B, S, H, hd = 2, 57, 2, 16
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, S, H, hd))
                           .astype(np.float32)) for _ in range(3))
    ref = ops.flash_attention_fn(mode, backend="reference")(
        q, k, v, causal=True)
    pal = ops.flash_attention_fn(mode, backend="pallas")(
        q, k, v, causal=True, block=64)
    # one padded block -> the online pipeline reduces to the two-pass ref
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["exact", "sole"])
def test_paged_attention_backends_agree(rng, mode):
    n, bs, kv, hd, h, b, c = 12, 4, 2, 16, 4, 2, 1
    kp = jnp.asarray(rng.normal(0, 1, (n, kv, bs, hd)).astype(np.float32))
    vp = jnp.asarray(rng.normal(0, 1, (n, kv, bs, hd)).astype(np.float32))
    tables = jnp.asarray(np.array([[3, 1, 6, 2], [5, 2, 7, 9]], np.int32))
    q = jnp.asarray(rng.normal(0, 1, (b, c, h, hd)).astype(np.float32))
    q_start = jnp.asarray([9, 12], jnp.int32)
    kv_len = q_start + c
    ref = ops.paged_attention_fn(mode, backend="reference")(
        q, kp, vp, tables, q_start, kv_len, causal=True)
    pal = ops.paged_attention_fn(mode, backend="pallas")(
        q, kp, vp, tables, q_start, kv_len, causal=True)
    if mode == "exact":
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    else:
        # the online quantized Correction deviates elementwise across
        # page boundaries (paper Alg. 1); the mean stays tight.
        assert float(jnp.mean(jnp.abs(pal - ref))) < 0.02


# -- model-level integration --------------------------------------------------


def test_model_forward_agrees_across_backends(rng):
    """A smoke transformer forward pass produces (near-)identical logits
    with ops_backend=reference and ops_backend=pallas, SOLE mode."""
    import jax

    from repro.models import api
    cfg = get_config("qwen2_0_5b").smoke()
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 24))
                         .astype(np.int32))
    outs = {}
    for backend in ("reference", "pallas"):
        c = dataclasses.replace(cfg, ops_backend=backend)
        outs[backend] = api.forward(params, {"tokens": tokens}, c, "serve")
    np.testing.assert_allclose(np.asarray(outs["pallas"]),
                               np.asarray(outs["reference"]),
                               rtol=1e-4, atol=1e-4)
