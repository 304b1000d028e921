"""Tests for the §Perf optimization features (EXPERIMENTS.md):
dot-native decode caches, int8 KV, SWA-aware blocked attention,
chunked WKV, FSDP param specs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.models import api


def _decode_parity(cfg, tol):
    m = api.get_model(cfg)
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    b, s, extra = 2, 16, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s + extra), 0,
                              cfg.vocab_size)
    fw = m.forward(params, toks, cfg, "serve")
    full = fw[0] if isinstance(fw, tuple) else fw
    logits_p, cache = m.prefill(params, toks[:, :s], cfg, s + extra)
    errs = [float(jnp.max(jnp.abs(logits_p[:, 0] - full[:, s - 1])))]
    for i in range(extra):
        lg, cache = m.decode_step(params, cache, toks[:, s + i],
                                  jnp.asarray(s + i, jnp.int32), cfg)
        errs.append(float(jnp.max(jnp.abs(lg - full[:, s + i]))))
    return max(errs)


def test_int8_kv_cache_decode_close():
    """int8 KV decode stays close to the full-precision path (the cache
    quantization is the only difference)."""
    cfg = dataclasses.replace(
        get_config("qwen2_0_5b").smoke(), softmax_mode="exact",
        norm_mode="exact", logit_int8=False, kv_cache_dtype="int8")
    err = _decode_parity(cfg, tol=None)
    # int8 grid scale 1/16: logits differ by O(q-noise); bounded, small.
    assert err < 0.5, err
    # and the cache really is int8
    m = api.get_model(cfg)
    cache = m.init_cache(cfg, 2, 8)
    assert cache["k"].dtype == jnp.int8 and cache["v"].dtype == jnp.int8


def test_swa_windowed_blocked_matches_dense(rng):
    from repro.models import layers as L
    cfg = dataclasses.replace(get_config("mixtral_8x7b").smoke(), window=24,
                              softmax_mode="exact", logit_int8=False,
                              attn_block=16)
    B, S, H, hd = 2, 100, 4, 16
    q = jnp.asarray(rng.normal(0, 1, (B, S, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, S, 2, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, S, 2, hd)).astype(np.float32))
    pos = jnp.arange(S)
    dense = L.attend_dense(q, k, v, pos, pos, cfg, "serve", causal=True)
    blocked = L.attend_blocked(q, k, v, pos, pos, cfg, "serve", causal=True)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(dense),
                               atol=2e-6)


def test_chunked_wkv_matches_sequential(rng):
    from repro.models import rwkv6
    B, S, H, hd = 2, 64, 3, 8
    r, k, v = (jnp.asarray(rng.normal(0, 1, (B, S, H, hd)).astype(np.float32))
               for _ in range(3))
    w = jnp.asarray(rng.uniform(0.05, 0.999, (B, S, H, hd)).astype(np.float32))
    u = jnp.asarray(rng.normal(0, 0.5, (H, hd)).astype(np.float32))
    S0 = jnp.asarray(rng.normal(0, 0.3, (B, H, hd, hd)).astype(np.float32))
    o1, s1 = rwkv6._wkv_sequential(r, k, v, w, u, S0)
    for chunk in (8, 16, 32):
        o2, s2 = rwkv6._wkv_chunked(r, k, v, w, u, S0, chunk)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=1e-4, rtol=1e-4)


def test_chunked_wkv_model_level():
    cfg = get_config("rwkv6_7b").smoke()
    cfgc = dataclasses.replace(cfg, rwkv_chunk=16)
    m = api.get_model(cfg)
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                              cfg.vocab_size)
    a = m.forward(params, toks, cfg, "train")
    b = m.forward(params, toks, cfgc, "train")
    assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_fsdp_param_spec():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.sharding.rules import Rules, fsdp_param_spec
    mesh = make_mesh((1,), ("data",))
    r = Rules.__new__(Rules)
    r.mesh = mesh
    r.table = {}
    r.axis_sizes = {"data": 16, "model": 16}
    # largest dim divisible by 256 shards over both axes
    assert fsdp_param_spec((4096, 12288), r) == P(None, ("data", "model"))
    # vocab 256128 not divisible by 256 -> falls to the other dim
    assert fsdp_param_spec((256128, 4096), r) == P(None, ("data", "model"))
    # nothing divisible by 256 -> falls back to data=16
    assert fsdp_param_spec((48, 31), r) == P("data", None)
    # nothing divisible at all -> replicated
    assert fsdp_param_spec((7, 5), r) == P(None, None)


def test_decode_cache_layout_axes_match_structure():
    """cache_axes trees must match init_cache trees for every family."""
    from repro.configs.base import ARCH_NAMES
    for arch in ARCH_NAMES:
        cfg = get_config(arch).smoke()
        if cfg.family == "ssm":
            continue
        m = api.get_model(cfg)
        cache = jax.eval_shape(lambda: m.init_cache(cfg, 2, 16))
        axes = m.cache_axes(cfg)
        ct = jax.tree.structure(cache)
        at = jax.tree.structure(
            axes, is_leaf=lambda x: isinstance(x, tuple))
        assert ct == at, f"{arch}: cache/axes structure mismatch"
