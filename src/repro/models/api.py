"""Unified model API: family registry, loss, and abstract input specs.

Every architecture family exposes init / forward / init_cache / prefill /
decode_step; this module dispatches on ``cfg.family`` and defines the
training loss (next-token cross-entropy + MoE aux loss) and the
ShapeDtypeStruct input builders used by the dry-run (no allocation).
"""
from __future__ import annotations

import importlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig

Array = jax.Array

_FAMILY_MODULES = {
    "dense": "repro.models.transformer",
    "moe": "repro.models.moe",
    "encdec": "repro.models.whisper",
    "vlm": "repro.models.vlm",
    "ssm": "repro.models.rwkv6",
    "hybrid": "repro.models.rglru",
}

AUX_LOSS_WEIGHT = 0.01
WHISPER_DEC_LEN = 448


def get_model(cfg: ArchConfig):
    return importlib.import_module(_FAMILY_MODULES[cfg.family])


def init_params(rng, cfg: ArchConfig, rules=None):
    """Returns (param value tree, logical-axes tree).

    ``rng`` is a PRNG key, or a plain int seed — key construction lives
    here so callers outside the sampling contract (serve/, notably)
    never touch ``jax.random.PRNGKey`` themselves (lint rule RPR004).

    With ``rules`` (sharding/rules.py) the init runs as one jitted
    program whose outputs are laid out per the logical axes, so every
    device only ever holds its own shard — a model sized for a mesh is
    never materialized whole on one device first.
    """
    from repro.models.layers import split_params
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)

    def init(key):
        return get_model(cfg).init(key, cfg)

    if rules is None:
        return split_params(init(rng))
    shapes, axes = split_params(jax.eval_shape(init, rng))
    shardings = jax.tree.map(lambda s, ax: rules.sharding(ax, s.shape),
                             shapes, axes)
    params = jax.jit(lambda key: split_params(init(key))[0],
                     out_shardings=shardings)(rng)
    return params, axes


def forward(params, batch: Dict[str, Array], cfg: ArchConfig,
            phase: str = "serve"):
    m = get_model(cfg)
    if cfg.family in ("dense", "ssm", "hybrid"):
        return m.forward(params, batch["tokens"], cfg, phase)
    if cfg.family == "moe":
        logits, _ = m.forward(params, batch["tokens"], cfg, phase)
        return logits
    return m.forward(params, batch, cfg, phase)


def cross_entropy(logits: Array, targets: Array) -> Array:
    """Mean next-token NLL. logits (B,S,V) fp32, targets (B,S) int32."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def loss_fn(params, batch: Dict[str, Array], cfg: ArchConfig
            ) -> Tuple[Array, Dict[str, Array]]:
    m = get_model(cfg)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "ssm", "hybrid"):
        logits = m.forward(params, batch["tokens"], cfg, "train")
    elif cfg.family == "moe":
        logits, aux = m.forward(params, batch["tokens"], cfg, "train")
    else:
        logits = m.forward(params, batch, cfg, "train")
    xent = cross_entropy(logits, batch["targets"])
    loss = xent + AUX_LOSS_WEIGHT * aux
    return loss, {"xent": xent, "aux": aux}


# -- abstract input specs (dry-run: ShapeDtypeStruct, zero allocation) --------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def train_inputs(cfg: ArchConfig, shape: ShapeConfig):
    """(batch SDS tree, logical-axes tree) for train_step."""
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        t = min(WHISPER_DEC_LEN, s)
        batch = {"frames": _sds((b, s, cfg.d_model), cfg.dtype),
                 "tokens": _sds((b, t), jnp.int32),
                 "targets": _sds((b, t), jnp.int32)}
        axes = {"frames": ("batch", None, None), "tokens": ("batch", None),
                "targets": ("batch", None)}
    elif cfg.family == "vlm":
        batch = {"embeds": _sds((b, s, cfg.d_model), cfg.dtype),
                 "positions": _sds((3, b, s), jnp.int32),
                 "targets": _sds((b, s), jnp.int32)}
        axes = {"embeds": ("batch", None, None),
                "positions": (None, "batch", None),
                "targets": ("batch", None)}
    else:
        batch = {"tokens": _sds((b, s), jnp.int32),
                 "targets": _sds((b, s), jnp.int32)}
        axes = {"tokens": ("batch", None), "targets": ("batch", None)}
    return batch, axes


def prefill_inputs(cfg: ArchConfig, shape: ShapeConfig):
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        t = min(WHISPER_DEC_LEN, s)
        return ({"frames": _sds((b, s, cfg.d_model), cfg.dtype),
                 "tokens": _sds((b, t), jnp.int32)},
                {"frames": ("batch", None, None), "tokens": ("batch", None)})
    if cfg.family == "vlm":
        return ({"embeds": _sds((b, s, cfg.d_model), cfg.dtype),
                 "positions": _sds((3, b, s), jnp.int32)},
                {"embeds": ("batch", None, None),
                 "positions": (None, "batch", None)})
    return ({"tokens": _sds((b, s), jnp.int32)},
            {"tokens": ("batch", None)})


def decode_inputs(cfg: ArchConfig, shape: ShapeConfig):
    """(cache SDS tree, cache axes, token SDS, pos SDS)."""
    b, s = shape.global_batch, shape.seq_len
    m = get_model(cfg)
    cache = jax.eval_shape(lambda: m.init_cache(cfg, b, s))
    axes = m.cache_axes(cfg)
    token = _sds((b,), jnp.int32)
    pos = _sds((), jnp.int32)
    return cache, axes, token, pos


def paged_decode_inputs(cfg: ArchConfig, shape: ShapeConfig,
                        block_size: int = 16):
    """Abstract inputs for the paged decode step (dry-run, no allocation).

    Returns (state SDS tree, state axes tree, token SDS, pos SDS,
    refs SDS tree) — the composite *sequence state* the family's
    ``decode_step_paged`` consumes: ``{"k","v"}`` page pools sized to the
    dense-cache-equivalent capacity (plus the null page and, for encdec,
    the per-request cross pages), a ``"slots"`` pool with one slot per
    lane plus the null slot, and the reference vectors (page tables,
    slot ids, cross tables) the engine passes per dispatch.
    """
    from repro.models.layers import kv_store_dtype
    from repro.serve.kv_cache import PAGED_KV_AXES, cdiv
    spec = sequence_state_spec(cfg)
    if not spec.servable:
        raise ValueError(
            f"family {cfg.family!r} is not paged-servable "
            "(see its sequence_state_spec)")
    b, s = shape.global_batch, shape.seq_len
    state, axes, refs = {}, {}, {}
    if spec.has_pages:
        cross_blocks = cdiv(spec.cross_tokens, block_size)
        num_blocks = b * (cdiv(s, block_size) + cross_blocks) + 1
        pool_shape = (spec.kv_layers, num_blocks, cfg.n_kv_heads,
                      block_size, cfg.head_dim)
        dt = kv_store_dtype(cfg)
        state["k"] = _sds(pool_shape, dt)
        state["v"] = _sds(pool_shape, dt)
        axes["k"], axes["v"] = PAGED_KV_AXES["k"], PAGED_KV_AXES["v"]
        refs["tables"] = _sds((b, cdiv(s, block_size)), jnp.int32)
        if spec.cross_tokens:
            refs["cross"] = _sds((b, cross_blocks), jnp.int32)
            refs["cross_valid"] = _sds((b,), jnp.int32)
    if spec.has_slots:
        state["slots"] = jax.tree.map(
            lambda l: _sds((b + 1,) + l.shape, l.dtype), spec.slot_shapes)
        axes["slots"] = jax.tree.map(
            lambda ax: ("state_slots",) + tuple(ax), spec.slot_axes,
            is_leaf=lambda x: isinstance(x, tuple))
        refs["slots"] = _sds((b,), jnp.int32)
    token = _sds((b,), jnp.int32)
    pos = _sds((b,), jnp.int32)
    return state, axes, token, pos, refs


# -- paged family dispatch (the ONLY model surface serve/ talks to) -----------
#
# serve/engine.py used to import models.transformer directly, which made
# "paged serving" a dense-only feature. Every paged entry point now
# dispatches here on cfg.family with ONE calling convention:
#
#   state — the composite sequence-state tree the engine owns:
#           {"k","v"} page pools (families with kv_layers > 0) and/or
#           "slots" (a StateSlotPool's device tree);
#   refs  — per-dispatch reference vectors: "tables" (B, NB) page
#           tables, "slots" (B,) slot ids, "cross"/"cross_valid" for
#           encdec. Only the keys the family's spec calls for.
#
# dense/moe keep their historical (tables, pools) signatures (pinned by
# tests that call them directly); the adapters below bridge. Lint rule
# RPR007 enforces that serve/ never bypasses this dispatch.


def sequence_state_spec(cfg: ArchConfig):
    """The family's :class:`repro.models.state.SequenceStateSpec`."""
    return get_model(cfg).sequence_state_spec(cfg)


def _check_servable(cfg: ArchConfig):
    if not sequence_state_spec(cfg).servable:
        raise ValueError(
            f"family {cfg.family!r} is not paged-servable "
            "(see its sequence_state_spec)")


def prefill_paged(params, tokens, q_start, n_valid, refs, state,
                  cfg: ArchConfig, *, backend=None):
    """One chunked-prefill step. Returns (logits (B,C,V), state)."""
    _check_servable(cfg)
    m = get_model(cfg)
    if cfg.family in ("dense", "moe"):
        logits, pools = m.prefill_paged(
            params, tokens, q_start, n_valid, refs["tables"], state, cfg,
            backend=backend)
        return logits, dict(state, **pools)
    return m.prefill_paged(params, tokens, q_start, n_valid, refs, state,
                           cfg, backend=backend)


def decode_step_paged(params, token, pos, refs, state, cfg: ArchConfig, *,
                      backend=None):
    """One decode step: token/pos (B,). Returns (logits (B,V), state)."""
    _check_servable(cfg)
    m = get_model(cfg)
    if cfg.family in ("dense", "moe"):
        logits, pools = m.decode_step_paged(
            params, state, token, pos, refs["tables"], cfg, backend=backend)
        return logits, dict(state, **pools)
    return m.decode_step_paged(params, token, pos, refs, state, cfg,
                               backend=backend)


def decode_horizon_paged(params, token, pos, refs, state, temperature,
                         top_k, seed, counter, eos_ids, cfg: ArchConfig, *,
                         num_steps, use_top_k=True, stochastic=True,
                         use_eos=True, backend=None):
    """``num_steps`` fused decode+sample steps. Returns
    (tokens (B, num_steps), done (B, num_steps), state)."""
    _check_servable(cfg)
    m = get_model(cfg)
    if cfg.family in ("dense", "moe"):
        toks, done, pools = m.decode_horizon_paged(
            params, state, token, pos, refs["tables"], temperature, top_k,
            seed, counter, eos_ids, cfg, num_steps=num_steps,
            use_top_k=use_top_k, stochastic=stochastic, use_eos=use_eos,
            backend=backend)
        return toks, done, dict(state, **pools)
    return m.decode_horizon_paged(
        params, token, pos, refs, state, temperature, top_k, seed,
        counter, eos_ids, cfg, num_steps=num_steps, use_top_k=use_top_k,
        stochastic=stochastic, use_eos=use_eos, backend=backend)


def verify_paged(params, tokens, q_start, n_valid, refs, state,
                 temperature, top_k, seed, counter, eos_ids,
                 cfg: ArchConfig, *, use_top_k=True, stochastic=True,
                 use_eos=True, backend=None):
    """Speculative-verify dispatch (spec-decode-capable families only).
    Returns (pinned (B,C), done (B,C), state)."""
    if not sequence_state_spec(cfg).supports_spec_decode:
        raise ValueError(
            f"family {cfg.family!r} does not support speculative decoding "
            "(its sequence state cannot rewind rejected drafts)")
    m = get_model(cfg)
    pinned, done, pools = m.verify_paged(
        params, state, tokens, q_start, n_valid, refs["tables"],
        temperature, top_k, seed, counter, eos_ids, cfg,
        use_top_k=use_top_k, stochastic=stochastic, use_eos=use_eos,
        backend=backend)
    return pinned, done, dict(state, **pools)


def encode_paged(params, frames, cross_table, state, cfg: ArchConfig):
    """Admission-time encoder run (encdec only): park cross-attention
    K/V in the request's cross pages. Returns the updated state."""
    if cfg.family != "encdec":
        raise ValueError(f"encode_paged is encdec-only, got {cfg.family}")
    return get_model(cfg).encode_paged(params, frames, cross_table, state,
                                       cfg)
