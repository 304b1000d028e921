"""Whisper-small backbone (enc-dec). The log-mel conv frontend is a stub:
``input_specs`` provides precomputed frame embeddings (B, S_enc, d_model)
with positional information already folded in (DESIGN.md §4).

Shape interpretation for the assigned LM shapes (documented deviation):
  train_4k     encoder frames = seq_len, decoder tokens = 448 (whisper's
               decoding context), loss over decoder positions.
  prefill_32k  encoder frames = seq_len + 448-token decoder prompt.
  decode_32k   one decoder token against a self-KV cache of seq_len and a
               1500-frame cross-attention context.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.transformer import remat_wrap
from repro.sharding.rules import constrain

Array = jax.Array
DEC_LEN = 448


def init_enc_layer(key, cfg: ArchConfig):
    k1, k2 = jax.random.split(key)
    return {"ln1": L.init_norm(cfg), "attn": L.init_attention(k1, cfg),
            "ln2": L.init_norm(cfg), "mlp": L.init_mlp(k2, cfg)}


def init_dec_layer(key, cfg: ArchConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": L.init_norm(cfg), "attn": L.init_attention(k1, cfg),
            "ln_x": L.init_norm(cfg), "xattn": L.init_attention(k2, cfg),
            "ln2": L.init_norm(cfg), "mlp": L.init_mlp(k3, cfg)}


def init(rng, cfg: ArchConfig):
    ke, k1, k2 = jax.random.split(rng, 3)
    enc = jax.vmap(lambda k: init_enc_layer(k, cfg))(
        jax.random.split(k1, cfg.n_enc_layers))
    dec = jax.vmap(lambda k: init_dec_layer(k, cfg))(
        jax.random.split(k2, cfg.n_layers))
    return {
        "embed": L.init_embed(ke, cfg),
        "enc_layers": L.stack_layer_params(enc),
        "enc_norm": L.init_norm(cfg),
        "dec_layers": L.stack_layer_params(dec),
        "final_norm": L.init_norm(cfg),
    }


def _sin_pos(s: int, d: int) -> Array:
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def encode(params, frames: Array, cfg: ArchConfig, phase: str) -> Array:
    """frames (B, S_enc, D) -> encoder states (B, S_enc, D)."""
    x = L.cast(jnp.asarray(frames), cfg)
    s = x.shape[1]
    positions = jnp.arange(s)

    def layer(x, lp):
        h = L.apply_norm(x, lp["ln1"], cfg, phase)
        attn_out = L.apply_attention(lp["attn"], h, positions, cfg, phase,
                                     causal=False)
        x, h = L.apply_residual_norm(x, attn_out, lp["ln2"], cfg, phase)
        x = x + L.apply_mlp(h, lp["mlp"], cfg)
        return constrain(x, "batch", "seq", "embed"), None

    x, _ = jax.lax.scan(remat_wrap(layer, cfg), x, params["enc_layers"])
    return L.apply_norm(x, params["enc_norm"], cfg, phase)


def decode(params, tokens: Array, enc_out: Array, cfg: ArchConfig,
           phase: str) -> Array:
    b, s = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, cfg)
    x = x + L.cast(_sin_pos(s, cfg.d_model), cfg)[None]
    positions = jnp.arange(s)

    def layer(x, lp):
        h = L.apply_norm(x, lp["ln1"], cfg, phase)
        attn_out = L.apply_attention(lp["attn"], h, positions, cfg, phase,
                                     causal=True)
        x, h = L.apply_residual_norm(x, attn_out, lp["ln_x"], cfg, phase)
        kv = L.cross_kv(lp["xattn"], enc_out, cfg)
        xattn_out = L.apply_cross_attention(lp["xattn"], h, kv, cfg, phase)
        x, h = L.apply_residual_norm(x, xattn_out, lp["ln2"], cfg, phase)
        x = x + L.apply_mlp(h, lp["mlp"], cfg)
        return constrain(x, "batch", "seq", "embed"), None

    x, _ = jax.lax.scan(remat_wrap(layer, cfg), x, params["dec_layers"])
    x = L.apply_norm(x, params["final_norm"], cfg, phase)
    return L.lm_logits(params["embed"], x, cfg)


def forward(params, batch: Dict[str, Array], cfg: ArchConfig,
            phase: str) -> Array:
    enc_out = encode(params, batch["frames"], cfg, phase)
    return decode(params, batch["tokens"], enc_out, cfg, phase)


# -- serving ------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, length: int):
    from repro.models.transformer import init_cache as dense_cache
    stacked = dense_cache(cfg, batch, length)
    ck = jnp.zeros((cfg.n_layers, batch, cfg.cross_len, cfg.n_kv_heads,
                    cfg.head_dim), jnp.dtype(cfg.dtype))
    return {"self": stacked, "cross_k": ck, "cross_v": ck,
            "cross_pos": jnp.arange(cfg.cross_len, dtype=jnp.int32)}


def cache_axes(cfg: ArchConfig):
    from repro.models.transformer import cache_axes as dense_axes
    xa = ("layers", "batch", "seq", "kv_heads", "head_dim")
    return {"self": dense_axes(cfg),
            "cross_k": xa, "cross_v": xa, "cross_pos": (None,)}


def prefill(params, batch: Dict[str, Array], cfg: ArchConfig,
            cache_len: int):
    """Encode audio + run the decoder prompt, fill self/cross caches."""
    enc_out = encode(params, batch["frames"], cfg, "serve")
    enc_ctx = enc_out[:, :cfg.cross_len]
    valid = enc_ctx.shape[1]
    cross_pos = jnp.arange(cfg.cross_len, dtype=jnp.int32)
    cross_pos = jnp.where(cross_pos < valid, cross_pos, 2**30)
    if valid < cfg.cross_len:
        enc_ctx = jnp.pad(enc_ctx, ((0, 0), (0, cfg.cross_len - valid),
                                    (0, 0)))
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, cfg)
    x = x + L.cast(_sin_pos(s, cfg.d_model), cfg)[None]
    positions = jnp.arange(s)
    t = cache_len

    def layer(x, lp):
        h = L.apply_norm(x, lp["ln1"], cfg, "serve")
        q, k, v = L._project_qkv(lp["attn"], h, cfg)
        ctx = L.attend_dense(q, k, v, positions, positions, cfg, "serve")
        attn_out = jnp.einsum("bshk,hkd->bsd", ctx,
                              L.cast(lp["attn"]["wo"], cfg))
        x, h = L.apply_residual_norm(x, attn_out, lp["ln_x"], cfg, "serve")
        ckv = L.cross_kv(lp["xattn"], enc_ctx, cfg)
        xattn_out = L.apply_cross_attention(lp["xattn"], h, ckv, cfg, "serve",
                                            k_pos=cross_pos)
        x, h = L.apply_residual_norm(x, xattn_out, lp["ln2"], cfg, "serve")
        x = x + L.apply_mlp(h, lp["mlp"], cfg)
        kq, vq, pp = L.pack_prefill_cache(k, v, positions, t, cfg)
        cache_l = {"k": kq, "v": vq, "pos": pp}
        return x, (cache_l, ckv[0].astype(jnp.dtype(cfg.dtype)),
                   ckv[1].astype(jnp.dtype(cfg.dtype)))

    x, (self_cache, ck, cv) = jax.lax.scan(layer, x, params["dec_layers"])
    self_cache = {"k": self_cache["k"], "v": self_cache["v"],
                  "pos": self_cache["pos"][0]}
    x = L.apply_norm(x, params["final_norm"], cfg, "serve")
    logits = L.lm_logits(params["embed"], x[:, -1:], cfg)
    return logits, {"self": self_cache, "cross_k": ck, "cross_v": cv,
                    "cross_pos": cross_pos}


def decode_step(params, cache, token: Array, pos: Array, cfg: ArchConfig):
    x = L.embed_tokens(params["embed"], token[:, None], cfg)
    d = cfg.d_model
    posv = jnp.arange(d // 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32) / jnp.power(10000.0, 2 * posv / d)
    x = x + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None].astype(x.dtype)

    t = cache["self"]["k"].shape[-1]
    slot = jnp.minimum(pos, t - 1)
    cpos = jax.lax.dynamic_update_index_in_dim(
        cache["self"]["pos"], pos.astype(jnp.int32), slot, 0)
    sk, sv = cache["self"]["k"], cache["self"]["v"]

    def layer(x, scanned):
        lp, idx, ck, cv = scanned
        h = L.apply_norm(x, lp["ln1"], cfg, "serve")
        attn_out, k_col, v_row = L.decode_attend_stacked(
            lp["attn"], h, sk, sv, cpos, idx, pos, cfg, rope=False)
        x, h = L.apply_residual_norm(x, attn_out, lp["ln_x"], cfg, "serve")
        xattn_out = L.apply_cross_attention(lp["xattn"], h,
                                            (L.cast(ck, cfg),
                                             L.cast(cv, cfg)),
                                            cfg, "serve",
                                            k_pos=cache["cross_pos"])
        x, h = L.apply_residual_norm(x, xattn_out, lp["ln2"], cfg, "serve")
        x = x + L.apply_mlp(h, lp["mlp"], cfg)
        return x, (k_col, v_row)

    x, (k_cols, v_rows) = jax.lax.scan(
        layer, x, (params["dec_layers"], jnp.arange(cfg.n_layers),
                   cache["cross_k"], cache["cross_v"]))
    sk, sv = L.write_kv_columns(sk, sv, k_cols, v_rows, slot)
    x = L.apply_norm(x, params["final_norm"], cfg, "serve")
    logits = L.lm_logits(params["embed"], x, cfg)
    return logits[:, 0], {"self": {"k": sk, "v": sv, "pos": cpos},
                          "cross_k": cache["cross_k"],
                          "cross_v": cache["cross_v"],
                          "cross_pos": cache["cross_pos"]}


# -- paged serving (decoder self-KV in pages + shared cross pages) ------------
#
# The encoder runs ONCE per request, at admission (``encode_paged``):
# every decoder layer's cross-attention K/V is written into pages the
# scheduler allocated for the request (``refs["cross"]``), in the same
# {"k","v"} pool the decoder's self-attention pages live in — one pool,
# two row namespaces. The cross pages are read-only for the request's
# lifetime: prefill chunks and decode steps gather them per layer and
# never write them, so preemption/resume re-runs only the cheap decoder
# replay, not the encoder (the pages survive as long as the sequence
# holds its refs; a preempted-and-evicted request re-encodes).


def sequence_state_spec(cfg: ArchConfig):
    from repro.models.state import SequenceStateSpec
    return SequenceStateSpec(
        family="encdec", kv_layers=cfg.n_layers,
        cross_tokens=cfg.cross_len,
        # cross pages are per-request (encoder output), so decoder
        # prompts cannot COW-share across requests; spec-decode's
        # verify path is dense-family only.
        supports_prefix_cache=False, supports_spec_decode=False,
        supports_cow_fork=False, window=0)


def encode_paged(params, frames: Array, cross_table: Array, state,
                 cfg: ArchConfig):
    """Run the encoder and park every decoder layer's cross K/V in the
    request's cross pages. frames (B, S_enc, D); cross_table (B, NBc)
    covering ``cfg.cross_len`` rows. Returns the updated state."""
    from repro.serve.kv_cache import slots_for_positions, write_tokens
    enc_out = encode(params, frames, cfg, "serve")
    enc_ctx = enc_out[:, :cfg.cross_len]
    valid = enc_ctx.shape[1]
    if valid < cfg.cross_len:
        enc_ctx = jnp.pad(enc_ctx, ((0, 0), (0, cfg.cross_len - valid),
                                    (0, 0)))
    pk, pv = state["k"], state["v"]
    bs = pk.shape[3]
    positions = jnp.broadcast_to(jnp.arange(cfg.cross_len)[None],
                                 (frames.shape[0], cfg.cross_len))
    block_ids, offsets = slots_for_positions(positions, bs, cross_table)
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["dec_layers"])
        ck, cv = L.cross_kv(lp["xattn"], enc_ctx, cfg)
        pk = pk.at[i].set(write_tokens(pk[i], L.kv_quant(ck, cfg),
                                       block_ids, offsets))
        pv = pv.at[i].set(write_tokens(pv[i], L.kv_quant(cv, cfg),
                                       block_ids, offsets))
    return dict(state, k=pk, v=pv)


def _gather_cross(state, refs, cfg: ArchConfig):
    """Per-layer (ck, cv) read from the request's cross pages — hoisted
    out of the horizon scan (the rows are read-only)."""
    from repro.serve.kv_cache import gather_kv
    return [(gather_kv(state["k"][i], refs["cross"])[:, :cfg.cross_len],
             gather_kv(state["v"][i], refs["cross"])[:, :cfg.cross_len])
            for i in range(cfg.n_layers)]


def _forward_paged(params, tokens, positions, n_valid, kv_len, refs, state,
                   cfg: ArchConfig, *, causal, backend, cross=None):
    """Decoder forward for C tokens per lane against paged self-KV and
    page-parked cross-KV. Mirrors transformer._paged_forward's write-
    then-attend discipline for the self pages; ``cross`` optionally
    passes pre-gathered per-layer cross K/V (see :func:`_gather_cross`).
    """
    from repro.serve.kv_cache import (PAGED_KV_AXES, slots_for_positions,
                                      write_tokens)
    pk = constrain(state["k"], *PAGED_KV_AXES["k"])
    pv = constrain(state["v"], *PAGED_KV_AXES["v"])
    tables = refs["tables"]
    bs = pk.shape[3]
    x = L.embed_tokens(params["embed"], tokens, cfg)
    # per-lane sinusoidal positions — same rows _sin_pos builds
    d = cfg.d_model
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, None, :]
    ang = (positions.astype(jnp.float32)[:, :, None]
           / jnp.power(10000.0, 2 * dim / d))
    x = x + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(x.dtype)
    q_start = positions[:, 0]
    block_ids, offsets = slots_for_positions(positions, bs, tables)
    write_end = (q_start + n_valid)[:, None]
    block_ids = jnp.where(positions < write_end, block_ids, 0)
    tcl = cfg.cross_len
    cross_pos = jnp.where(
        jnp.arange(tcl)[None] < refs["cross_valid"][:, None],
        jnp.arange(tcl)[None], 2**30)
    if cross is None:
        cross = _gather_cross(state, refs, cfg)
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["dec_layers"])
        h = L.apply_norm(x, lp["ln1"], cfg, "serve")
        q, k, v = L._project_qkv(lp["attn"], h, cfg)
        pk = pk.at[i].set(write_tokens(pk[i], L.kv_quant(k, cfg),
                                       block_ids, offsets))
        pv = pv.at[i].set(write_tokens(pv[i], L.kv_quant(v, cfg),
                                       block_ids, offsets))
        ctx = L.paged_attend(q, pk[i], pv[i], tables, q_start, kv_len,
                             cfg, causal=causal, backend=backend)
        attn_out = jnp.einsum("bshk,hkd->bsd", ctx,
                              L.cast(lp["attn"]["wo"], cfg))
        x, h = L.apply_residual_norm(x, attn_out, lp["ln_x"], cfg, "serve")
        ck, cv = cross[i]
        xattn_out = L.apply_cross_attention(
            lp["xattn"], h, (L.cast(ck, cfg), L.cast(cv, cfg)), cfg,
            "serve", k_pos=cross_pos)
        x, h = L.apply_residual_norm(x, xattn_out, lp["ln2"], cfg, "serve")
        x = x + L.apply_mlp(h, lp["mlp"], cfg)
    x = L.apply_norm(x, params["final_norm"], cfg, "serve")
    logits = L.lm_logits(params["embed"], x, cfg)
    return logits, dict(state, k=pk, v=pv)


def prefill_paged(params, tokens: Array, q_start: Array, n_valid: Array,
                  refs, state, cfg: ArchConfig, *, backend=None):
    """One chunked-prefill step over the decoder prompt (the encoder
    already ran at admission — see :func:`encode_paged`). Returns
    (logits (B,C,V), state)."""
    c = tokens.shape[1]
    positions = q_start[:, None] + jnp.arange(c)[None]
    return _forward_paged(params, tokens, positions, n_valid,
                          q_start + n_valid, refs, state, cfg,
                          causal=True, backend=backend)


def decode_step_paged(params, token: Array, pos: Array, refs, state,
                      cfg: ArchConfig, *, backend=None):
    """One decode step: token (B,) at positions (B,). Returns
    (logits (B, V), state)."""
    logits, state = _forward_paged(
        params, token[:, None], pos[:, None], jnp.ones_like(pos), pos + 1,
        refs, state, cfg, causal=False, backend=backend)
    return logits[:, 0], state


def decode_horizon_paged(params, token: Array, pos: Array, refs, state,
                         temperature: Array, top_k: Array, seed: Array,
                         counter: Array, eos_ids: Array, cfg: ArchConfig, *,
                         num_steps: int, use_top_k: bool = True,
                         stochastic: bool = True, use_eos: bool = True,
                         backend=None):
    """``num_steps`` fused decode+sample steps (see the transformer
    variant for the sampling/eos contract). The cross pages are
    read-only, so their gather is hoisted out of the scan — per-horizon
    cross traffic, not per-token."""
    from repro.serve.sampling import eos_hits, sample_tokens
    cross = _gather_cross(state, refs, cfg)

    def step(carry, i):
        st, tok, p = carry
        logits, st = _forward_paged(
            params, tok[:, None], p[:, None], jnp.ones_like(p), p + 1,
            refs, st, cfg, causal=False, backend=backend, cross=cross)
        nxt = sample_tokens(logits[:, 0], temperature, top_k, seed,
                            counter + i, cfg.vocab_size,
                            use_top_k=use_top_k, stochastic=stochastic)
        done = (eos_hits(nxt, eos_ids) if use_eos
                else jnp.zeros(nxt.shape, jnp.bool_))
        return (st, nxt, p + 1), (nxt, done)

    (state, _, _), (toks, done) = jax.lax.scan(
        step, (state, token, pos), jnp.arange(num_steps, dtype=jnp.int32))
    return jnp.transpose(toks), jnp.transpose(done), state
