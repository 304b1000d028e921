"""Dense decoder-only LM (qwen2 / stablelm / nemotron / minitron / mixtral
backbone). MoE archs reuse this module with the FFN swapped (models/moe.py).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.sharding.rules import constrain

Array = jax.Array


def remat_wrap(fn: Callable, cfg: ArchConfig) -> Callable:
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        policy = jax.checkpoint_policies.nothing_saveable
    else:
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint(fn, policy=policy)


def init_layer(key, cfg: ArchConfig, ffn_init=None):
    k1, k2 = jax.random.split(key)
    ffn_init = ffn_init or L.init_mlp
    return {
        "ln1": L.init_norm(cfg),
        "attn": L.init_attention(k1, cfg),
        "ln2": L.init_norm(cfg),
        "mlp": ffn_init(k2, cfg),
    }


def init(rng, cfg: ArchConfig, ffn_init=None):
    ke, kl = jax.random.split(rng)
    keys = jax.random.split(kl, cfg.n_layers)
    layer_stack = jax.vmap(lambda k: init_layer(k, cfg, ffn_init))(keys)
    return {
        "embed": L.init_embed(ke, cfg),
        "layers": L.stack_layer_params(layer_stack),
        "final_norm": L.init_norm(cfg),
    }


def _layer_fn(cfg: ArchConfig, phase: str, ffn_apply=None):
    ffn_apply = ffn_apply or (lambda p, x, c, ph: L.apply_mlp(x, p, c))

    def layer(x, lp, positions):
        h = L.apply_norm(x, lp["ln1"], cfg, phase)
        attn_out = L.apply_attention(lp["attn"], h, positions, cfg, phase)
        x, h = L.apply_residual_norm(x, attn_out, lp["ln2"], cfg, phase)
        x = x + ffn_apply(lp["mlp"], h, cfg, phase)
        return constrain(x, "batch", "seq", "embed")

    return layer


def forward(params, tokens: Array, cfg: ArchConfig, phase: str,
            ffn_apply=None) -> Array:
    """tokens (B, S) -> logits (B, S, padded_vocab)."""
    x = L.embed_tokens(params["embed"], tokens, cfg)
    positions = jnp.arange(tokens.shape[1])
    layer = _layer_fn(cfg, phase, ffn_apply)
    body = remat_wrap(lambda x, lp: (layer(x, lp, positions), None), cfg)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        leaves = [jax.tree.map(lambda a: a[i], params["layers"])
                  for i in range(cfg.n_layers)]
        for lp in leaves:
            x, _ = body(x, lp)
    x = L.apply_norm(x, params["final_norm"], cfg, phase)
    return L.lm_logits(params["embed"], x, cfg)


# -- serving ------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, length: int):
    """Stacked dot-layout-native caches (see decode_attend_stacked):
    k (L,B,KV,hd,T), v (L,B,KV,T,hd), one shared position ring (T,)."""
    t = min(length, cfg.window) if cfg.window else length
    dt = L.kv_store_dtype(cfg)
    lk = (cfg.n_layers, batch, cfg.n_kv_heads, cfg.head_dim, t)
    lv = (cfg.n_layers, batch, cfg.n_kv_heads, t, cfg.head_dim)
    return {"k": jnp.zeros(lk, dt), "v": jnp.zeros(lv, dt),
            "pos": jnp.full((t,), 2**30, jnp.int32)}


def cache_axes(cfg: ArchConfig):
    return {"k": ("layers", "batch", "kv_heads", "head_dim", None),
            "v": ("layers", "batch", "kv_heads", None, "head_dim"),
            "pos": (None,)}


def sequence_state_spec(cfg: ArchConfig):
    """Dense LMs: sequence state is attention KV and nothing else —
    every layer pages, every paged feature (prefix sharing, COW forks,
    speculative verify) is exact."""
    from repro.models.state import SequenceStateSpec
    return SequenceStateSpec(
        family="dense", kv_layers=cfg.n_layers,
        supports_prefix_cache=True, supports_spec_decode=True,
        supports_cow_fork=True, window=cfg.window)


def prefill(params, tokens: Array, cfg: ArchConfig, cache_len: int,
            ffn_apply=None, n_pad=None) -> Tuple[Array, Dict[str, Array]]:
    """Run the full prompt, returning last-position logits + filled cache.

    ``n_pad`` (B,) marks left-padding per lane: lane ``j``'s real tokens
    occupy columns ``n_pad[j]..S-1`` at logical positions ``0..``. Pad
    columns are masked out of every key set (stored position 2**30) and
    RoPE sees the local positions, so a left-padded lane is bit-for-bit
    the same computation (at its real rows) as serving the prompt alone.
    ``n_pad=None`` keeps the legacy shared ``arange(S)`` positions.
    """
    b, s = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, cfg)
    if n_pad is None:
        q_pos = k_pos = rope_pos = jnp.arange(s)
    else:
        local = jnp.arange(s)[None] - n_pad[:, None]      # (B, S)
        k_pos = jnp.where(local < 0, 2**30, local)        # pads: masked keys
        q_pos = rope_pos = jnp.maximum(local, 0)          # pad rows: garbage,
        # but never all-masked (they see the lane's first real key), and
        # pad keys are invalid so they never reach real rows.
    ffn_apply = ffn_apply or (lambda p, x, c, ph: L.apply_mlp(x, p, c))
    t = min(cache_len, cfg.window) if cfg.window else cache_len

    def layer(x, lp):
        h = L.apply_norm(x, lp["ln1"], cfg, "serve")
        q, k, v = L._project_qkv(lp["attn"], h, cfg)
        if cfg.pos_kind == "rope":
            q = L.apply_rope(q, rope_pos, cfg)
            k = L.apply_rope(k, rope_pos, cfg)
        impl = cfg.attn_impl
        if impl == "auto":
            impl = "blocked" if s >= 8192 else "dense"
        if impl == "blocked" and n_pad is not None:
            impl = "dense"      # blocked path is shared-positions only
        fn = L.attend_blocked if impl == "blocked" else L.attend_dense
        ctx = fn(q, k, v, q_pos, k_pos, cfg, "serve", causal=cfg.causal)
        attn_out = L._wo_proj(ctx, lp["attn"], cfg)
        x, h = L.apply_residual_norm(x, attn_out, lp["ln2"], cfg, "serve")
        x = x + ffn_apply(lp["mlp"], h, cfg, "serve")
        kq, vq, pp = L.pack_prefill_cache(k, v, k_pos, t, cfg)
        cache_l = {"k": kq, "v": vq, "pos": pp}
        return constrain(x, "batch", "seq", "embed"), cache_l

    x, cache = jax.lax.scan(layer, x, params["layers"])
    cache = {"k": cache["k"], "v": cache["v"], "pos": cache["pos"][0]}
    x = L.apply_norm(x, params["final_norm"], cfg, "serve")
    logits = L.lm_logits(params["embed"], x[:, -1:], cfg)
    return logits, cache


def decode_step(params, cache, token: Array, pos: Array, cfg: ArchConfig,
                ffn_apply=None, write_pos=None
                ) -> Tuple[Array, Dict[str, Array]]:
    """One decode step. token (B,), pos scalar int32 — or (B,) per-lane
    logical positions for left-padded batches, with ``write_pos`` the
    shared scalar physical column (prompt length + step).

    The stacked dot-native caches are READ-ONLY inside the layer scan
    (no aliasing copies); each layer's new (k, v) column is emitted via
    scan ys and all layers' columns are written in one batched
    dynamic-update-slice afterwards — per-token HBM traffic is one read
    of each layer's K/V + one tiny write (§Perf hillclimb A).
    """
    x = L.embed_tokens(params["embed"], token[:, None], cfg)
    ffn_apply = ffn_apply or (lambda p, x, c, ph: L.apply_mlp(x, p, c))
    t = cache["k"].shape[-1]
    wp = pos if write_pos is None else write_pos
    slot = jnp.mod(wp, t) if cfg.window else jnp.minimum(wp, t - 1)
    if cache["pos"].ndim == 2:           # per-lane position ring (B, T)
        col = jnp.broadcast_to(pos.astype(jnp.int32),
                               (cache["pos"].shape[0],))[:, None]
        cpos = jax.lax.dynamic_update_slice(
            cache["pos"], col, (jnp.zeros((), slot.dtype), slot))
    else:
        cpos = jax.lax.dynamic_update_index_in_dim(
            cache["pos"], pos.astype(jnp.int32), slot, 0)
    ck, cv = cache["k"], cache["v"]      # read-only inside the layer scan

    def layer(x, scanned):
        lp, idx = scanned
        h = L.apply_norm(x, lp["ln1"], cfg, "serve")
        attn_out, k_col, v_row = L.decode_attend_stacked(
            lp["attn"], h, ck, cv, cpos, idx, pos, cfg, slot=slot)
        x, h = L.apply_residual_norm(x, attn_out, lp["ln2"], cfg, "serve")
        x = x + ffn_apply(lp["mlp"], h, cfg, "serve")
        return x, (k_col, v_row)

    x, (k_cols, v_rows) = jax.lax.scan(
        layer, x, (params["layers"], jnp.arange(cfg.n_layers)))
    ck, cv = L.write_kv_columns(ck, cv, k_cols, v_rows, slot)
    x = L.apply_norm(x, params["final_norm"], cfg, "serve")
    logits = L.lm_logits(params["embed"], x, cfg)
    return logits[:, 0], {"k": ck, "v": cv, "pos": cpos}


# -- paged serving (block-paged KV pool; see serve/kv_cache.py) ---------------


def _paged_forward(params, tokens, positions, n_valid, kv_len, tables,
                   pools, cfg: ArchConfig, *, causal: bool,
                   backend: Optional[str], ffn_apply=None):
    """Run C tokens per sequence against the paged pools.

    tokens/positions: (B, C) — absolute positions (a prefill chunk, or
    C=1 for decode); n_valid: (B,) real (non-padded) tokens in this
    chunk; kv_len: (B,) valid keys after this chunk's writes;
    tables: (B, NB) page tables; pools: {"k","v"} (L, N, KV, bs, hd).

    Each layer writes the chunk's K/V into its pages *before* attending,
    so queries see themselves through the same page-table path as the
    rest of the context. Writes beyond ``n_valid`` (the padded tail of a
    final prefill chunk) are routed to the null page, so padding never
    consumes — or corrupts — an allocated page; with on-demand
    allocation a sequence's table covers exactly its live tokens.
    Layers run as a Python loop (pools carry a per-layer scatter that
    scan cannot batch); returns (logits (B,C,V), updated pools).

    The serve hot path defers each residual add into the *consumer*
    norm: the MLP output of layer i merges with layer i+1's ln1 (and
    the last one with the final norm) through
    :func:`L.apply_residual_norm`, so in SOLE/pallas mode every
    residual-add + PTF quantize + AILayerNorm runs as one fused
    VMEM-resident kernel instead of three HBM round trips.
    """
    from repro.serve.kv_cache import (PAGED_KV_AXES, slots_for_positions,
                                      write_tokens)
    lay = params["layers"]
    # w8a8 dataflow: residual norms whose consumer is a quantized matmul
    # emit (int8 codes, scale) directly — the fused-output variant — so
    # the activation never round-trips through fp between norm and GEMM.
    # The FFN input is only quantized when the FFN is the stock dense MLP
    # (a custom ffn_apply, e.g. MoE routing, expects fp activations).
    qact = cfg.quant.acts and L.is_qtensor(lay["attn"]["wq"])
    quant_ffn = (qact and ffn_apply is None
                 and isinstance(lay.get("mlp"), dict)
                 and L.is_qtensor(lay["mlp"].get("up")))
    ffn_apply = ffn_apply or (lambda p, x, c, ph: L.apply_mlp(x, p, c))
    with jax.named_scope("embed"):
        x = L.embed_tokens(params["embed"], tokens, cfg)
    q_start = positions[:, 0]
    # Pin the pool layout (kv_heads over model, pages host-global) so
    # donated jit round trips and the scatter/attend pair below keep one
    # stable sharding instead of letting GSPMD re-derive it per call.
    pk = constrain(pools["k"], *PAGED_KV_AXES["k"])
    pv = constrain(pools["v"], *PAGED_KV_AXES["v"])
    block_size = pk.shape[3]
    block_ids, offsets = slots_for_positions(positions, block_size, tables)
    # mask padded-tail writes to the null page (page 0): positions at or
    # beyond q_start + n_valid hold no real token.
    write_end = (q_start + n_valid)[:, None]
    block_ids = jnp.where(positions < write_end, block_ids, 0)
    leaves = [jax.tree.map(lambda a: a[i], params["layers"])
              for i in range(cfg.n_layers)]
    pending = None                      # deferred MLP residual
    # Each sublayer runs under a stable jax.named_scope (the op_name
    # path of its HLO ops): embed, norm, qkv, kv_write, paged_attention,
    # attn_out, mlp, lm_head, and sample in decode_horizon_paged. A
    # trace reduction attributes device time by these names.
    for i, lp in enumerate(leaves):
        with jax.named_scope("norm"):
            if pending is None:
                h = L.apply_norm(x, lp["ln1"], cfg, "serve")
            else:
                x, h = L.apply_residual_norm(x, pending, lp["ln1"], cfg,
                                             "serve", quant_out=qact)
        with jax.named_scope("qkv"):
            q, k, v = L._project_qkv(lp["attn"], h, cfg)
            if cfg.pos_kind == "rope":
                q = L.apply_rope(q, positions, cfg)
                k = L.apply_rope(k, positions, cfg)
        with jax.named_scope("kv_write"):
            pk = pk.at[i].set(write_tokens(pk[i], L.kv_quant(k, cfg),
                                           block_ids, offsets))
            pv = pv.at[i].set(write_tokens(pv[i], L.kv_quant(v, cfg),
                                           block_ids, offsets))
        with jax.named_scope("paged_attention"):
            ctx = L.paged_attend(q, pk[i], pv[i], tables, q_start, kv_len,
                                 cfg, causal=causal, backend=backend)
        with jax.named_scope("attn_out"):
            attn_out = L._wo_proj(ctx, lp["attn"], cfg)
        with jax.named_scope("norm"):
            x, h = L.apply_residual_norm(x, attn_out, lp["ln2"], cfg,
                                         "serve", quant_out=quant_ffn)
        x = constrain(x, "batch", "seq", "embed")
        with jax.named_scope("mlp"):
            pending = ffn_apply(lp["mlp"], h, cfg, "serve")
    with jax.named_scope("norm"):
        if pending is None:
            x = L.apply_norm(x, params["final_norm"], cfg, "serve")
        else:
            _, x = L.apply_residual_norm(x, pending, params["final_norm"],
                                         cfg, "serve", quant_out=qact)
    with jax.named_scope("lm_head"):
        logits = L.lm_logits(params["embed"], x, cfg)
    return logits, {"k": pk, "v": pv}


def prefill_paged(params, tokens: Array, q_start: Array, n_valid: Array,
                  tables: Array, pools, cfg: ArchConfig, *,
                  backend: Optional[str] = None, ffn_apply=None):
    """One chunked-prefill step: write + attend C replay tokens.

    tokens (B, C) at absolute positions q_start..q_start+C-1 (B,), of
    which the first n_valid (B,) are real; returns (logits (B, C, V),
    pools). Padded tail tokens in the final chunk write to the null
    page and contribute no keys (kv_len stops at the last real token);
    causality keeps real queries' contexts exact either way.
    """
    c = tokens.shape[1]
    positions = q_start[:, None] + jnp.arange(c)[None]
    kv_len = q_start + n_valid
    return _paged_forward(params, tokens, positions, n_valid, kv_len,
                          tables, pools, cfg, causal=True, backend=backend,
                          ffn_apply=ffn_apply)


def decode_step_paged(params, pools, token: Array, pos: Array,
                      tables: Array, cfg: ArchConfig, *,
                      backend: Optional[str] = None, ffn_apply=None):
    """One continuous-batching decode step: token (B,) at positions (B,).

    The live token is written to its page first, then attended through
    the single-query fast path (kv_len = pos + 1, no causal iota work).
    Returns (logits (B, V), pools).
    """
    logits, pools = _paged_forward(
        params, token[:, None], pos[:, None], jnp.ones_like(pos), pos + 1,
        tables, pools, cfg, causal=False, backend=backend,
        ffn_apply=ffn_apply)
    return logits[:, 0], pools


def decode_horizon_paged(params, pools, token: Array, pos: Array,
                         tables: Array, temperature: Array, top_k: Array,
                         seed: Array, counter: Array, eos_ids: Array,
                         cfg: ArchConfig, *,
                         num_steps: int, use_top_k: bool = True,
                         stochastic: bool = True, use_eos: bool = True,
                         backend: Optional[str] = None, ffn_apply=None):
    """``num_steps`` fused decode+sample steps in one ``lax.scan``.

    token/pos (B,) are the feed token and its absolute position for step
    0; temperature/top_k/seed/counter (B,) are the per-lane sampling
    stream parameters (see serve/sampling.py — step ``i`` draws with
    counter ``counter + i``; ``use_top_k``/``stochastic`` are the
    static fast-path switches, safe whenever no lane in the batch uses
    top-k / a temperature). The page tables must already cover
    positions ``pos .. pos + num_steps - 1`` (the scheduler pre-extends
    them, COW copies applied up front), so the whole horizon runs on
    device with no host round trip: each scan step runs
    :func:`decode_step_paged` — the single decode-forward
    implementation — then samples the next token in-jit and feeds it
    forward. Only the (B, num_steps) sampled ids come back to the host
    — per-token logits transfers are gone.

    **Early exit / eos.** ``eos_ids`` (B, E) is each lane's ``-1``-padded
    terminator table; with ``use_eos`` (static, skip when no lane has
    eos ids) each step also emits the lane's eos membership mask
    (:func:`serve.sampling.eos_hits`). The scan cannot stop early — its
    shape is static — so a lane that samples an eos keeps decoding
    self-absorbing garbage for the rest of the horizon (writes stay
    inside its pre-extended, private pages); the host reads the
    returned ``(B, num_steps)`` done mask, truncates the lane's output
    at the first hit and reclaims the unused page tail
    (``PagedKVCache.truncate``). Tokens after the first hit never enter
    the sampler stream.

    Null lanes (all-zero table rows) are self-absorbing: their writes
    land in the null page and their sampled garbage feeds only
    themselves (see the null-page invariant in serve/kv_cache.py).
    Returns (tokens (B, num_steps) int32, eos (B, num_steps) bool,
    pools).
    """
    from repro.serve.sampling import eos_hits, sample_tokens

    def step(carry, i):
        pools, tok, p = carry
        logits, pools = decode_step_paged(params, pools, tok, p, tables,
                                          cfg, backend=backend,
                                          ffn_apply=ffn_apply)
        with jax.named_scope("sample"):
            nxt = sample_tokens(logits, temperature, top_k, seed,
                                counter + i, cfg.vocab_size,
                                use_top_k=use_top_k, stochastic=stochastic)
            done = (eos_hits(nxt, eos_ids) if use_eos
                    else jnp.zeros(nxt.shape, jnp.bool_))
        return (pools, nxt, p + 1), (nxt, done)

    (pools, _, _), (toks, done) = jax.lax.scan(
        step, (pools, token, pos), jnp.arange(num_steps, dtype=jnp.int32))
    return jnp.transpose(toks), jnp.transpose(done), pools


def verify_paged(params, pools, tokens: Array, q_start: Array,
                 n_valid: Array, tables: Array, temperature: Array,
                 top_k: Array, seed: Array, counter: Array, eos_ids: Array,
                 cfg: ArchConfig, *, use_top_k: bool = True,
                 stochastic: bool = True, use_eos: bool = True,
                 backend: Optional[str] = None, ffn_apply=None):
    """Speculative-verify dispatch: score C = K+1 positions per lane in
    **one** target forward and draw the pinned counter-keyed sample at
    every position in-jit.

    tokens (B, C) is each lane's last kept token followed by its K draft
    tokens (padded with zeros past ``n_valid``), fed at absolute
    positions ``q_start .. q_start + C - 1``. The forward is exactly the
    chunked-prefill path (:func:`prefill_paged`): causal, draft K/V
    written to the lane's pre-extended pages up front, padded-tail
    writes routed to the null page. In exact softmax mode the logits at
    slot ``i`` are bit-identical to what ``decode_step_paged`` would
    produce after feeding the same prefix — pinned by
    tests/test_spec_decode.py — so the pinned draw at slot ``i``
    (counter ``counter + i``; see serve/sampling.py) is exactly the
    token non-speculative decode would emit there. Acceptance on the
    host is then a prefix match: accept drafts while they equal the
    pinned draws; the first mismatching slot's pinned draw is the
    correction token, and a fully matching draft yields slot K's draw
    as a bonus token.

    Returns ``(pinned (B, C) int32, done (B, C) bool, pools)`` — the
    per-slot pinned draws, their eos membership mask (``eos_ids``
    (B, E), ``-1``-padded), and the updated pools. Rejected slots'
    page-table tail is reclaimed by the caller via
    ``PagedKVCache.truncate``; their written K/V is never read (kv_len
    masks it) and is overwritten by the next dispatch.
    """
    from repro.serve.sampling import eos_hits, sample_tokens
    logits, pools = prefill_paged(params, tokens, q_start, n_valid,
                                  tables, pools, cfg, backend=backend,
                                  ffn_apply=ffn_apply)
    b, c = tokens.shape
    flat = logits.reshape(b * c, logits.shape[-1])
    ctr = (counter[:, None] + jnp.arange(c)[None]).reshape(-1)
    rep = lambda a: jnp.repeat(a, c)     # (B,) lane params -> (B*C,)
    with jax.named_scope("sample"):
        pinned = sample_tokens(flat, rep(temperature), rep(top_k),
                               rep(seed), ctr, cfg.vocab_size,
                               use_top_k=use_top_k,
                               stochastic=stochastic).reshape(b, c)
        done = (eos_hits(pinned, eos_ids[:, None, :]) if use_eos
                else jnp.zeros(pinned.shape, jnp.bool_))
    return pinned, done, pools
