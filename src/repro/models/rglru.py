"""RecurrentGemma / Griffin hybrid [arXiv:2402.19427]: 12 x (rec, rec,
local-attn) blocks + 2 trailing recurrent layers = 38 layers (26:12).

TPU adaptation (DESIGN.md §2): the RG-LRU linear recurrence
``h_t = a_t h_{t-1} + sqrt(1 - a_t^2) (i_t * x_t)`` runs as a
jax.lax.associative_scan (log-depth parallel scan — the TPU-native
realization of the paper-family's sequential CUDA scan); decode uses the
O(1) single-step update. The causal depthwise conv (width 4) is expressed
as shift-and-multiply-accumulate, which shards trivially.
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


def init_recurrent(key, cfg: ArchConfig):
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    return {
        "ln": L.init_norm(cfg),
        "in_x": L.make_param(ks[0], (d, d), ("embed", "ff")),
        "in_gate": L.make_param(ks[1], (d, d), ("embed", "ff")),
        "conv_w": L.make_param(ks[2], (cfg.conv_width, d), ("conv", "ff")),
        "conv_b": L.zeros_param((d,), ("ff",)),
        "wa": L.make_param(ks[3], (d, d), ("ff", None)),
        "ba": L.Param(jnp.full((d,), 2.0, jnp.float32), ("ff",)),
        "wx": L.make_param(ks[4], (d, d), ("ff", None)),
        "bx": L.zeros_param((d,), ("ff",)),
        "lam": L.Param(jnp.full((d,), 0.7, jnp.float32), ("ff",)),
        "out": L.make_param(ks[5], (d, d), ("ff", "embed")),
        "ln_mlp": L.init_norm(cfg),
        "mlp": L.init_mlp(jax.random.fold_in(key, 7), cfg),
    }


def init_attn_layer(key, cfg: ArchConfig):
    k1, k2 = jax.random.split(key)
    return {
        "ln": L.init_norm(cfg),
        "attn": L.init_attention(k1, cfg),
        "ln_mlp": L.init_norm(cfg),
        "mlp": L.init_mlp(k2, cfg),
    }


def init(rng, cfg: ArchConfig):
    ke, kb, kt = jax.random.split(rng, 3)
    n_blocks = (cfg.n_layers - cfg.n_tail_layers) // len(cfg.block_pattern)
    bkeys = jax.random.split(kb, n_blocks)

    def one_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"rec1": init_recurrent(k1, cfg),
                "rec2": init_recurrent(k2, cfg),
                "attn": init_attn_layer(k3, cfg)}

    blocks = jax.vmap(one_block)(bkeys)
    tail = jax.vmap(lambda k: init_recurrent(k, cfg))(
        jax.random.split(kt, cfg.n_tail_layers))
    return {
        "embed": L.init_embed(ke, cfg),
        "blocks": L.stack_layer_params(blocks),
        "tail": L.stack_layer_params(tail),
        "final_norm": L.init_norm(cfg),
    }


def _causal_conv(x: Array, w: Array, b: Array, conv_state=None,
                 n_valid=None):
    """Depthwise causal conv via shifted adds. x (B,S,D); w (W,D).

    conv_state: (B, W-1, D) previous inputs for decode/streaming.
    ``n_valid`` (B,) marks the real length of a padded chunk (paged
    serving): the carried state is then the W-1 inputs *ending at the
    last real token* rather than the buffer tail.
    """
    width = w.shape[0]
    if conv_state is None:
        hist = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        hist = conv_state.astype(x.dtype)
    xp = jnp.concatenate([hist, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[width - 1 - i]
              for i in range(width))
    if n_valid is None:
        new_state = xp[:, -(width - 1):]
    else:
        # real inputs sit at xp rows [W-1, W-1 + n_valid); the W-1 rows
        # of context ending there are xp[n_valid : n_valid + W-1].
        idx = n_valid[:, None] + jnp.arange(width - 1)[None]
        idx3 = jnp.broadcast_to(idx[:, :, None],
                                (x.shape[0], width - 1, x.shape[2]))
        new_state = jnp.take_along_axis(xp, idx3, axis=1)
    return out + b, new_state


def rg_lru(x: Array, r_in: Array, p, cfg: ArchConfig, h0=None, mask=None):
    """RG-LRU over (B,S,D); h0 (B,D) initial state. Returns (y, h_last).

    Gate matmuls run in bf16 with sharded ("ff") outputs — the TP
    partitioner then emits reduce-scatter (X bytes) instead of a
    replicating all-reduce (2X) and the payload itself is half of fp32
    (§Perf hillclimb B). The recurrence stays fp32.

    ``mask`` (B, S) marks real tokens in a padded chunk (paged serving):
    padded positions get a := 1 and gated := 0, an exact identity step,
    so ``h_last`` is the hidden state at the last *real* token.
    """
    xf = x.astype(jnp.float32)
    ga = constrain(r_in @ L.cast(p["wa"], cfg), "batch", "seq", "ff")
    gx = constrain(r_in @ L.cast(p["wx"], cfg), "batch", "seq", "ff")
    # sigmoid in bf16 so the TP partial-sum collective carries bf16 (the
    # f32 convert must stay downstream of the nonlinearity); the decay
    # exponentiation and the scan itself remain fp32.
    r = jax.nn.sigmoid(ga + L.cast(p["ba"], cfg)).astype(jnp.float32)
    i = jax.nn.sigmoid(gx + L.cast(p["bx"], cfg)).astype(jnp.float32)
    log_a = -cfg.rglru_c * jax.nn.softplus(p["lam"]) * r
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i * xf)
    if mask is not None:
        m = mask[:, :, None]
        a = jnp.where(m, a, 1.0)
        gated = jnp.where(m, gated, 0.0)
    if h0 is not None:
        # fold the initial state in as a virtual first step
        a = jnp.concatenate([jnp.ones_like(a[:, :1]), a], axis=1)
        gated = jnp.concatenate([h0.astype(jnp.float32)[:, None], gated], 1)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
    if h0 is not None:
        h = h[:, 1:]
    return h.astype(x.dtype), h[:, -1]


def recurrent_block(p, x: Array, cfg: ArchConfig, phase: str,
                    state: Dict[str, Array] = None, n_valid=None):
    """Griffin recurrent layer + MLP residual. state: {"h","conv"}.

    ``n_valid`` (B,) freezes the carried conv/LRU state at the last
    real token of a padded chunk (paged serving); None is the dense
    path, bit-for-bit unchanged."""
    h = L.apply_norm(x, p["ln"], cfg, phase)
    bx = h @ L.cast(p["in_x"], cfg)
    bg = jax.nn.gelu(h @ L.cast(p["in_gate"], cfg))
    bx = constrain(bx, "batch", "seq", "ff")
    conv_state = None if state is None else state["conv"]
    bx, conv_new = _causal_conv(bx, L.cast(p["conv_w"], cfg),
                                L.cast(p["conv_b"], cfg), conv_state,
                                n_valid=n_valid)
    h0 = None if state is None else state["h"]
    mask = (None if n_valid is None
            else jnp.arange(x.shape[1])[None] < n_valid[:, None])
    y, h_last = rg_lru(bx, bx, p, cfg, h0, mask=mask)
    y = y * bg
    x = x + y @ L.cast(p["out"], cfg)
    hh = L.apply_norm(x, p["ln_mlp"], cfg, phase)
    x = x + L.apply_mlp(hh, p["mlp"], cfg)
    new_state = {"h": h_last.astype(jnp.float32),
                 "conv": conv_new.astype(jnp.float32)}
    return constrain(x, "batch", "seq", "embed"), new_state


def attn_block(p, x: Array, positions: Array, cfg: ArchConfig, phase: str):
    h = L.apply_norm(x, p["ln"], cfg, phase)
    x = x + L.apply_attention(p["attn"], h, positions, cfg, phase)
    hh = L.apply_norm(x, p["ln_mlp"], cfg, phase)
    x = x + L.apply_mlp(hh, p["mlp"], cfg)
    return constrain(x, "batch", "seq", "embed")


def forward(params, tokens: Array, cfg: ArchConfig, phase: str) -> Array:
    b, s = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, cfg)
    positions = jnp.arange(s)

    def block(x, bp):
        x, _ = recurrent_block(bp["rec1"], x, cfg, phase)
        x, _ = recurrent_block(bp["rec2"], x, cfg, phase)
        x = attn_block(bp["attn"], x, positions, cfg, phase)
        return x, None

    x, _ = jax.lax.scan(remat_wrap(block, cfg), x, params["blocks"])

    def tail(x, tp):
        x, _ = recurrent_block(tp, x, cfg, phase)
        return x, None

    x, _ = jax.lax.scan(remat_wrap(tail, cfg), x, params["tail"])
    x = L.apply_norm(x, params["final_norm"], cfg, phase)
    return L.lm_logits(params["embed"], x, cfg)


# -- serving ------------------------------------------------------------------


def _empty_rec_state(cfg: ArchConfig, b: int):
    return {"h": jnp.zeros((b, cfg.d_model), jnp.float32),
            "conv": jnp.zeros((b, cfg.conv_width - 1, cfg.d_model),
                              jnp.float32)}


def init_cache(cfg: ArchConfig, batch: int, length: int):
    n_blocks = (cfg.n_layers - cfg.n_tail_layers) // len(cfg.block_pattern)
    rec = _empty_rec_state(cfg, batch)
    kv = L.init_kv_cache(cfg, batch, length)
    block = {"rec1": rec, "rec2": rec, "attn": kv}
    cache = {
        "blocks": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_blocks,) + a.shape).copy(), block),
        "tail": jax.tree.map(
            lambda a: jnp.broadcast_to(
                a, (cfg.n_tail_layers,) + a.shape).copy(), rec),
        "pos": jnp.zeros((), jnp.int32),
    }
    return cache


REC_AXES = {"h": ("layers", "batch", "ff"),
            "conv": ("layers", "batch", None, "ff")}


def cache_axes(cfg: ArchConfig):
    kv_axes = {k: ("layers",) + v for k, v in L.KV_CACHE_AXES.items()}
    return {"blocks": {"rec1": dict(REC_AXES), "rec2": dict(REC_AXES),
                       "attn": kv_axes},
            "tail": dict(REC_AXES), "pos": ()}


def prefill(params, tokens: Array, cfg: ArchConfig, cache_len: int):
    b, s = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, cfg)
    positions = jnp.arange(s)
    t = min(cache_len, cfg.window) if cfg.window else cache_len

    def block(x, bp):
        x, st1 = recurrent_block(bp["rec1"], x, cfg, "serve",
                                 _empty_rec_state(cfg, b))
        x, st2 = recurrent_block(bp["rec2"], x, cfg, "serve",
                                 _empty_rec_state(cfg, b))
        h = L.apply_norm(x, bp["attn"]["ln"], cfg, "serve")
        q, k, v = L._project_qkv(bp["attn"]["attn"], h, cfg)
        q = L.apply_rope(q, positions, cfg)
        k = L.apply_rope(k, positions, cfg)
        ctx = L.attend_dense(q, k, v, positions, positions, cfg, "serve")
        x = x + jnp.einsum("bshk,hkd->bsd", ctx,
                           L.cast(bp["attn"]["attn"]["wo"], cfg))
        hh = L.apply_norm(x, bp["attn"]["ln_mlp"], cfg, "serve")
        x = x + L.apply_mlp(hh, bp["attn"]["mlp"], cfg)
        # rolling window cache
        kk = k[:, -t:] if s >= t else jnp.pad(k, ((0, 0), (0, t - s), (0, 0), (0, 0)))
        vv = v[:, -t:] if s >= t else jnp.pad(v, ((0, 0), (0, t - s), (0, 0), (0, 0)))
        pp = positions[-t:] if s >= t else jnp.pad(positions, (0, t - s),
                                                   constant_values=2**30)
        shift = jnp.mod(s, t) if s >= t else 0
        kv_cache = {"k": jnp.roll(kk, shift, 1).astype(jnp.dtype(cfg.dtype)),
                    "v": jnp.roll(vv, shift, 1).astype(jnp.dtype(cfg.dtype)),
                    "pos": jnp.roll(pp, shift, 0).astype(jnp.int32)}
        return x, {"rec1": st1, "rec2": st2, "attn": kv_cache}

    x, blocks_cache = jax.lax.scan(block, x, params["blocks"])

    def tail(x, tp):
        x, st = recurrent_block(tp, x, cfg, "serve", _empty_rec_state(cfg, b))
        return x, st

    x, tail_cache = jax.lax.scan(tail, x, params["tail"])
    x = L.apply_norm(x, params["final_norm"], cfg, "serve")
    logits = L.lm_logits(params["embed"], x[:, -1:], cfg)
    return logits, {"blocks": blocks_cache, "tail": tail_cache,
                    "pos": jnp.asarray(s, jnp.int32)}


def decode_step(params, cache, token: Array, pos: Array, cfg: ArchConfig):
    x = L.embed_tokens(params["embed"], token[:, None], cfg)

    def block(x, scanned):
        bp, c = scanned
        x, st1 = recurrent_block(bp["rec1"], x, cfg, "serve", c["rec1"])
        x, st2 = recurrent_block(bp["rec2"], x, cfg, "serve", c["rec2"])
        h = L.apply_norm(x, bp["attn"]["ln"], cfg, "serve")
        attn_out, kv = L.decode_attend(bp["attn"]["attn"], h, c["attn"],
                                       pos, cfg)
        x = x + attn_out
        hh = L.apply_norm(x, bp["attn"]["ln_mlp"], cfg, "serve")
        x = x + L.apply_mlp(hh, bp["attn"]["mlp"], cfg)
        return x, {"rec1": st1, "rec2": st2, "attn": kv}

    x, blocks_cache = jax.lax.scan(block, x, (params["blocks"],
                                              cache["blocks"]))

    def tail(x, scanned):
        tp, c = scanned
        x, st = recurrent_block(tp, x, cfg, "serve", c)
        return x, st

    x, tail_cache = jax.lax.scan(tail, x, (params["tail"], cache["tail"]))
    x = L.apply_norm(x, params["final_norm"], cfg, "serve")
    logits = L.lm_logits(params["embed"], x, cfg)
    return logits[:, 0], {"blocks": blocks_cache, "tail": tail_cache,
                          "pos": pos + 1}


# -- paged serving (paged KV for attention blocks + state slots) --------------
#
# The hybrid composes both state pools: each (rec, rec, attn) block's
# attention layer writes ref-counted KV pages (pool layer index ==
# block index, ``kv_layers = n_blocks``), while the RG-LRU hidden +
# causal-conv state of every recurrent layer lives in per-sequence
# slots. The attention blocks replicate the dense family's paged
# pattern (write the chunk's K/V, then ``paged_attend``); pages are
# append-only, so serving is only allowed while ``max_seq_len <=
# cfg.window`` — the window never binds and the paged computation is
# the windowed oracle's, bit for bit (the engine enforces this).


def _n_blocks(cfg: ArchConfig) -> int:
    return (cfg.n_layers - cfg.n_tail_layers) // len(cfg.block_pattern)


def sequence_state_spec(cfg: ArchConfig):
    from repro.models.state import SequenceStateSpec, sds
    d, w = cfg.d_model, cfg.conv_width
    nb, nt = _n_blocks(cfg), cfg.n_tail_layers

    def rec(n):
        return {"h": sds((n, d), jnp.float32),
                "conv": sds((n, w - 1, d), jnp.float32)}

    def rec_axes():
        return {"h": ("layers", "ff"), "conv": ("layers", None, "ff")}

    return SequenceStateSpec(
        family="hybrid", kv_layers=nb,
        slot_shapes={"blocks": {"rec1": rec(nb), "rec2": rec(nb)},
                     "tail": rec(nt)},
        slot_axes={"blocks": {"rec1": rec_axes(), "rec2": rec_axes()},
                   "tail": rec_axes()},
        # prefix hits need BOTH an aligned page match and a state
        # checkpoint at the same boundary (the scheduler takes the min);
        # spec-decode would need LRU/conv state rewind — unsupported.
        supports_prefix_cache=True, supports_spec_decode=False,
        supports_cow_fork=False, window=cfg.window)


def _stack_states(lst, empty):
    """List of per-layer {"h","conv"} -> (B, n, ...) stacked tree."""
    if not lst:
        return empty
    return jax.tree.map(lambda *xs: jnp.stack(xs, 1), *lst)


def _forward_paged(params, tokens, positions, n_valid, kv_len, refs, state,
                   cfg: ArchConfig, *, causal, backend):
    """Run C tokens per lane through recurrent slots + paged attention.

    Mirrors transformer._paged_forward for the attention layers (write
    the chunk's K/V before attending, padded-tail writes routed to the
    null page) and threads each lane's gathered slot states through the
    recurrent layers with ``n_valid`` masking. Returns
    (logits (B,C,V), new state dict with the same keys as ``state``).
    """
    from repro.serve.kv_cache import (PAGED_KV_AXES, slots_for_positions,
                                      write_tokens)
    sid = refs["slots"]
    rows = jax.tree.map(lambda s: s[sid], state["slots"])
    x = L.embed_tokens(params["embed"], tokens, cfg)
    q_start = positions[:, 0]
    nb, nt = _n_blocks(cfg), cfg.n_tail_layers
    has_pages = nb > 0
    if has_pages:
        pk = constrain(state["k"], *PAGED_KV_AXES["k"])
        pv = constrain(state["v"], *PAGED_KV_AXES["v"])
        tables = refs["tables"]
        block_size = pk.shape[3]
        block_ids, offsets = slots_for_positions(positions, block_size,
                                                 tables)
        write_end = (q_start + n_valid)[:, None]
        block_ids = jnp.where(positions < write_end, block_ids, 0)
    new1, new2 = [], []
    for i in range(nb):
        bp = jax.tree.map(lambda a: a[i], params["blocks"])
        st1 = jax.tree.map(lambda a: a[:, i], rows["blocks"]["rec1"])
        st2 = jax.tree.map(lambda a: a[:, i], rows["blocks"]["rec2"])
        x, st1n = recurrent_block(bp["rec1"], x, cfg, "serve", st1,
                                  n_valid=n_valid)
        x, st2n = recurrent_block(bp["rec2"], x, cfg, "serve", st2,
                                  n_valid=n_valid)
        h = L.apply_norm(x, bp["attn"]["ln"], cfg, "serve")
        q, k, v = L._project_qkv(bp["attn"]["attn"], h, cfg)
        q = L.apply_rope(q, positions, cfg)
        k = L.apply_rope(k, positions, cfg)
        pk = pk.at[i].set(write_tokens(pk[i], L.kv_quant(k, cfg),
                                       block_ids, offsets))
        pv = pv.at[i].set(write_tokens(pv[i], L.kv_quant(v, cfg),
                                       block_ids, offsets))
        ctx = L.paged_attend(q, pk[i], pv[i], tables, q_start, kv_len,
                             cfg, causal=causal, backend=backend)
        x = x + jnp.einsum("bshk,hkd->bsd", ctx,
                           L.cast(bp["attn"]["attn"]["wo"], cfg))
        hh = L.apply_norm(x, bp["attn"]["ln_mlp"], cfg, "serve")
        x = x + L.apply_mlp(hh, bp["attn"]["mlp"], cfg)
        x = constrain(x, "batch", "seq", "embed")
        new1.append(st1n)
        new2.append(st2n)
    newt = []
    for i in range(nt):
        tp = jax.tree.map(lambda a: a[i], params["tail"])
        stt = jax.tree.map(lambda a: a[:, i], rows["tail"])
        x, stn = recurrent_block(tp, x, cfg, "serve", stt, n_valid=n_valid)
        newt.append(stn)
    x = L.apply_norm(x, params["final_norm"], cfg, "serve")
    logits = L.lm_logits(params["embed"], x, cfg)
    new_rows = {"blocks": {
                    "rec1": _stack_states(new1, rows["blocks"]["rec1"]),
                    "rec2": _stack_states(new2, rows["blocks"]["rec2"])},
                "tail": _stack_states(newt, rows["tail"])}
    slots = jax.tree.map(
        lambda s, r: s.at[sid].set(r.astype(s.dtype)),
        state["slots"], new_rows)
    out = {"slots": slots}
    if has_pages:
        out["k"], out["v"] = pk, pv
    return logits, out


def prefill_paged(params, tokens: Array, q_start: Array, n_valid: Array,
                  refs, state, cfg: ArchConfig, *, backend=None):
    """One chunked-prefill step: advance slots by ``n_valid`` real
    tokens and write the chunk's attention K/V. Returns
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
    variant for the sampling/eos contract). Pages and slot rows both
    ride the scan carry; slots are gathered/scattered once per horizon.
    """
    from repro.serve.sampling import eos_hits, sample_tokens
    sid = refs["slots"]
    rows0 = jax.tree.map(lambda s: s[sid], state["slots"])
    pages0 = {k: state[k] for k in ("k", "v") if k in state}

    def step(carry, i):
        pages, rows, tok, p = carry
        # the gathered rows act as a B-slot pool with identity slot ids,
        # so the single-step core is shared verbatim with decode_step
        ident = {"slots": jnp.arange(tok.shape[0], dtype=jnp.int32),
                 "tables": refs.get("tables")}
        logits, new = _forward_paged(
            params, tok[:, None], p[:, None], jnp.ones_like(p), p + 1,
            ident, dict(pages, slots=rows), cfg, causal=False,
            backend=backend)
        nxt = sample_tokens(logits[:, 0], temperature, top_k, seed,
                            counter + i, cfg.vocab_size,
                            use_top_k=use_top_k, stochastic=stochastic)
        done = (eos_hits(nxt, eos_ids) if use_eos
                else jnp.zeros(nxt.shape, jnp.bool_))
        pages = {k: new[k] for k in pages}
        return (pages, new["slots"], nxt, p + 1), (nxt, done)

    (pages, rows, _, _), (toks, done) = jax.lax.scan(
        step, (pages0, rows0, token, pos),
        jnp.arange(num_steps, dtype=jnp.int32))
    slots = jax.tree.map(lambda s, r: s.at[sid].set(r.astype(s.dtype)),
                         state["slots"], rows)
    out = dict(pages, slots=slots)
    return jnp.transpose(toks), jnp.transpose(done), out
