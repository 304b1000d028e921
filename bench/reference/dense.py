"""The dense family: a decoder LM of the qwen2 / stablelm shape, token
embedding, pre-norm blocks of RoPE attention with QKV bias and a SwiGLU
MLP, a final norm and an untied LM head, with softmax and norms exact or
SOLE as the configuration's ``softmax_mode`` / ``norm_mode`` say.

A configuration names its family module under ``"reference"``, and the
harness takes from it all it knows of the model:

* the weight tree the served path reads (``shapes``, ``make``);
* the work a token does (``work``), which ``harness/work.py`` counts;
* the names of the device scopes its forward runs under (``SCOPES``),
  by which ``harness/scopes.py`` reduces a trace;
* the plain reference (``logit_gaps``), which the output check runs.

The reference states the arithmetic that the served path defines, in plain
``jax.numpy``, and imports nothing of the program:

* Storage is bfloat16 wherever the configuration's dtype puts a tensor
  in memory: the residual stream, norm outputs, q/k/v (and so the KV
  cache), the attention context and every projection output. Matmuls
  multiply those bf16 values in float32 at ``HIGHEST`` precision and
  round the result to bf16 once.
* Exact norms are LayerNorm (epsilon 1e-5) or RMSNorm (1e-6) in
  float32; exact attention is softmax over the causal keys in float32.
* SOLE norms are AILayerNorm / AIRMSNorm on PTF-quantized 8-bit codes
  (DynamicCompress squares, per-channel power-of-two factors, integer
  sums, rsqrt, affine). PTF calibration is per token: each row's own
  channel magnitudes set its scale and factors, so a token's output
  never depends on what else shares its batch.
* SOLE attention is E2Softmax run online over the KV pages in order (paper
  Alg. 1): logits snapped to an int8 grid relative to the running max,
  4-bit Log2Exp weights, the running sum and accumulator rescaled by
  the quantized Correction at every page boundary, and ALDivision on
  the final sum. A two-pass E2Softmax differs from this by design (by
  about 0.26 rel L2 on the logits at these widths), so the reference
  follows the online order of the pages the cache holds.
* Logits leave the head in float32.

Departures from the published models, shared with the program under
test: rotate-half RoPE over the whole head (stablelm-2 rotates 25%), an
untied LM head (qwen2 ties it), and no epsilon in SOLE's AILayerNorm
(it floors the variance at one code step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BF16 = jnp.bfloat16
NEG = -1e30
INV_LN2 = 1.4375          # Log2Exp's shift-add 1/ln2
ALDIV_BIAS = 1.636        # ALDivision's unbiasing constant
LOGIT_INT8_SCALE = 12.0 / 127.0
ROW_BLOCK = 256           # attention rows per block
T_BUCKET = 512            # sequences are padded to a multiple of this
# the program's paged forward, decode horizon and verify step
# (models/transformer.py) run each sublayer under one of these
SCOPES = ("embed", "norm", "qkv", "kv_write", "paged_attention",
          "attn_out", "mlp", "lm_head", "sample")


def shapes(model: dict) -> dict:
    """Leaf name -> (shape, kind) of the served tree."""
    d, f = model["d_model"], model["d_ff"]
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    n, vp = model["n_layers"], -(-model["vocab_size"] // 128) * 128

    def norm_leaves(prefix, lead):
        out = {f"{prefix}.g": (lead + (d,), "gain")}
        if model["norm_kind"] == "layernorm":
            out[f"{prefix}.b"] = (lead + (d,), "bias")
        return out

    leaves = {"embed.table": ((vp, d), "proj"),
              "embed.head": ((d, vp), "head")}
    for ln in ("ln1", "ln2"):
        leaves.update(norm_leaves(f"layers.{ln}", (n,)))
    leaves.update({
        "layers.attn.wq": ((n, d, h, hd), "proj"),
        "layers.attn.wk": ((n, d, kv, hd), "proj"),
        "layers.attn.wv": ((n, d, kv, hd), "proj"),
        "layers.attn.wo": ((n, h, hd, d), "proj"),
        "layers.mlp.gate": ((n, d, f), "proj"),
        "layers.mlp.up": ((n, d, f), "proj"),
        "layers.mlp.down": ((n, f, d), "proj"),
    })
    if model["qkv_bias"]:
        leaves.update({"layers.attn.bq": ((n, h, hd), "bias"),
                       "layers.attn.bk": ((n, kv, hd), "bias"),
                       "layers.attn.bv": ((n, kv, hd), "bias")})
    leaves.update(norm_leaves("final_norm", ()))
    return leaves


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        *parents, leaf = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = value
    return tree


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(key, spec):
    flat = {}
    for i, (path, shape, kind, d) in enumerate(spec):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "gain":
            v = 1.0 + 0.1 * z
        elif kind == "head":
            v = z * d ** -0.5
        else:
            v = 0.02 * z
        flat[path] = v.astype(jnp.bfloat16)
    return _nest(flat)


def make(model: dict, seed: int) -> dict:
    """The bf16 weight tree for ``seed`` (any non-negative integer), made
    on the device in one jitted call. Projections are N(0, 0.02); the LM
    head N(0, 1/d_model) so logits have unit scale; norm gains
    1 + N(0, 0.1), norm and QKV biases N(0, 0.02). Non-trivial gains and
    biases make the comparison cover those paths."""
    spec = tuple((path, shape, kind, model["d_model"])
                 for path, (shape, kind) in shapes(model).items())
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    return _make(key, spec)


def work(model: dict) -> dict:
    """The work a token does, in the terms ``harness/work.py`` counts:
    every layer is an attention layer over the whole context, a norm
    before its attention and its MLP, and one after the last."""
    d, f = model["d_model"], model["d_ff"]
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    n = model["n_layers"]
    return {"block_params": n * (d * (h + 2 * kv) * hd + h * hd * d
                                 + 3 * d * f),
            "attention": [(h, kv, hd, 0)] * n,
            "norms": 2 * n + 1,
            "width": d,
            "head_params": d * model["vocab_size"]}


def _bf16(x):
    return x.astype(BF16).astype(jnp.float32)


def _mm(a, w, spec):
    return _bf16(jnp.einsum(spec, a, w.astype(jnp.float32),
                            precision=HIGHEST,
                            preferred_element_type=jnp.float32))


def ptf_norm(h, gamma, beta, *, rms: bool):
    """SOLE norm of bf16 rows ``h`` (T, C) with per-row PTF calibration.
    Returns float32 (T, C)."""
    hb = h.astype(BF16)
    # calibration, in the activation dtype: widest channel / 127 sets the
    # shared scale (over 2^3), each channel the least power of two >= it
    ideal = jnp.maximum(jnp.abs(hb), 1e-8) / 127.0
    scale = jnp.max(ideal, axis=-1, keepdims=True) / 8.0
    alpha = jnp.clip(jnp.ceil(jnp.log2(ideal / scale) - 1e-6), 0, 3)
    alpha = alpha.astype(jnp.int32)
    denom = scale.astype(jnp.float32) * jnp.exp2(alpha.astype(jnp.float32))
    xi = jnp.clip(jnp.round(hb.astype(jnp.float32) / denom), -128, 127)
    xi = xi.astype(jnp.int32)
    # DynamicCompress square: 4-bit code y of |x| at shift 2 or 4
    a = jnp.abs(xi)
    big = (a >= 64).astype(jnp.int32)
    y = jnp.where(big == 1, a >> 4, a >> 2)
    sq = (y * y + y) << (4 * big)
    xs = xi << alpha
    c = h.shape[-1]
    ex2 = jnp.sum(sq << (2 * alpha), axis=-1, keepdims=True)
    g = gamma.astype(jnp.float32)
    if rms:
        ms = jnp.maximum(ex2.astype(jnp.float32) * 16.0 / c, 1.0)
        return g * xs.astype(jnp.float32) * jax.lax.rsqrt(ms)
    ex = jnp.sum(xs, axis=-1, keepdims=True)
    mu = ex.astype(jnp.float32) / c
    var = jnp.maximum(ex2.astype(jnp.float32) * 16.0 / c - mu * mu, 1.0)
    return (g * jax.lax.rsqrt(var) * (xs.astype(jnp.float32) - mu)
            + beta.astype(jnp.float32))


def exact_norm(h, gamma, beta, *, rms: bool):
    """LayerNorm (eps 1e-5) or RMSNorm (eps 1e-6) of rows h, float32."""
    x = h.astype(jnp.float32)
    g = gamma.astype(jnp.float32)
    if rms:
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * g
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + beta.astype(
        jnp.float32)


def norm(model, h, p):
    fn = ptf_norm if model["norm_mode"] == "sole" else exact_norm
    return _bf16(fn(h, p["g"], p.get("b"),
                    rms=model["norm_kind"] == "rmsnorm"))


def rope(x, positions, theta: float):
    """Rotate-half RoPE over the whole head; x (T, heads, hd)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return _bf16(jnp.concatenate([x1 * cos - x2 * sin,
                                  x1 * sin + x2 * cos], -1))


def exact_attention(q, k, v):
    """Causal softmax attention, float32. q (T, H, hd), k/v (T, KV, hd)."""
    t, h, hd = q.shape
    group = h // k.shape[1]
    kq = jnp.repeat(k, group, axis=1)
    vq = jnp.repeat(v, group, axis=1)
    cols = jnp.arange(t)

    def block(r0):
        rows = r0 + jnp.arange(ROW_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, r0, ROW_BLOCK, 0) * hd ** -0.5
        logits = jnp.einsum("rhd,thd->hrt", qb, kq, precision=HIGHEST,
                            preferred_element_type=jnp.float32)
        mask = cols[None, None, :] <= rows[None, :, None]
        p = jax.nn.softmax(jnp.where(mask, logits, NEG), axis=-1)
        out = jnp.einsum("hrt,thd->hrd", p, vq, precision=HIGHEST,
                         preferred_element_type=jnp.float32)
        return jnp.transpose(out, (1, 0, 2))

    return jax.lax.map(block, jnp.arange(0, t, ROW_BLOCK)).reshape(t, h, hd)


def e2softmax_attention(q, k, v, *, page: int, exp_bits: int,
                        logit_int8: bool):
    """Causal attention of T rows, E2Softmax online over pages of
    ``page`` keys. q (T, H, hd), k/v (T, KV, hd), bf16-valued float32.
    Returns the float32 context (T, H, hd)."""
    t, h, hd = q.shape
    kvh = k.shape[1]
    group = h // kvh
    kq = jnp.repeat(k, group, axis=1)              # q head i -> kv i//g
    vq = jnp.repeat(v, group, axis=1)
    qs = q * (hd ** -0.5)
    nb = t // page
    kmax = float(2 ** exp_bits - 1)
    smax = float(2 ** (exp_bits + 2) - 1)
    cols = jnp.arange(t)

    def block(r0):
        rows = r0 + jnp.arange(ROW_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(qs, r0, ROW_BLOCK, 0)
        logits = jnp.einsum("rhd,thd->hrt", qb, kq, precision=HIGHEST,
                            preferred_element_type=jnp.float32)
        mask = (cols[None, :] <= rows[:, None]).reshape(ROW_BLOCK, nb, page)
        lp = jnp.where(mask, logits.reshape(h, ROW_BLOCK, nb, page), NEG)
        m_new = jax.lax.cummax(jnp.max(lp, axis=-1), axis=2)
        m_prev = jnp.concatenate(
            [jnp.full(m_new.shape[:2] + (1,), NEG, jnp.float32),
             m_new[..., :-1]], axis=-1)
        dm = lp - m_new[..., None]
        if logit_int8:
            dm = jnp.clip(jnp.round(dm / LOGIT_INT8_SCALE), -127, 0) \
                * LOGIT_INT8_SCALE
        kcode = jnp.clip(jnp.round(-dm * INV_LN2), 0.0, kmax)
        w = jnp.where(mask, jnp.exp2(-kcode), 0.0)
        # Correction at page j rescales everything before it by
        # 2^-sub_j; page j's weights end scaled by the later ones.
        sub = jnp.clip(jnp.round(-(m_prev - m_new) * INV_LN2), 0.0, smax)
        later = jnp.cumsum(sub[..., ::-1], axis=-1)[..., ::-1] - sub
        scale = jnp.exp2(-later)
        s = jnp.sum(jnp.sum(w, axis=-1) * scale, axis=-1)
        wv = (w * scale[..., None]).reshape(h, ROW_BLOCK, t)
        acc = jnp.einsum("hrt,thd->hrd", wv, vq, precision=HIGHEST,
                         preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 2.0 ** -30)
        mant, expo = jnp.frexp(s)
        factor = jnp.where(mant >= 0.75, ALDIV_BIAS - 0.5, ALDIV_BIAS)
        out = acc * (jnp.exp2(-expo.astype(jnp.float32)) * factor)[..., None]
        return jnp.transpose(out, (1, 0, 2))        # (R, H, hd)

    starts = jnp.arange(0, t, ROW_BLOCK)
    return jax.lax.map(block, starts).reshape(t, h, hd)


def _layer(model, x, pending, lw, positions):
    """One block. ``x`` is the residual stream (bf16-valued) and
    ``pending`` the previous block's MLP output (zeros before the first
    block), added into this block's first norm as the served path
    does. Returns the new stream and this block's MLP output."""
    x = _bf16(x + pending)
    h = norm(model, x, lw["ln1"])
    at = lw["attn"]
    q = _mm(h, at["wq"], "td,dhk->thk")
    k = _mm(h, at["wk"], "td,dhk->thk")
    v = _mm(h, at["wv"], "td,dhk->thk")
    if model["qkv_bias"]:
        q = _bf16(q + at["bq"].astype(jnp.float32))
        k = _bf16(k + at["bk"].astype(jnp.float32))
        v = _bf16(v + at["bv"].astype(jnp.float32))
    q = rope(q, positions, model["rope_theta"])
    k = rope(k, positions, model["rope_theta"])
    if model["softmax_mode"] == "sole":
        ctx = e2softmax_attention(
            q, k, v, page=model["block_size"], exp_bits=model["exp_bits"],
            logit_int8=model["logit_int8"])
    else:
        ctx = exact_attention(q, k, v)
    x = _bf16(x + _mm(_bf16(ctx), at["wo"], "thk,hkd->td"))
    h = norm(model, x, lw["ln2"])
    mlp = lw["mlp"]
    gate = _mm(h, mlp["gate"], "td,df->tf")
    up = _mm(h, mlp["up"], "td,df->tf")
    act = _bf16(_bf16(jax.nn.silu(gate.astype(BF16)).astype(jnp.float32))
                * up)
    return x, _mm(act, mlp["down"], "tf,fd->td")


@functools.partial(jax.jit, static_argnames=("model_items",))
def _hidden(weights, tokens, model_items):
    """Final-norm output (T, D), float32, for padded tokens (T,)."""
    model = dict(model_items)
    t = tokens.shape[0]
    positions = jnp.arange(t, dtype=jnp.int32)
    x = _bf16(jnp.take(weights["embed"]["table"], tokens, axis=0)
              .astype(jnp.float32))

    def body(carry, lw):
        return _layer(model, *carry, lw, positions), None

    (x, pending), _ = jax.lax.scan(body, (x, jnp.zeros_like(x)),
                                   weights["layers"])
    return norm(model, _bf16(x + pending), weights["final_norm"])


@functools.partial(jax.jit, static_argnames=("vocab",))
def _best_and_picked(h_rows, head, picks, vocab):
    """Best logit of each row of h, and the logits of ``picks`` (P, R)."""
    logits = jnp.einsum("rd,dv->rv", _bf16(h_rows), head.astype(jnp.float32),
                        precision=HIGHEST,
                        preferred_element_type=jnp.float32)[:, :vocab]
    picked = jax.vmap(lambda p: jnp.take_along_axis(
        logits, p[:, None], axis=-1)[:, 0])(picks)
    return jnp.max(logits, axis=-1), picked


def model_items(model: dict) -> tuple:
    keys = ("norm_kind", "norm_mode", "softmax_mode", "qkv_bias",
            "rope_theta", "block_size", "exp_bits", "logit_int8")
    return tuple((k, model[k]) for k in keys)


def hidden_states(weights, model: dict, tokens: np.ndarray):
    """Final-norm rows for ``tokens`` (padded to a bucket inside)."""
    t = len(tokens)
    tp = -(-t // T_BUCKET) * T_BUCKET
    padded = np.zeros(tp, np.int32)
    padded[:t] = tokens
    with jax.default_matmul_precision("highest"):
        return _hidden(weights, jnp.asarray(padded), model_items(model))


def logit_gaps(weights, model: dict, tokens: np.ndarray, first: int,
               picks_by_name: dict, rows_per_call: int = 256) -> dict:
    """The reference's next-token logits at positions first ..
    len(tokens) - 2; returns, for each named list of tokens picked at
    those positions, best logit minus the picked token's logit."""
    h = hidden_states(weights, model, tokens)
    n = len(tokens) - 1 - first
    names = list(picks_by_name)
    picks = np.stack([np.asarray(picks_by_name[k], np.int32)[:n]
                      for k in names])
    out = np.zeros((len(names), n), np.float64)
    head = weights["embed"]["head"]
    for r0 in range(0, n, rows_per_call):
        m = min(rows_per_call, n - r0)
        idx = np.zeros(rows_per_call, np.int32)
        idx[:m] = first + r0 + np.arange(m)
        pk = np.zeros((len(names), rows_per_call), np.int32)
        pk[:, :m] = picks[:, r0:r0 + m]
        best, picked = _best_and_picked(h[jnp.asarray(idx)], head,
                                        jnp.asarray(pk),
                                        vocab=model["vocab_size"])
        out[:, r0:r0 + m] = (np.asarray(best, np.float64)[None]
                             - np.asarray(picked, np.float64))[:, :m]
    return dict(zip(names, out))
