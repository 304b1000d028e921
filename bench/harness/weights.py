"""Seeded bf16 weights of a dense decoder LM, made on the device in one
jitted call, in the tree the served path reads.

Projections are N(0, 0.02); the LM head N(0, 1/d_model) so logits have
unit scale; norm gains 1 + N(0, 0.1), norm and QKV biases N(0, 0.02).
Non-trivial gains and biases make the comparison cover those paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shapes(model: dict) -> dict:
    """Leaf name -> (shape, kind) of the served tree."""
    d, f = model["d_model"], model["d_ff"]
    h, kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    n, vp = model["n_layers"], -(-model["vocab_size"] // 128) * 128

    def norm(prefix, lead):
        out = {f"{prefix}.g": (lead + (d,), "gain")}
        if model["norm_kind"] == "layernorm":
            out[f"{prefix}.b"] = (lead + (d,), "bias")
        return out

    leaves = {"embed.table": ((vp, d), "proj"),
              "embed.head": ((d, vp), "head")}
    for ln in ("ln1", "ln2"):
        leaves.update(norm(f"layers.{ln}", (n,)))
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
    leaves.update(norm("final_norm", ()))
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
    """The bf16 weight tree for ``seed`` (any non-negative integer)."""
    spec = tuple((path, shape, kind, model["d_model"])
                 for path, (shape, kind) in shapes(model).items())
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    return _make(key, spec)
