"""``repro.ops`` — the unified reference↔Pallas op backend.

Every softmax / layernorm / rmsnorm / attention implementation in the
repo is obtained here, keyed by ``(op, mode, backend)``:

  * **mode** picks the approximation (``exact``, ``sole``, ``softermax``,
    ``ibert``) — the SOLE technique and its baselines stay first-class,
    swappable features;
  * **backend** picks the execution engine (``reference`` pure jnp, or
    ``pallas`` fused kernels), resolved per-op from
    ``ArchConfig.ops_backend`` plus platform autodetect — the same model
    code compiles kernels on TPU and interprets them in CPU tests.

Typical model-code usage::

    from repro import ops
    probs = ops.softmax_fn(mode, cfg)(logits, mask=mask)
    h     = ops.layernorm_fn(mode, cfg)(x, gamma, beta)
    x, h  = ops.residual_norm_fn("layernorm", mode, cfg)(x, r, gamma, beta)

``resolve(op, mode, backend)`` is the strict, explicit entry point;
the ``*_fn`` helpers add the config-driven backend resolution (``auto``
falls back to ``reference`` when a combination has no kernel — the mode
is never changed, only the execution engine; a forced backend with no
kernel raises).
"""
from __future__ import annotations

from typing import Callable, Optional

from repro.ops.interpret import pallas_compiles, resolve_interpret
from repro.ops.registry import (ATTN_MODES, BACKENDS, MATMUL_MODES,
                                MODES_BY_OP, NORM_MODES, OPS, SOFTMAX_MODES,
                                backend_for, default_backend, is_registered,
                                register, resolve)
from repro.ops import reference  # registers the reference backend
from repro.ops import pallas     # registers the pallas backend
from repro.ops.reference import snap_logits

__all__ = [
    "OPS", "BACKENDS", "SOFTMAX_MODES", "NORM_MODES", "ATTN_MODES",
    "MATMUL_MODES", "MODES_BY_OP", "register", "resolve", "is_registered",
    "backend_for", "default_backend", "pallas_compiles", "resolve_interpret",
    "snap_logits", "softmax_fn", "layernorm_fn", "rmsnorm_fn",
    "residual_norm_fn", "residual_norm_q_fn", "matmul_fn",
    "flash_attention_fn", "paged_attention_fn",
    "reference", "pallas",
]


def softmax_fn(mode: str, cfg=None,
               backend: Optional[str] = None) -> Callable:
    """softmax(x, axis=-1, mask=None, ...) for the given mode."""
    return resolve("softmax", mode, backend_for(cfg, "softmax", mode,
                                                backend))


def layernorm_fn(mode: str, cfg=None,
                 backend: Optional[str] = None) -> Callable:
    """layernorm(x, gamma, beta, ...) for the given mode."""
    return resolve("layernorm", mode, backend_for(cfg, "layernorm", mode,
                                                  backend))


def rmsnorm_fn(mode: str, cfg=None,
               backend: Optional[str] = None) -> Callable:
    """rmsnorm(x, gamma, ...) for the given mode."""
    return resolve("rmsnorm", mode, backend_for(cfg, "rmsnorm", mode,
                                                backend))


def residual_norm_fn(kind: str, mode: str, cfg=None,
                     backend: Optional[str] = None) -> Callable:
    """(x, r, gamma[, beta]) -> (x + r, norm(x + r)), fused when the
    backend has a kernel for it (SOLE AILayerNorm on the serve path)."""
    if kind not in ("layernorm", "rmsnorm"):
        raise ValueError(f"unknown norm kind {kind!r}")
    op = f"residual_{kind}"
    return resolve(op, mode, backend_for(cfg, op, mode, backend))


def residual_norm_q_fn(kind: str, mode: str, cfg=None,
                       backend: Optional[str] = None) -> Callable:
    """(x, r, gamma[, beta]) -> (x + r, (int8 codes, per-row scale)) —
    the residual_norm twin whose normalized output leaves as dynamic
    per-token int8, feeding the next w8a8 matmul directly."""
    if kind not in ("layernorm", "rmsnorm"):
        raise ValueError(f"unknown norm kind {kind!r}")
    op = f"residual_{kind}_q"
    return resolve(op, mode, backend_for(cfg, op, mode, backend))


def matmul_fn(mode: str, cfg=None,
              backend: Optional[str] = None) -> Callable:
    """(x, w, *, n_contract) serve-path matmul at the configured
    quantization level (exact | w8a16 | w8a8)."""
    return resolve("matmul", mode, backend_for(cfg, "matmul", mode, backend))


def flash_attention_fn(mode: str, cfg=None,
                       backend: Optional[str] = None) -> Callable:
    """(q, k, v, *, causal, ...) fused-softmax attention, model layout."""
    return resolve("flash_attention", mode,
                   backend_for(cfg, "flash_attention", mode, backend))


def paged_attention_fn(mode: str, cfg=None,
                       backend: Optional[str] = None) -> Callable:
    """(q, pools, tables, q_start, kv_len, *, causal, ...) paged-KV
    attention for the continuous-batching serve engine."""
    return resolve("paged_attention", mode,
                   backend_for(cfg, "paged_attention", mode, backend))
