"""The op registry: one swappable surface for every softmax / norm /
attention implementation in the repo.

Each entry is keyed ``(op, mode, backend)``:

  op       what the model asks for — ``softmax``, ``layernorm``,
           ``rmsnorm``, ``residual_layernorm``, ``residual_rmsnorm``,
           ``flash_attention``, ``paged_attention``
  mode     the approximation — ``exact``, ``sole`` (the paper),
           ``softermax``, ``ibert``
  backend  the execution engine — ``reference`` (pure jnp, the oracle)
           or ``pallas`` (fused TPU kernels; interpret mode off-TPU)

Model and serve code never imports ``core.nonlin`` or ``repro.kernels``
directly; it calls :func:`resolve` (or the typed helpers in
``repro.ops``) and gets back a callable. A new kernel is a one-line
:func:`register` call, not a new special-case call path.

Resolution order for the backend (see :func:`backend_for`):

  1. an explicit ``backend=`` argument;
  2. ``ArchConfig.ops_backend`` when not ``"auto"``;
  3. platform autodetect: ``pallas`` when compiled Pallas is available
     (TPU) *and* the combination is registered, else ``reference``.

Only step 3 chooses: a backend demanded by steps 1 or 2 is used as
demanded, so a combination with no kernel raises instead of silently
running (and being measured as) the reference engine. :func:`resolve`
is strict: an unregistered combination raises ``NotImplementedError``;
unknown names raise ``ValueError``.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

from repro.ops.interpret import pallas_compiles

OPS = ("softmax", "layernorm", "rmsnorm", "residual_layernorm",
       "residual_rmsnorm", "flash_attention", "paged_attention",
       "matmul", "residual_layernorm_q", "residual_rmsnorm_q")
BACKENDS = ("reference", "pallas")

SOFTMAX_MODES = ("exact", "sole", "softermax", "ibert")
NORM_MODES = ("exact", "sole", "ibert")
ATTN_MODES = ("exact", "sole")
# matmul modes are the serve-time quantization levels: exact = config
# dtype, w8a16 = int8 weights x fp acts, w8a8 = int8 weights x int8 acts
# with exact int32 accumulation.
MATMUL_MODES = ("exact", "w8a16", "w8a8")

MODES_BY_OP: Dict[str, Tuple[str, ...]] = {
    "softmax": SOFTMAX_MODES,
    "layernorm": NORM_MODES,
    "rmsnorm": NORM_MODES,
    "residual_layernorm": NORM_MODES,
    "residual_rmsnorm": NORM_MODES,
    "flash_attention": ATTN_MODES,
    # the paged reference path is the fallback for softmax modes the
    # paged kernel does not implement, so it spans all softmax modes.
    "paged_attention": SOFTMAX_MODES,
    "matmul": MATMUL_MODES,
    # *_q twins of the fused residual+norm ops additionally emit the
    # normalized activations as dynamic per-token int8 codes + scale,
    # ready for the next w8a8 matmul.
    "residual_layernorm_q": NORM_MODES,
    "residual_rmsnorm_q": NORM_MODES,
}

_REGISTRY: Dict[Tuple[str, str, str], Callable] = {}


def register(op: str, mode: str, backend: str):
    """Decorator: register ``fn`` as the (op, mode, backend) implementation."""
    _check_names(op, mode, backend)

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(op, mode, backend)] = fn
        return fn

    return deco


def _check_names(op: str, mode: str, backend: str) -> None:
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; known: {OPS}")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; known: {BACKENDS}")
    if mode not in MODES_BY_OP[op]:
        raise ValueError(
            f"unknown mode {mode!r} for op {op!r}; known: {MODES_BY_OP[op]}")


def is_registered(op: str, mode: str, backend: str) -> bool:
    return (op, mode, backend) in _REGISTRY


def resolve(op: str, mode: str, backend: str = "reference") -> Callable:
    """Strict lookup: the callable for (op, mode, backend), or raise."""
    _check_names(op, mode, backend)
    key = (op, mode, backend)
    if key not in _REGISTRY:
        raise NotImplementedError(
            f"op {op!r} mode {mode!r} has no {backend!r} backend "
            f"(registered backends: "
            f"{[b for b in BACKENDS if (op, mode, b) in _REGISTRY]})")
    return _REGISTRY[key]


def default_backend() -> str:
    """Platform autodetect: pallas where it compiles, reference elsewhere."""
    return "pallas" if pallas_compiles() else "reference"


def backend_for(cfg, op: str, mode: str,
                backend: Optional[str] = None) -> str:
    """Resolve the backend for one (op, mode) call site.

    ``cfg`` is an ``ArchConfig`` (or None); its ``ops_backend`` field is
    the per-model selection knob. A backend demanded by the ``backend``
    argument or by the config raises ``NotImplementedError`` when this
    combination has no implementation for it — a run that forced
    ``pallas`` never serves (or is measured on) a different engine than
    it asked for. Only ``auto`` falls back to ``reference`` for a
    combination without a kernel.
    """
    b = backend
    if b is None:
        b = getattr(cfg, "ops_backend", "auto") if cfg is not None else "auto"
    if b != "auto":
        resolve(op, mode, b)
        return b
    b = default_backend()
    _check_names(op, mode, b)
    return b if is_registered(op, mode, b) else "reference"
