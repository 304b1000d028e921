"""Logical-axis sharding rules with divisibility fallback (MaxText-style).

Models annotate parameters and activations with *logical* axis names; the
active :class:`Rules` object maps them to mesh axes, dropping any mapping
whose dimension is not divisible by the mesh-axis size (e.g. qwen2-0.5b's
14 heads on a 16-way model axis fall back to replicated attention while
its FFN still shards). This keeps every (arch x shape x mesh) cell
compilable without per-arch hand-tuning — see DESIGN.md §5.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# Modules that need per-shard code (moe dispatch, paged attention TP)
# import these from here: per-shard bodies that read axis_index or mix
# replicated and sharded operands skip the replication check.
shard_map = jax.shard_map
SHARD_MAP_NOCHECK = {"check_vma": False}

# logical axis -> preferred mesh axes (joined). Tuples shard over the
# product of the listed mesh axes (those present in the mesh).
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": (),                # sequence replicated by default (SP opt-in)
    "seq_shard": ("data",),   # opt-in sequence parallelism
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "ff": ("model",),
    "vocab": ("model",),
    "experts": ("data",),
    "expert_ff": ("model",),
    "layers": (),
    "conv": (),
    "stats": (),
    # serve-time paged KV pool: pages replicate (any device can host any
    # sequence's pages); the kv_heads dim of each page shards over model.
    "pages": (),
    # serve-time recurrent state slots (ssm wkv/shift, hybrid RG-LRU
    # hidden + conv): the slot dim replicates like pages; inner dims
    # shard per the family's slot_axes.
    "state_slots": (),
}


class Rules:
    def __init__(self, mesh: Mesh, table: Optional[dict] = None):
        self.mesh = mesh
        self.table = dict(DEFAULT_RULES)
        if table:
            self.table.update(table)
        self.axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _mesh_axes(self, logical: Optional[str]) -> Tuple[str, ...]:
        if logical is None:
            return ()
        pref = self.table.get(logical, ())
        return tuple(a for a in pref if a in self.axis_sizes)

    def dim_spec(self, logical: Optional[str], size: Optional[int]):
        """Mesh axes for one dim, honoring divisibility of ``size``."""
        axes = self._mesh_axes(logical)
        if not axes:
            return None
        if size is not None:
            total = math.prod(self.axis_sizes[a] for a in axes)
            if size % total != 0:
                # try a prefix of the axes (e.g. batch=32 on pod*data=32 ok,
                # batch=1 -> replicate)
                while axes:
                    axes = axes[:-1]
                    total = math.prod(self.axis_sizes[a] for a in axes)
                    if axes and size % total == 0:
                        break
                if not axes:
                    return None
        return axes if len(axes) > 1 else axes[0]

    def spec(self, logical_axes: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        dims = []
        for i, ax in enumerate(logical_axes):
            size = None if shape is None else shape[i]
            dims.append(self.dim_spec(ax, size))
        return P(*dims)

    def sharding(self, logical_axes, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))


# FSDP: activations batch-shard over the whole mesh; no tensor parallelism.
FSDP_RULES = {
    "batch": ("pod", "data", "model"),
    "seq": (), "embed": (), "heads": (), "kv_heads": (), "head_dim": (),
    "ff": (), "vocab": (), "experts": ("data",), "expert_ff": (),
    "layers": (), "conv": (), "stats": (), "pages": (),
    "state_slots": (),
}


def fsdp_param_spec(shape, rules: "Rules") -> P:
    """Shard the largest divisible dim over the full (data, model) mesh
    product (falling back to 'data' alone) — ZeRO-3 parameter layout;
    XLA SPMD inserts the per-layer all-gathers and gradient
    reduce-scatters."""
    for axes in (("data", "model"), ("data",), ("model",)):
        if not all(a in rules.axis_sizes for a in axes):
            continue
        n = math.prod(rules.axis_sizes[a] for a in axes)
        best, best_size = None, 0
        for i, dim in enumerate(shape):
            if dim % n == 0 and dim > best_size:
                best, best_size = i, dim
        if best is not None:
            dims = [None] * len(shape)
            dims[best] = axes if len(axes) > 1 else axes[0]
            return P(*dims)
    return P(*([None] * len(shape)))


def make_rules_for(cfg, mesh) -> "Rules":
    """Strategy-aware rules factory (cfg.sharding_strategy)."""
    table = FSDP_RULES if getattr(cfg, "sharding_strategy", "tp") == "fsdp"         else None
    return Rules(mesh, table)


_ACTIVE: contextvars.ContextVar[Optional[Rules]] = contextvars.ContextVar(
    "active_rules", default=None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    tok = _ACTIVE.set(rules)
    try:
        yield rules
    finally:
        _ACTIVE.reset(tok)


def active_rules() -> Optional[Rules]:
    return _ACTIVE.get()


def unpartitioned(fn):
    """Run a Pallas kernel entry point whole on every device of the
    active rules' mesh.

    GSPMD cannot partition a Mosaic custom call, so under a mesh a
    kernel call must sit inside ``shard_map``. This wrapper puts it
    there with every operand replicated: each device computes the whole
    op, which is what tensor parallelism already holds for the norm and
    softmax inputs (their ``embed`` dim is never sharded), and what
    per-call PTF calibration needs to see the same rows as on one
    device. Without active rules, or inside a shard_map body (e.g. the
    head-split paged attention), ``fn`` is called as is.
    """
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        rules = _ACTIVE.get()
        if rules is None or jax.sharding.get_abstract_mesh().manual_axes:
            return fn(*args, **kw)
        # array-valued keywords (masks, head maps) enter as operands;
        # the rest (modes, block sizes) stay static.
        dyn = {k: v for k, v in kw.items()
               if any(isinstance(leaf, jax.Array)
                      for leaf in jax.tree.leaves(v))}
        static = {k: v for k, v in kw.items() if k not in dyn}
        return shard_map(lambda a, d: fn(*a, **d, **static),
                         mesh=rules.mesh, in_specs=P(), out_specs=P(),
                         **SHARD_MAP_NOCHECK)(args, dyn)
    return wrapped


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Apply with_sharding_constraint if rules are active (no-op otherwise)."""
    rules = _ACTIVE.get()
    if rules is None:
        return x
    spec = rules.spec(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


def param_specs(axes_tree, shapes_tree, rules: Rules):
    """Map a tree of logical-axes tuples + shapes to PartitionSpecs."""
    return jax.tree.map(
        lambda axes, shape: rules.spec(axes, shape),
        axes_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def shard_params(params, axes_tree, rules: Optional[Rules]):
    """device_put a param-value tree onto the rules' mesh layout.

    ``axes_tree`` is the logical-axes tree returned by
    ``models.api.init_params`` (tuples of logical names per leaf);
    divisibility fallback applies per dim. No-op when ``rules`` is None.
    """
    if rules is None:
        return params
    return jax.tree.map(
        lambda v, ax: jax.device_put(v, rules.sharding(ax, v.shape)),
        params, axes_tree)


# ---------------------------------------------------------------------------
# Serving-time weight quantization (SOLE W8A8 pipeline).
#
# Matmul weights are packed as {"q": int8, "s": fp32 scale} leaves; the
# scale reduces over each weight's contraction axes (leading, after any
# "layers" stacking dim) so it applies once after the int8 dot. The
# packed dict composes with shard_params: quantize_param_axes mirrors
# the logical-axes tree ({"q": axes, "s": axes}) and the divisibility
# fallback in Rules.dim_spec replicates the scale's size-1 contraction
# dims while out dims (heads/ff/vocab) stay sharded like the codes.
# ---------------------------------------------------------------------------

# name -> (n_contract, base_ndim): every matmul weight in the serve path
# contracts its *leading* base axes (wq/wk/wv (d,h,k) contract d; wo
# (h,k,d) contracts (h,k); gate/up/down/head (in,out) contract in). A
# leaf stacked with extra leading dims (per-layer "layers") quantizes
# with offset = ndim - base_ndim so each layer gets its own scales.
QUANT_WEIGHT_SPEC = {
    "wq": (1, 3), "wk": (1, 3), "wv": (1, 3), "wo": (2, 3),
    "gate": (1, 2), "up": (1, 2), "down": (1, 2), "head": (1, 2),
}


def _is_axes_leaf(v) -> bool:
    return isinstance(v, tuple) and all(
        a is None or isinstance(a, str) for a in v)


def quantize_params(params):
    """Pack the named matmul weights as per-channel int8 codes + scales.

    Idempotent: already-packed ``{"q","s"}`` leaves pass through, so
    engine replicas can re-feed a quantized tree. Non-matmul leaves
    (embeddings, norms, biases, caches) are untouched.
    """
    from repro.core.sole import quant as Q

    def walk(node):
        if isinstance(node, dict):
            if Q.is_qtensor(node):
                return node
            out = {}
            for k, v in node.items():
                spec = QUANT_WEIGHT_SPEC.get(k)
                if (spec is not None and hasattr(v, "ndim")
                        and v.ndim >= spec[1]):
                    n, base = spec
                    out[k] = Q.quantize_weight(v, n, offset=v.ndim - base)
                else:
                    out[k] = walk(v)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(params)


def quantize_param_axes(axes_tree):
    """Mirror a logical-axes tree onto the packed-weight structure.

    Each quantized leaf's axes tuple becomes ``{"q": axes, "s": axes}``
    — the scale keeps the same logical names; its size-1 contraction
    dims fall back to replicated via the divisibility rule.
    """
    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in QUANT_WEIGHT_SPEC and _is_axes_leaf(v):
                    out[k] = {"q": v, "s": v}
                else:
                    out[k] = walk(v)
            return out
        if isinstance(node, (list, tuple)) and not _is_axes_leaf(node):
            return type(node)(walk(v) for v in node)
        return node

    return walk(axes_tree)


def param_bytes(params) -> int:
    """Total bytes resident across all param leaves (codes + scales)."""
    return sum(int(v.size) * v.dtype.itemsize
               for v in jax.tree.leaves(params)
               if hasattr(v, "dtype"))


def zero1_spec(spec: P, shape, rules: Rules, axis: str = "data") -> P:
    """ZeRO-1: additionally shard the largest unsharded dim over ``axis``.

    Applied to optimizer moments and the fp32 master copy so that
    optimizer memory scales down with the data axis.
    """
    if axis not in rules.axis_sizes:
        return spec
    n = rules.axis_sizes[axis]
    dims = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for d in dims:
        for a in (d if isinstance(d, tuple) else (d,)):
            if a:
                used.add(a)
    if axis in used:
        return spec
    best, best_size = None, 0
    for i, d in enumerate(dims):
        if d is None and shape[i] % n == 0 and shape[i] > best_size:
            best, best_size = i, shape[i]
    if best is None:
        return spec
    dims[best] = axis
    return P(*dims)
