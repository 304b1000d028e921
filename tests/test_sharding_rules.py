"""Logical-axis rules: divisibility fallback + ZeRO-1 spec (no mesh exec)."""
import math

import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding.rules import (DEFAULT_RULES, Rules, fsdp_param_spec,
                                  zero1_spec)
from tests._hypothesis_compat import given, settings, st


def _fake_rules(axis_sizes):
    """Rules over a 1-device mesh with injected production axis sizes
    (spec logic only touches axis_sizes)."""
    mesh = make_mesh((1,), ("data",))
    r = Rules.__new__(Rules)
    r.mesh = mesh
    r.table = dict(DEFAULT_RULES)
    r.axis_sizes = dict(axis_sizes)
    return r


def test_divisible_dims_shard():
    r = _fake_rules({"data": 16, "model": 16})
    assert r.spec(("batch", None, "ff"), (256, 4096, 14336)) == \
        P("data", None, "model")
    assert r.spec(("embed", "heads", "head_dim"), (896, 48, 128)) == \
        P(None, "model", None)


def test_nondivisible_heads_fall_back_replicated():
    r = _fake_rules({"data": 16, "model": 16})
    # qwen2-0.5b: 14 heads, whisper: 12 heads -> replicate
    assert r.spec(("embed", "heads", "head_dim"), (896, 14, 64)) == \
        P(None, None, None)
    # kv heads 8 on 16-way model -> replicate (Megatron behavior)
    assert r.spec(("embed", "kv_heads", "head_dim"), (6144, 8, 128)) == \
        P(None, None, None)


def test_batch_prefix_fallback_multi_pod():
    r = _fake_rules({"pod": 2, "data": 16, "model": 16})
    # batch 256 divisible by pod*data=32
    assert r.spec(("batch", None), (256, 4096)) == P(("pod", "data"), None)
    # batch 1 (long_500k): fully replicated
    assert r.spec(("batch", None), (1, 524288)) == P(None, None)


def test_experts_rule():
    r = _fake_rules({"data": 16, "model": 16})
    assert r.dim_spec("experts", 16) == "data"     # dbrx
    assert r.dim_spec("experts", 8) is None        # mixtral falls back


def test_vocab_padding_shards():
    r = _fake_rules({"data": 16, "model": 16})
    # whisper vocab 51865 is padded to 51968 = 406*128 (divisible by 16)
    from repro.configs.base import get_config
    cfg = get_config("whisper_small")
    assert cfg.padded_vocab % 128 == 0
    assert r.dim_spec("vocab", cfg.padded_vocab) == "model"
    assert r.dim_spec("vocab", cfg.vocab_size) is None


def test_zero1_spec_shards_largest_free_dim():
    r = _fake_rules({"data": 16, "model": 16})
    spec = P(None, "model")
    out = zero1_spec(spec, (8192, 14336), r)
    assert out == P("data", "model")
    # no free divisible dim -> unchanged
    out2 = zero1_spec(P("model",), (14336,), r)
    assert out2 == P("model")
    # already uses data -> unchanged
    out3 = zero1_spec(P("data", None), (256, 31), r)
    assert out3 == P("data", None)


# -- property tests: the fallback invariants hold for ALL sizes ---------------
#
# dim_spec / fsdp_param_spec / zero1_spec are only exercised on a few
# production shapes above; the divisibility contract has to hold for
# arbitrary (dim, mesh) combinations or sharded kernels get ragged
# shards. Axis sizes are powers of two up to 32 (the realistic mesh
# range); dims are unconstrained small ints so non-divisible cases
# dominate.

_AXIS_SIZES = st.sampled_from([1, 2, 4, 8, 16, 32])
_LOGICALS = st.sampled_from(sorted(DEFAULT_RULES))


def _axes_product(r, axes):
    names = axes if isinstance(axes, tuple) else (axes,)
    return math.prod(r.axis_sizes[a] for a in names)


@settings(max_examples=200, deadline=None)
@given(logical=_LOGICALS, size=st.integers(1, 4096),
       pod=_AXIS_SIZES, data=_AXIS_SIZES, model=_AXIS_SIZES)
def test_dim_spec_product_always_divides(logical, size, pod, data, model):
    r = _fake_rules({"pod": pod, "data": data, "model": model})
    axes = r.dim_spec(logical, size)
    if axes is not None:
        assert size % _axes_product(r, axes) == 0


@settings(max_examples=200, deadline=None)
@given(logical=_LOGICALS, size=st.integers(1, 4096),
       pod=_AXIS_SIZES, data=_AXIS_SIZES, model=_AXIS_SIZES)
def test_dim_spec_prefix_fallback_monotone(logical, size, pod, data, model):
    """The chosen axes are always a *prefix* of the rule's preference
    list — the fallback only ever drops axes from the tail, it never
    reorders or skips, so a bigger divisible dim can only keep a
    superset of a smaller one's axes."""
    r = _fake_rules({"pod": pod, "data": data, "model": model})
    pref = tuple(a for a in r.table.get(logical, ())
                 if a in r.axis_sizes)
    axes = r.dim_spec(logical, size)
    names = (() if axes is None
             else axes if isinstance(axes, tuple) else (axes,))
    assert names == pref[:len(names)]
    # monotonicity: multiplying the dim by the full preference product
    # can never make the spec *shorter*
    if pref:
        bigger = r.dim_spec(logical, size * _axes_product(r, pref))
        bnames = (() if bigger is None
                  else bigger if isinstance(bigger, tuple) else (bigger,))
        assert len(bnames) >= len(names)


@settings(max_examples=200, deadline=None)
@given(shape=st.lists(st.integers(1, 512), min_size=1, max_size=4),
       data=_AXIS_SIZES, model=_AXIS_SIZES)
def test_fsdp_param_spec_divides_and_single_dim(shape, data, model):
    r = _fake_rules({"data": data, "model": model})
    spec = fsdp_param_spec(tuple(shape), r)
    assert len(spec) == len(shape)
    sharded = [(i, d) for i, d in enumerate(spec) if d is not None]
    assert len(sharded) <= 1          # ZeRO-3 shards exactly one dim
    for i, d in sharded:
        assert shape[i] % _axes_product(r, d) == 0


@settings(max_examples=200, deadline=None)
@given(shape=st.lists(st.integers(1, 512), min_size=1, max_size=3),
       sharded_dim=st.integers(0, 2), data=_AXIS_SIZES, model=_AXIS_SIZES)
def test_zero1_never_double_uses_an_axis(shape, sharded_dim, data, model):
    """zero1_spec may add 'data' to one free divisible dim, but must
    never produce a spec using any mesh axis twice, and must leave the
    base spec's dims untouched."""
    r = _fake_rules({"data": data, "model": model})
    shape = tuple(shape)
    dims = [None] * len(shape)
    if sharded_dim < len(shape) and shape[sharded_dim] % model == 0:
        dims[sharded_dim] = "model"
    base = P(*dims)
    out = zero1_spec(base, shape, r)
    used = [a for d in out
            for a in (d if isinstance(d, tuple) else (d,)) if a]
    assert len(used) == len(set(used)), f"axis double-use: {out}"
    for i, d in enumerate(base):
        assert out[i] == d or d is None   # base dims preserved
    for i, d in enumerate(out):
        if d == "data" and base[i] is None:
            assert shape[i] % r.axis_sizes["data"] == 0


def test_constrain_noop_without_rules():
    import jax.numpy as jnp

    from repro.sharding.rules import constrain
    x = jnp.ones((4, 4))
    np.testing.assert_array_equal(np.asarray(constrain(x, "batch", None)),
                                  np.asarray(x))
