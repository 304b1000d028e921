"""Cross-mesh parity harness for tensor-parallel paged serving.

Each mesh shape runs in a subprocess with N simulated host devices
(tests/_mesh_helpers.py — the main pytest process keeps the real
single-device view). The single-device oracle outputs are computed once
here, in the main process with ``rules=None``, and injected into every
subprocess as literals, so "sharded == oracle" really compares against
an engine that never saw a mesh.

What must hold, bit for bit, on every shape:

* cold prefill + decode-horizon traces (greedy, eos table active);
* warm replay (prefix-cache hits + the COW fork on the shared partial
  block);
* recompute-preemption under a tight pool (watermark 0);
* open-loop arrival traces through AsyncEngine (``step()`` enters the
  engine's rules context — the regression this pins);
* counter-keyed stochastic sampling — both whole-engine traces and the
  in-jit ``sample_tokens`` vs host ``Sampler`` direct comparison
  (collective safety: one logical draw per token, identical on every
  model shard).

The shapes cover the three paged-attention sharding regimes of
qwen2_0_5b.smoke() (4 q heads, 2 kv heads): matched head/KV
partitioning (model axis 2), replicated-KV GQA fallback (model axis 4),
and full head replication via the divisibility fallback (model axis 8).

Set ``SHARDED_SERVE_MESH=2x4`` (etc.) to run a single shape — CI's
multidevice matrix fans the shapes out across runners this way.
"""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import api
from repro.serve.engine import PagedEngine, Request
from repro.serve.loop import ReplicatedAsyncEngine
from tests._mesh_helpers import run_with_devices

pytestmark = pytest.mark.slow


def _exact_cfg():
    return dataclasses.replace(get_config("qwen2_0_5b").smoke(),
                               softmax_mode="exact", norm_mode="exact",
                               logit_int8=False)


def _requests(cfg):
    """The shared trace: two identical prompts (COW fork on the partial
    third block), one diverging after two full blocks, plus a seeded
    stochastic wave. Reproduced verbatim inside the subprocess battery
    (numpy Generator draws are deterministic across processes)."""
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)
    tail = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
    greedy = [Request(prompt=shared, max_new_tokens=6, eos_ids=(7,)),
              Request(prompt=shared.copy(), max_new_tokens=6),
              Request(prompt=np.concatenate([shared[:16], tail]),
                      max_new_tokens=6)]
    sampled = [Request(prompt=shared[:12], max_new_tokens=6,
                       temperature=0.8, top_k=8, seed=100 + i)
               for i in range(3)]
    return greedy, sampled


def _paged(cfg, params, **kw):
    base = dict(num_blocks=40, block_size=8, max_seq_len=64, max_running=4,
                decode_batch=4, prefill_chunk=8, decode_horizon=4,
                backend="pallas")
    base.update(kw)
    return PagedEngine(cfg, params, **base)


@pytest.fixture(scope="module")
def oracle():
    """Single-device (rules=None) reference traces."""
    cfg = _exact_cfg()
    params, axes = api.init_params(jax.random.PRNGKey(0), cfg)
    greedy, sampled = _requests(cfg)
    eng = _paged(cfg, params)
    ref_greedy = eng.generate(greedy)
    ref_sampled = eng.generate(sampled)
    eng.cache.check_refcounts()
    return cfg, params, axes, ref_greedy, ref_sampled


# The subprocess battery. SHAPE / PREEMPT / ASYNC / REF_* are prepended
# as literals per test; keep this string free of {braces-for-format}.
_PRELUDE = """
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.base import get_config
from repro.launch.mesh import make_mesh, make_rules
from repro.models import api
from repro.serve.engine import PagedEngine, Request
from repro.serve.loop import AsyncEngine
from repro.serve.sampling import Sampler, sample_tokens
from repro.sharding.rules import use_rules

cfg = dataclasses.replace(get_config("qwen2_0_5b").smoke(),
                          softmax_mode="exact", norm_mode="exact",
                          logit_int8=False)
params, axes = api.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(7)
shared = rng.integers(0, cfg.vocab_size, size=20).astype(np.int32)
tail = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
greedy = [Request(prompt=shared, max_new_tokens=6, eos_ids=(7,)),
          Request(prompt=shared.copy(), max_new_tokens=6),
          Request(prompt=np.concatenate([shared[:16], tail]),
                  max_new_tokens=6)]
sampled = [Request(prompt=shared[:12], max_new_tokens=6, temperature=0.8,
                   top_k=8, seed=100 + i) for i in range(3)]


def engine(rules=None, ax=None, **kw):
    base = dict(num_blocks=40, block_size=8, max_seq_len=64, max_running=4,
                decode_batch=4, prefill_chunk=8, decode_horizon=4,
                backend="pallas", rules=rules, param_axes=ax)
    base.update(kw)
    return PagedEngine(cfg, params, **base)


mesh = make_mesh(SHAPE, ("data", "model"))
rules = make_rules(mesh)
"""

_BATTERY = _PRELUDE + """
sh = engine(rules, axes)
assert sh.generate(list(greedy)) == REF_GREEDY, "cold parity"
warm = sh.generate(list(greedy))
assert warm == REF_GREEDY, "warm (prefix-hit + COW fork) parity"
st = sh.stats()
assert st["prefix_hit_rate"] > 0, st
assert st["cow_copies"] > 0, st
assert sh.generate(list(sampled)) == REF_SAMPLED, "stochastic parity"
sh.cache.check_refcounts()

if PREEMPT:
    tight = engine(rules, axes, num_blocks=8, watermark=0)
    assert tight.generate(list(greedy)) == REF_GREEDY, "preempt parity"
    assert tight.stats()["preemptions"] > 0, tight.stats()
    tight.cache.check_refcounts()

if ASYNC:
    loop = AsyncEngine(sh)
    hs = [loop.add_request(r, arrival=3 * i) for i, r in enumerate(greedy)]
    loop.run()
    assert [h.tokens for h in hs] == REF_GREEDY, "open-loop parity"
    sh.cache.check_refcounts()

# in-jit counter-keyed sampling under the mesh == host Sampler draws,
# bit for bit (one logical draw per token on every model shard)
logits = np.asarray(rng.normal(size=(4, cfg.padded_vocab)), np.float32)
temp = np.asarray([0.7, 1.3, 0.0, 0.9], np.float32)
topk = np.asarray([5, 0, 0, 3], np.int32)
seed = np.asarray([1, 2, 3, 4], np.uint32)
ctr = np.asarray([0, 5, 2, 9], np.int32)
with mesh, use_rules(rules):
    dev = jax.jit(lambda z: sample_tokens(
        jnp.asarray(z), jnp.asarray(temp), jnp.asarray(topk),
        jnp.asarray(seed), jnp.asarray(ctr), cfg.vocab_size))(logits)
host = []
for i in range(4):
    s = Sampler(float(temp[i]), int(topk[i]), int(seed[i]), cfg.vocab_size)
    s.skip(int(ctr[i]))
    host.append(s(logits[i]))
assert [int(t) for t in np.asarray(dev)] == host, (dev, host)
print("BATTERY-PASS")
"""

# (devices, mesh shape, run preempt leg, run async leg). Preempt/async
# legs each compile one more engine, so they run on one shape per
# regime rather than everywhere.
SHAPES = [
    (1, (1, 1), False, False),
    (2, (1, 2), True, True),      # matched head/KV sharding
    (4, (2, 2), False, False),    # matched, with a data axis
    (8, (1, 8), False, False),    # 4 heads % 8 != 0: full replication
    (8, (2, 4), True, True),      # GQA fallback: q sharded, KV replicated
    (8, (8, 1), False, False),    # model axis absent from sharding
]


@pytest.mark.parametrize(
    "spec", SHAPES, ids=[f"{s[1][0]}x{s[1][1]}" for s in SHAPES])
def test_sharded_engine_token_parity(spec, oracle):
    ndev, shape, preempt, use_async = spec
    only = os.environ.get("SHARDED_SERVE_MESH", "")
    if only and f"{shape[0]}x{shape[1]}" != only:
        pytest.skip(f"SHARDED_SERVE_MESH={only}")
    _, _, _, ref_greedy, ref_sampled = oracle
    code = (f"SHAPE = {shape!r}\nPREEMPT = {preempt!r}\n"
            f"ASYNC = {use_async!r}\nREF_GREEDY = {ref_greedy!r}\n"
            f"REF_SAMPLED = {ref_sampled!r}\n" + _BATTERY)
    assert "BATTERY-PASS" in run_with_devices(code, n_devices=ndev)


def test_gqa_kv_fallback_pinned(oracle):
    """Regression pin for satellite: kv_heads (2) smaller than the model
    axis (4) must replicate the KV pool while q heads (4) stay sharded —
    and the resulting plan must still reproduce the oracle trace."""
    only = os.environ.get("SHARDED_SERVE_MESH", "")
    if only and only != "1x4":
        pytest.skip(f"SHARDED_SERVE_MESH={only}")
    _, _, _, ref_greedy, _ = oracle
    code = (f"SHAPE = (1, 4)\nREF_GREEDY = {ref_greedy!r}\n" + _PRELUDE + """
from repro.models.layers import _paged_tp_plan
assert rules.dim_spec("heads", cfg.n_heads) == "model"
assert rules.dim_spec("kv_heads", cfg.n_kv_heads) is None, \\
    "2 kv heads must not shard over a 4-way model axis"
assert _paged_tp_plan(rules, cfg.n_heads, cfg.n_kv_heads) == \\
    ("model", False), "q heads sharded, KV replicated"
sh = engine(rules, axes)
assert sh.generate(list(greedy)) == REF_GREEDY, "gqa fallback parity"
sh.cache.check_refcounts()
print("BATTERY-PASS")
""")
    assert "BATTERY-PASS" in run_with_devices(code, n_devices=4)


# -- data-parallel replicas (single device: routing + parity) -----------------


def _single_device_leg():
    """The replica tests need no mesh: in the CI matrix they run on the
    1x1 control leg only instead of once per shape."""
    only = os.environ.get("SHARDED_SERVE_MESH", "")
    if only and only != "1x1":
        pytest.skip(f"SHARDED_SERVE_MESH={only}")


def test_replicated_front_door_routing_and_parity(oracle):
    """N engines behind one routed front door: prompts sharing a first
    block co-locate (prefix affinity), outputs match the single-engine
    oracle, and aggregate stats add up."""
    _single_device_leg()
    cfg, params, _, ref_greedy, ref_sampled = oracle
    greedy, sampled = _requests(cfg)
    engines = [_paged(cfg, params) for _ in range(2)]
    rep = ReplicatedAsyncEngine(engines)
    # all six prompts share the same first block -> one deterministic home
    homes = {rep.route(r) for r in greedy + sampled}
    assert len(homes) == 1
    hs = [rep.add_request(r) for r in greedy + sampled]
    rep.run()
    assert [h.tokens for h in hs] == ref_greedy + ref_sampled
    st = rep.stats()
    assert st["replicas"] == 2
    assert st["completed"] == 6
    assert st["routed_by_prefix"] == 6
    assert st["decode_tokens"] == sum(
        s["engine"]["decode_tokens"] for s in st["per_replica"])
    for e in engines:
        e.cache.check_refcounts()


def test_replicated_short_prompts_balance_by_load(oracle):
    """Prompts below one block have no prefix key: they go to the least
    loaded replica, so two enqueued back-to-back split across replicas."""
    _single_device_leg()
    cfg, params, _, _, _ = oracle
    engines = [_paged(cfg, params) for _ in range(2)]
    rep = ReplicatedAsyncEngine(engines)
    short = [Request(prompt=np.arange(1, 5, dtype=np.int32).astype(np.int32),
                     max_new_tokens=2) for _ in range(2)]
    h0 = rep.add_request(short[0])
    h1 = rep.add_request(short[1])
    assert rep.stats()["routed_by_load"] == 2
    # one outstanding on the first home -> the second goes to the other
    assert {h0._loop, h1._loop} == set(rep.replicas)
    rep.run()
    assert h0.finished and h1.finished
    for e in engines:
        e.cache.check_refcounts()
