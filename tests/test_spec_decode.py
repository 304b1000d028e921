"""Speculative decoding: drafter proposals, the batched K+1 verify
dispatch, pinned-stream accept-prefix, and the EMA K controller.

The load-bearing contract (serve/spec.py): speculative output streams
are **bit-for-bit identical** to non-speculative decode for greedy and
stochastic lanes alike — speculation only changes how many target
dispatches it takes. That reduces to two pins, both covered here:
verify-path logits equal decode-path logits bitwise in exact mode, and
the per-slot pinned draws equal the host ``Sampler`` oracle's draws at
the same counters (with discarded draws never advancing the stream)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from _mesh_helpers import run_with_devices
from repro.configs.base import get_config
from repro.models import api
from repro.serve.engine import PagedEngine, Request
from repro.serve.sampling import Sampler, sample_tokens
from repro.serve.spec import DraftModelDrafter, NGramDrafter, SpecConfig


@pytest.fixture(scope="module")
def exact_lm():
    cfg = get_config("qwen2_0_5b").smoke()
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    cfg = dataclasses.replace(cfg, softmax_mode="exact", norm_mode="exact",
                              logit_int8=False)
    return cfg, params


def _paged(cfg, params, **kw):
    base = dict(num_blocks=40, block_size=8, max_seq_len=64, max_running=4,
                decode_batch=4, prefill_chunk=8, backend="pallas")
    base.update(kw)
    return PagedEngine(cfg, params, **base)


def _requests(cfg, n, rng, plen=16, new=8, **kw):
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=plen)
                    .astype(np.int32), max_new_tokens=new, **kw)
            for _ in range(n)]


def _mixed_requests(cfg, rng):
    """Greedy + stochastic lanes in one trace."""
    return (_requests(cfg, 2, rng) +
            _requests(cfg, 2, rng, temperature=0.9, top_k=6, new=7, seed=5))


class OracleDrafter:
    """Proposes the true continuation (the all-accepted edge): drafts
    are read off a precomputed non-speculative run, so every verify
    round accepts all K drafts plus the bonus token."""

    def __init__(self, oracle_outs):
        self._outs = oracle_outs         # seq_id -> full output list

    def propose(self, lanes, ks):
        return [self._outs[s.seq_id][len(s.out):len(s.out) + k]
                for s, k in zip(lanes, ks)]


class AntiOracleDrafter(OracleDrafter):
    """Proposes provably wrong tokens (the all-rejected edge): the true
    next token shifted by one mod vocab can never match the pinned
    draw, so every draft is rejected and each verify emits exactly the
    one correction token — output must still match plain decode."""

    def __init__(self, oracle_outs, vocab_size):
        super().__init__(oracle_outs)
        self._vocab = vocab_size

    def propose(self, lanes, ks):
        return [[(t + 1) % self._vocab for t in d]
                for d in super().propose(lanes, ks)]


# -- the two load-bearing pins ------------------------------------------------


def test_verify_logits_bitwise_match_decode_path(exact_lm):
    """The whole acceptance scheme rests on this: the causal multi-query
    verify forward (prefill_paged) must produce logits bit-identical to
    the single-query decode forward at every slot in exact mode —
    including ragged lanes whose padded tail routes to the null page."""
    from repro.models.transformer import decode_step_paged, prefill_paged
    cfg, params = exact_lm
    eng = _paged(cfg, params)
    rng = np.random.default_rng(3)
    seq = eng.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, 13).astype(np.int32),
        max_new_tokens=16))
    while len(seq.out) < 3:
        eng.step()
    k = 3
    pos = seq.prompt_len + len(seq.out) - 1
    eng._apply_copies(eng.sched.ensure_tokens(seq, pos, pos + k + 1))
    table = jnp.asarray(eng.cache.batch_tables([seq.seq_id]))
    pools = eng.cache.pools
    toks, dec, dp, p, cur = [seq.out[-1]], [], pools, pos, seq.out[-1]
    for _ in range(k + 1):
        lg, dp = decode_step_paged(
            params, dp, jnp.asarray([cur], jnp.int32),
            jnp.asarray([p], jnp.int32), table, cfg, backend="pallas")
        dec.append(np.asarray(lg[0]))
        cur = int(np.argmax(dec[-1][:cfg.vocab_size]))
        toks.append(cur)
        p += 1
    row = np.zeros((1, k + 1), np.int32)
    row[0] = toks[:k + 1]
    vlg, _ = prefill_paged(
        params, jnp.asarray(row), jnp.asarray([pos], jnp.int32),
        jnp.asarray([k + 1], jnp.int32), table, pools, cfg,
        backend="pallas")
    for i in range(k + 1):
        assert np.array_equal(dec[i], np.asarray(vlg[0, i])), f"slot {i}"
    # ragged: n_valid=2 inside a width-4 dispatch (padded tail -> null)
    row2 = np.zeros((1, 4), np.int32)
    row2[0, :2] = toks[:2]
    vlg2, _ = prefill_paged(
        params, jnp.asarray(row2), jnp.asarray([pos], jnp.int32),
        jnp.asarray([2], jnp.int32), table, pools, cfg, backend="pallas")
    for i in range(2):
        assert np.array_equal(dec[i], np.asarray(vlg2[0, i]))
    eng.sched.cancel(seq)


@settings(max_examples=60, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       temperature=st.sampled_from([0.0, 0.3, 0.9, 1.7]),
       top_k=st.sampled_from([0, 1, 3, 8]),
       k=st.sampled_from([1, 2, 4, 8]),
       n0=st.integers(0, 40),
       edge=st.sampled_from(["accept_all", "reject_all", "mixed"]),
       data_seed=st.integers(0, 2**31 - 1))
def test_acceptance_matches_host_sampler_oracle(seed, temperature, top_k,
                                                k, n0, edge, data_seed):
    """Property pin of the acceptance layer against the host Sampler
    oracle, across (seed, temperature, top-k, K) grids with all-accepted
    / all-rejected / mixed drafts.

    Given K+1 logits rows, the in-jit per-slot draws (exactly what
    ``verify_paged`` computes: flattened ``sample_tokens`` with
    counters ``n0 .. n0+K``) must equal ``Sampler.draw`` bit-for-bit;
    accept-prefix must then emit exactly the tokens a non-speculative
    sequential ``Sampler`` produces on the same rows, advancing the
    stream by the kept count only (discarded draws never move it)."""
    vocab = 64
    c = k + 1
    rng = np.random.default_rng(data_seed)
    logits = rng.normal(size=(c, vocab)).astype(np.float32)
    host = Sampler(temperature, top_k, seed, vocab)
    pinned = [host.draw(logits[i], n0 + i) for i in range(c)]
    ones = lambda v, dt: np.full((c,), v, dt)
    dev = np.asarray(sample_tokens(
        jnp.asarray(logits), jnp.asarray(ones(temperature, np.float32)),
        jnp.asarray(ones(top_k, np.int32)),
        jnp.asarray(ones(np.uint32(seed & 0xFFFFFFFF), np.uint32)),
        jnp.asarray(n0 + np.arange(c, dtype=np.int32)), vocab))
    assert [int(t) for t in dev] == pinned
    if edge == "accept_all":
        draft = pinned[:k]
    elif edge == "reject_all":
        draft = [(t + 1) % vocab for t in pinned[:k]]
    else:
        draft = [pinned[i] if (data_seed >> i) & 1 else (pinned[i] + 1)
                 % vocab for i in range(k)]
    acc = 0
    while acc < k and draft[acc] == pinned[acc]:
        acc += 1
    emitted = pinned[:acc + 1]
    # the non-speculative oracle: one sequential draw per emitted token
    oracle = Sampler(temperature, top_k, seed, vocab)
    oracle.skip(n0)
    assert [oracle(logits[i]) for i in range(len(emitted))] == emitted
    stochastic = temperature > 0
    assert oracle.draws == n0 + (len(emitted) if stochastic else 0)


# -- engine-level parity ------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_spec_parity_self_draft_k_grid(exact_lm, k):
    """Self-draft (draft model == target) across K: outputs bit-match
    plain decode for greedy and stochastic lanes, and acceptance is
    near-total (dense-forward draft logits agree with the paged verify
    at token level in exact mode)."""
    cfg, params = exact_lm
    rng = np.random.default_rng(41)
    reqs = _mixed_requests(cfg, rng)
    ref = _paged(cfg, params, decode_horizon=8).generate(reqs)
    spec = SpecConfig(DraftModelDrafter(cfg, params, window=64), max_k=k)
    eng = _paged(cfg, params, spec_config=spec)
    assert eng.generate(reqs) == ref
    st_ = eng.stats()
    assert st_["spec_dispatches"] > 0
    assert st_["acceptance_rate"] > 0.9, st_
    assert st_["blocks_in_use"] == 0


def test_spec_all_accepted_edge_beats_plain_dispatch_count(exact_lm):
    """A perfect drafter accepts everything: acceptance_rate == 1.0 and
    the verify path needs strictly fewer target dispatches per token
    than the plain fused horizon."""
    cfg, params = exact_lm
    rng = np.random.default_rng(42)
    reqs = _requests(cfg, 4, rng, new=16)
    plain = _paged(cfg, params, decode_horizon=8)
    ref = plain.generate(reqs)
    oracle = {i: list(o) for i, o in enumerate(ref)}
    eng = _paged(cfg, params,
                 spec_config=SpecConfig(OracleDrafter(oracle), max_k=8))
    assert eng.generate(reqs) == ref
    st_ = eng.stats()
    assert st_["acceptance_rate"] == 1.0
    assert (st_["accepted_tokens_per_target_dispatch"]
            > plain.stats()["tokens_per_dispatch"])


def test_spec_all_rejected_edge_still_exact(exact_lm):
    """Every draft provably wrong: each verify emits exactly one
    correction token, outputs still bit-match plain decode, rejected
    draws are counted discarded, and the EMA controller walks every
    lane's K down to the plain-horizon fallback."""
    cfg, params = exact_lm
    rng = np.random.default_rng(43)
    reqs = _mixed_requests(cfg, rng)
    ref = _paged(cfg, params, decode_horizon=8).generate(reqs)
    oracle = {i: list(o) for i, o in enumerate(ref)}
    spec = SpecConfig(AntiOracleDrafter(oracle, cfg.vocab_size), max_k=4,
                      retry_after=100)
    eng = _paged(cfg, params, spec_config=spec)
    assert eng.generate(reqs) == ref
    st_ = eng.stats()
    assert st_["acceptance_rate"] == 0.0
    assert st_["spec_accepted_tokens"] == 0
    assert st_["truncated_tokens"] >= st_["spec_proposed_tokens"]
    # drafts stopped paying -> plain horizon decode took over
    assert st_["spec_fallback_steps"] > 0


def test_ngram_match_semantics():
    """Prompt-lookup rules, pinned directly: longest matching suffix
    wins, ties break to the most recent earlier occurrence, proposals
    clip to k, and an unseen suffix proposes nothing."""
    d = NGramDrafter(max_ngram=3, min_ngram=1)
    # suffix [7,8,9] re-occurs at the front; its continuation follows
    ctx = np.array([1, 7, 8, 9, 4, 5, 7, 8, 9], np.int32)
    assert d._match(ctx, 2) == [4, 5]
    assert d._match(ctx, 4) == [4, 5, 7, 8]    # clip to what exists
    # suffix [1,2] occurs twice: the most recent occurrence (-> 5) wins
    ctx = np.array([1, 2, 9, 1, 2, 5, 1, 2], np.int32)
    assert d._match(ctx, 1) == [5]
    assert d._match(np.array([1, 2, 3, 4], np.int32), 2) == []
    assert d._match(ctx, 0) == []


def test_spec_ngram_parity():
    """The model-free drafter end to end: parity is unconditional
    (acceptance only filters drafts against pinned draws), whatever the
    hit rate. Generated tokens from random params land anywhere in the
    vocab, so a guaranteed dispatch needs a guaranteed 1-gram hit: a
    32-token vocab with prompts that cover it means *every* generated
    token re-occurs earlier in the context and the drafter always has a
    proposal."""
    cfg = dataclasses.replace(get_config("qwen2_0_5b").smoke(),
                              vocab_size=32)
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    cfg = dataclasses.replace(cfg, softmax_mode="exact", norm_mode="exact",
                              logit_int8=False)
    rng = np.random.default_rng(44)
    reqs = [Request(prompt=rng.permutation(cfg.vocab_size)
                    .astype(np.int32), max_new_tokens=8)
            for _ in range(3)]
    ref = _paged(cfg, params, decode_horizon=8).generate(reqs)
    eng = _paged(cfg, params,
                 spec_config=SpecConfig(NGramDrafter(), max_k=4))
    assert eng.generate(reqs) == ref
    assert eng.stats()["spec_dispatches"] > 0
    assert eng.cache.blocks_in_use == 0


# -- finish events and preemption mid-verify ----------------------------------


def test_spec_eos_mid_verify(exact_lm):
    """An eos sampled inside the accepted prefix must cut the lane at
    that token exactly as plain decode does, with the verify tail
    discarded and its pages reclaimed."""
    cfg, params = exact_lm
    rng = np.random.default_rng(45)
    reqs = _requests(cfg, 4, rng, new=12)
    ref = _paged(cfg, params, decode_horizon=8).generate(reqs)
    # terminate each request on a token it actually emits mid-stream
    reqs_eos = [dataclasses.replace(r, eos_ids=(o[len(o) // 2],))
                for r, o in zip(reqs, ref)]
    plain = _paged(cfg, params, decode_horizon=8)
    ref_eos = plain.generate(reqs_eos)
    oracle = {i: list(o) for i, o in enumerate(ref)}
    eng = _paged(cfg, params,
                 spec_config=SpecConfig(OracleDrafter(oracle), max_k=8))
    assert eng.generate(reqs_eos) == ref_eos
    st_ = eng.stats()
    assert st_["finish_reasons"].get("eos", 0) == 4
    assert st_["blocks_in_use"] == 0


def test_spec_stop_sequence_spanning_verify_boundary(exact_lm):
    """A multi-token stop sequence straddling two verify dispatches is
    matched by the host window exactly as in the horizon path."""
    cfg, params = exact_lm
    rng = np.random.default_rng(46)
    reqs = _requests(cfg, 2, rng, new=10)
    ref = _paged(cfg, params, decode_horizon=8).generate(reqs)
    # with K=4 all-accepted verifies the first dispatch emits stream
    # indices 1..5 and the second 6..: a stop pair at (5, 6) completes
    # one token into the second dispatch, reaching back across the
    # boundary through apply_finish's match window
    reqs_stop = [dataclasses.replace(r, stop=((o[5], o[6]),))
                 for r, o in zip(reqs, ref)]
    ref_stop = _paged(cfg, params, decode_horizon=8).generate(reqs_stop)
    oracle = {i: list(o) for i, o in enumerate(ref)}
    eng = _paged(cfg, params,
                 spec_config=SpecConfig(OracleDrafter(oracle), max_k=4))
    assert eng.generate(reqs_stop) == ref_stop
    assert eng.stats()["finish_reasons"].get("stop", 0) == 2
    assert all(o == r[:7] for o, r in zip(ref_stop, ref))


def test_spec_parity_across_preemption(exact_lm):
    """A tight pool forces recompute-preemption mid-trace under
    speculation; replay must land on the plain roomy run's tokens."""
    cfg, params = exact_lm
    rng = np.random.default_rng(47)
    reqs = _requests(cfg, 5, rng, plen=16, new=8)
    ref = _paged(cfg, params, decode_horizon=1).generate(reqs)
    spec = SpecConfig(DraftModelDrafter(cfg, params, window=64), max_k=4)
    tight = _paged(cfg, params, num_blocks=8, watermark=0,
                   spec_config=spec)
    assert tight.generate(reqs) == ref
    assert tight.stats()["preemptions"] > 0


# -- rejected-tail page accounting --------------------------------------------


def test_rejected_tails_reclaim_pages_on_cow_forked_lanes(exact_lm):
    """Satellite pin: a lane COW-forked off a shared cached prefix runs
    wide always-rejected verifies; every rejected tail must hand its
    pre-extended pages back through ``truncate`` (block_size=8 and K=8
    guarantee each verify crosses a page boundary), refcounts must stay
    consistent, and the pool must drain to zero in-use blocks. COW
    needs an *overlapping-lifetime* fork — a cached page only carries
    refcount > 1 while the registering lane is still alive — so the
    second request is submitted mid-decode of the first via the
    submit()/step() API (registration happens at prefill completion).
    The 12-token prompt is deliberately *not* block-aligned: lookup
    matches 11 tokens, so the fork's recompute of the final prompt
    position writes into the shared partial page — a forced COW."""
    cfg, params = exact_lm
    rng = np.random.default_rng(48)
    shared = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    reqs = [Request(prompt=shared, max_new_tokens=10),
            Request(prompt=shared, max_new_tokens=10)]
    ref = _paged(cfg, params, decode_horizon=8).generate(reqs)
    oracle = {i: list(o) for i, o in enumerate(ref)}
    spec = SpecConfig(AntiOracleDrafter(oracle, cfg.vocab_size), max_k=8,
                      demote_below=0.0)   # keep K wide: never demote
    eng = _paged(cfg, params, spec_config=spec)
    a = eng.submit(reqs[0])
    while a.in_prefill:                   # prompt registered at the end
        eng.step()
    b = eng.submit(reqs[1])               # fork while A holds its pages
    while eng.sched.has_work:
        eng.step()
    assert [list(a.out), list(b.out)] == ref
    st_ = eng.stats()
    assert st_["cow_copies"] > 0          # forked lane wrote a shared page
    assert st_["prefix_hit_tokens"] > 0
    assert st_["reclaimed_pages"] > 0     # rejected tails handed back
    assert st_["acceptance_rate"] == 0.0
    assert st_["blocks_in_use"] == 0      # zero leaked pages
    eng.cache.check_refcounts()


def test_spec_controller_adapts_k(exact_lm):
    """The EMA policy: all-rejected lanes decay K to 0 (spec hands the
    step back to the horizon path), and the re-probe brings K back."""
    from repro.serve.scheduler import Scheduler, Sequence
    cfg, params = exact_lm
    eng = _paged(cfg, params)        # just for a live scheduler
    sched: Scheduler = eng.sched
    spec = SpecConfig(NGramDrafter(), max_k=8, ema_alpha=0.5,
                      retry_after=3)
    seq = Sequence(0, np.zeros(4, np.int32), max_new_tokens=100)
    assert sched.spec_ks([seq], spec) == [8]
    for _ in range(12):              # nothing accepted: decay to 0
        sched.spec_feedback(seq, proposed=seq.spec_k or 1, accepted=0,
                            spec=spec)
    assert seq.spec_k == 0
    for _ in range(2):
        assert sched.spec_ks([seq], spec) == [0]
    assert sched.spec_ks([seq], spec) == [1]   # re-probe after cooldown
    for _ in range(12):              # everything accepted: climb back
        sched.spec_feedback(seq, proposed=max(seq.spec_k, 1),
                            accepted=max(seq.spec_k, 1), spec=spec)
    assert seq.spec_k == 8
    # budget cap: never draft past remaining-1
    seq.out = [0] * 97
    assert sched.spec_ks([seq], spec) == [2]
    seq.out = [0] * 99
    assert sched.spec_ks([seq], spec) == [0]


# -- speculation under a tensor-parallel mesh ---------------------------------


_MESH_SNIPPET = """
import dataclasses
import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.mesh import make_mesh, make_rules
from repro.models import api
from repro.serve.engine import PagedEngine, Request
from repro.serve.spec import DraftModelDrafter, SpecConfig
from repro.sharding import rules as R

cfg = get_config("qwen2_0_5b").smoke()
params, axes = api.init_params(jax.random.PRNGKey(0), cfg)
cfg = dataclasses.replace(cfg, softmax_mode="exact", norm_mode="exact",
                          logit_int8=False)
mesh = make_mesh(SHAPE, ("data", "model"))
rules = make_rules(mesh)
rng = np.random.default_rng(41)
reqs = ([Request(prompt=rng.integers(0, cfg.vocab_size, 16)
                 .astype(np.int32), max_new_tokens=8) for _ in range(2)] +
        [Request(prompt=rng.integers(0, cfg.vocab_size, 16)
                 .astype(np.int32), max_new_tokens=7, temperature=0.9,
                 top_k=6, seed=5) for _ in range(2)])
spec = SpecConfig(DraftModelDrafter(cfg, params, window=64), max_k=4)
eng = PagedEngine(cfg, params, num_blocks=40, block_size=8,
                  max_seq_len=64, max_running=4, decode_batch=4,
                  prefill_chunk=8, backend="pallas", rules=rules,
                  param_axes=axes, spec_config=spec)
assert eng.generate(reqs) == REF, "spec parity under mesh"
st = eng.stats()
assert st["spec_dispatches"] > 0 and st["acceptance_rate"] > 0.9, st
eng.cache.check_refcounts()
print("SPEC-MESH-PASS")
"""


@pytest.mark.parametrize("shape", [(1, 1), (1, 2)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_spec_decode_under_mesh(exact_lm, shape):
    """Speculative decoding under the PR 6 tensor-parallel plan: the
    verify dispatch and the drafter both trace inside the mesh/rules
    context and must reproduce the single-device plain-decode stream."""
    only = os.environ.get("SPEC_DECODE_MESH", "")
    if only and f"{shape[0]}x{shape[1]}" != only:
        pytest.skip(f"SPEC_DECODE_MESH={only}")
    cfg, params = exact_lm
    rng = np.random.default_rng(41)
    ref = _paged(cfg, params, decode_horizon=8).generate(
        _mixed_requests(cfg, rng))
    code = f"SHAPE = {shape!r}\nREF = {[list(o) for o in ref]!r}\n" \
        + _MESH_SNIPPET
    assert "SPEC-MESH-PASS" in run_with_devices(
        code, n_devices=shape[0] * shape[1])
