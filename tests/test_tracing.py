"""Host spans and device scopes of the served path.

``AsyncEngine`` and ``PagedEngine`` write ``jax.profiler.TraceAnnotation``
spans (``loop.*``, ``engine.*``) into the profiler's trace, and the
paged forward and decode horizon run each sublayer under a stable
``jax.named_scope``. A trace reduction finds both by name, so these
tests pin the names, their nesting, their arguments, and that tracing
leaves the served tokens bit-identical.
"""
import dataclasses
import re
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import api
from repro.serve.engine import PagedEngine, Request
from repro.serve.loop import AsyncEngine

SCOPES = ("embed", "norm", "qkv", "kv_write", "paged_attention",
          "attn_out", "mlp", "lm_head", "sample")


@pytest.fixture(scope="module")
def exact_lm():
    cfg = get_config("qwen2_0_5b").smoke()
    params, _ = api.init_params(jax.random.PRNGKey(0), cfg)
    cfg = dataclasses.replace(cfg, softmax_mode="exact", norm_mode="exact",
                              logit_int8=False)
    return cfg, params


def _engine(cfg, params):
    return PagedEngine(cfg, params, num_blocks=40, block_size=8,
                       max_seq_len=64, max_running=4, decode_batch=4,
                       prefill_chunk=8, decode_horizon=4)


def _requests(cfg):
    rng = np.random.default_rng(3)
    # two greedy requests and one sampled with top-k; prompts span two
    # prefill chunks, so each request has a chunk that is not its last
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=m, **kw)
            for n, m, kw in ((12, 9, {}), (10, 6, {}),
                             (13, 7, dict(temperature=0.8, top_k=5,
                                          seed=11)))]


def _record_calls(eng):
    """Wrap the engine's jitted steps so each call's arguments are kept
    as shapes (the state is donated) and its static values as given."""
    calls = {"_prefill": [], "_decode_h": []}
    for name, seen in calls.items():
        inner = getattr(eng, name)

        def rec(*args, inner=inner, seen=seen):
            seen.append(jax.tree.map(
                lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                           if isinstance(a, jax.Array) else a), args))
            return inner(*args)
        setattr(eng, name, rec)
    return calls


def _serve(cfg, params, traced: bool):
    """Serve the requests behind AsyncEngine, under an active trace or
    not: (tokens per request, seq ids, calls, spans)."""
    eng = _engine(cfg, params)
    calls = _record_calls(eng)
    loop = AsyncEngine(eng)
    handles = [loop.add_request(r, arrival=2 * i)
               for i, r in enumerate(_requests(cfg))]
    spans = None
    if traced:
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            try:
                loop.run()
            finally:
                jax.profiler.stop_trace()
            spans = _host_spans(d)
    else:
        loop.run()
    return ([h.tokens for h in handles], [h._seq.seq_id for h in handles],
            calls, spans)


def _host_spans(trace_dir):
    """[(name, start, end, args, line)] of every ``loop.*`` and
    ``engine.*`` span on the host plane."""
    from jax.profiler import ProfileData
    path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("loop.", "engine.")):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats), line.name))
    return out


def _parent(spans, s):
    """The innermost other span on the same thread that encloses ``s``."""
    outer = [p for p in spans if p is not s and p[4] == s[4]
             and p[1] <= s[1] and s[2] <= p[2]]
    return min(outer, key=lambda p: p[2] - p[1], default=None)


@pytest.fixture(scope="module")
def traced(exact_lm):
    cfg, params = exact_lm
    return _serve(cfg, params, traced=True)


def test_spans_nest_on_the_serving_thread(traced):
    _, _, _, spans = traced
    names = {s[0] for s in spans}
    assert {"loop.step", "loop.admit", "loop.drain", "engine.step",
            "engine.admit", "engine.prefill", "engine.decode",
            "engine.stage", "engine.dispatch", "engine.sync",
            "engine.finish", "engine.reap"} <= names
    parent = {id(s): _parent(spans, s) for s in spans}
    for s in spans:
        p = parent[id(s)]
        if s[0] == "loop.step":
            assert p is None
        elif s[0] in ("loop.admit", "loop.drain", "engine.step"):
            assert p[0] == "loop.step"
        elif s[0] in ("engine.admit", "engine.prefill", "engine.decode",
                      "engine.reap"):
            assert p[0] == "engine.step"
        elif s[0] in ("engine.stage", "engine.dispatch", "engine.sync",
                      "engine.finish"):
            assert p[0] in ("engine.prefill", "engine.decode")
    for d in (s for s in spans if s[0] == "engine.decode"):
        kids = [c[0] for c in spans if parent[id(c)] is d]
        assert kids == ["engine.stage", "engine.dispatch", "engine.sync",
                        "engine.finish"]


def test_span_args_name_the_request_and_horizon(traced):
    _, seq_ids, calls, spans = traced
    prefill = [s[3] for s in spans if s[0] == "engine.prefill"]
    assert {a["seq"] for a in prefill} == set(seq_ids)
    for sid in seq_ids:
        chunks = sorted((a["start"], a["rows"]) for a in prefill
                        if a["seq"] == sid)
        # the chunks of one request tile its prompt from position 0
        assert chunks[0][0] == 0
        assert all(a + n == b for (a, n), (b, _) in zip(chunks, chunks[1:]))
    decode = [s[3] for s in sorted(spans, key=lambda s: s[1])
              if s[0] == "engine.decode"]
    # the horizon each decode span names is the one it dispatched
    assert [a["h"] for a in decode] == [c[10] for c in calls["_decode_h"]]
    assert all(1 <= a["lanes"] <= 4 for a in decode)
    admitted = sum(s[3]["n"] for s in spans if s[0] == "loop.admit")
    assert admitted == len(seq_ids)


def test_outputs_bit_identical_under_trace(exact_lm, traced):
    cfg, params = exact_lm
    tokens, _, _, _ = traced
    plain, _, _, _ = _serve(cfg, params, traced=False)
    assert tokens == plain
    assert sum(len(t) for t in tokens) == 9 + 6 + 7
    closed = _engine(cfg, params).generate(_requests(cfg))
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            closed_traced = _engine(cfg, params).generate(_requests(cfg))
        finally:
            jax.profiler.stop_trace()
    assert closed_traced == closed


@pytest.mark.parametrize("step,scopes", [
    ("_prefill", SCOPES[:-1]),
    ("_decode_h", SCOPES),
])
def test_jitted_steps_carry_every_scope(exact_lm, traced, step, scopes):
    """Every sublayer scope is in the op locations of the lowered step,
    so a refactor cannot drop one without this failing."""
    cfg, params = exact_lm
    _, _, calls, _ = traced
    args = calls[step][-1]
    text = getattr(_engine(cfg, params), step).lower(*args).as_text(
        debug_info=True)
    parts = {p for loc in re.findall(r'loc\("([^"]*)"', text)
             for p in loc.split("/")}
    assert set(scopes) <= parts
    assert "sample" not in parts or step == "_decode_h"
