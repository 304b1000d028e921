"""Chip smoke run: serve a model at its published width on a TPU.

One chip (the default) drives the served path a user calls —
``PagedEngine`` behind ``AsyncEngine``, SOLE softmax and norms,
``ops_backend`` auto, quantization off — with qwen2_0_5b at published
widths and random weights made from ``--seed``:

  python chip_smoke.py                 # one chip
  python chip_smoke.py --four-chips    # minitron_8b, TP over a (1, 4) mesh

``--four-chips`` runs only the tensor-parallel path: minitron_8b at
published widths with its parameters initialised already sharded, and
the same widths cut to two layers compared against one chip.

This is a smoke run, not a benchmark: the times it prints include
compilation and host noise. It checks what comes out (token budgets,
id ranges, page refcounts and leaks, logits against the reference
backend, or against one chip) and exits non-zero on the first failed
phase. It also fails, printing no result, when JAX finds no TPU or when
the repo's ``src/`` is not beside it. The last line of stdout is

  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``.jax_cache/`` of the checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"


class SmokeFailure(Exception):
    """A phase of the smoke run produced a wrong or missing result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(*parts) -> None:
    print(*parts, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(info: dict, chips: int) -> None:
    check(info["platform"] == "tpu",
          f"JAX found no TPU (platform {info['platform']!r})")
    check(info["count"] >= chips,
          f"needs {chips} TPU chips, JAX sees {info['count']}")


# -- traffic ------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Traffic:
    """Prompt lengths of the smoke trace: ``solo`` prompts of their own
    and ``len(shared_tails)`` prompts that share one ``shared``-token
    prefix, each followed by its own tail."""
    solo: tuple = (64, 128, 300, 512)
    shared: int = 256
    shared_tails: tuple = (32, 64, 128, 200)
    new_tokens: int = 32
    compare_len: int = 64        # prompt of the logits comparison

    @property
    def max_prompt(self) -> int:
        return max(max(self.solo),
                   self.shared + max(self.shared_tails, default=0))


def make_requests(cfg, traffic: Traffic, seed: int, prefill_chunk: int):
    """(request, arrival step) pairs. The first shared-prefix request
    arrives first; its followers arrive after its prefill has finished,
    so they attach its registered prefix pages."""
    import numpy as np

    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(0, cfg.vocab_size, n).astype(np.int32)

    prefix = toks(traffic.shared)
    shared = [np.concatenate([prefix, toks(t)]) for t in traffic.shared_tails]
    solo = [toks(n) for n in traffic.solo]
    later = -(-len(shared[0]) // prefill_chunk) + 2 if shared else 0
    plan = ([(shared[0], 0)] if shared else []) \
        + [(p, 1 + i) for i, p in enumerate(solo)] \
        + [(p, later + 2 * i) for i, p in enumerate(shared[1:])]
    return [(Request(prompt=p, max_new_tokens=traffic.new_tokens), a)
            for p, a in plan]


# -- engine helpers -----------------------------------------------------------


def engine_ctx(eng):
    """The mesh + rules context the engine's own step() enters."""
    from repro.sharding.rules import use_rules
    stack = contextlib.ExitStack()
    if eng.rules is not None:
        stack.enter_context(eng.rules.mesh)
        stack.enter_context(use_rules(eng.rules))
    return stack


def warm_up(eng, horizons) -> dict:
    """Compile the engine's jitted prefill and decode-horizon steps by
    running them once on all-null lanes (every write lands in the null
    page 0, which no read path treats as data), and check that each
    lowered step holds Pallas TPU kernels. Returns seconds per step."""
    import jax
    import jax.numpy as jnp
    nb, d = eng.cache.max_blocks_per_seq, eng.decode_batch

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    secs = {}
    with engine_ctx(eng):
        pre = (i32(1, eng.sched.prefill_chunk), i32(1), i32(1),
               {"tables": i32(1, nb)})
        steps = [("prefill", eng._prefill, pre, ())]
        for h in horizons:
            dec = (i32(d), i32(d), {"tables": i32(d, nb)},
                   jnp.zeros((d,), jnp.float32), i32(d),
                   jnp.zeros((d,), jnp.uint32), i32(d),
                   jnp.full((d, 1), -1, jnp.int32))
            steps.append((f"decode_h{h}", eng._decode_h, dec,
                          (h, False, False, False)))
        for name, fn, args, static in steps:
            t0 = time.perf_counter()
            text = fn.lower(eng.params, eng._state(), *args,
                            *static).as_text()
            check("tpu_custom_call" in text,
                  f"jitted {name} step holds no tpu_custom_call: the "
                  "Pallas kernels are not what runs")
            out = fn(eng.params, eng._state(), *args, *static)
            eng._put_state(out[-1])
            jax.block_until_ready(out)
            secs[name] = round(time.perf_counter() - t0, 3)
    return secs


def prefill_logits(eng, prompt):
    """Logits (len(prompt), vocab) of one prompt, prefilled chunk by
    chunk through the engine's jitted prefill step on pages the cache
    hands out and takes back."""
    import jax.numpy as jnp
    import numpy as np
    sid, c = -1, eng.sched.prefill_chunk
    eng.cache.attach(sid, [])
    check(eng.cache.append_tokens(sid, 0, len(prompt)) == [],
          "no pages for the comparison prompt")
    refs = {"tables": jnp.asarray(eng.cache.batch_tables([sid]))}
    rows = []
    with engine_ctx(eng):
        for start in range(0, len(prompt), c):
            real = min(c, len(prompt) - start)
            chunk = np.zeros((1, c), np.int32)
            chunk[0, :real] = prompt[start:start + real]
            logits, state = eng._prefill(
                eng.params, eng._state(), jnp.asarray(chunk),
                jnp.asarray(np.array([start], np.int32)),
                jnp.asarray(np.array([real], np.int32)), refs)
            eng._put_state(state)
            rows.append(np.asarray(logits)[0, :real, :eng.cfg.vocab_size])
    eng.cache.release(sid)
    return np.concatenate(rows).astype(np.float64)


def rel_l2(a, b) -> float:
    import numpy as np
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def serve(eng, reqs):
    """Serve ``(request, arrival)`` pairs through AsyncEngine and check
    every request's output. Returns (handles, wall seconds)."""
    import numpy as np

    from repro.serve.loop import AsyncEngine
    loop = AsyncEngine(eng)
    handles = [loop.add_request(r, arrival=a) for r, a in reqs]
    t0 = time.perf_counter()
    loop.run()
    wall = time.perf_counter() - t0
    vocab = eng.cfg.vocab_size
    for i, (h, (r, _)) in enumerate(zip(handles, reqs)):
        check(len(h.tokens) == r.max_new_tokens,
              f"request {i}: {len(h.tokens)} tokens, budget "
              f"{r.max_new_tokens}")
        check(h.finish_reason == "length",
              f"request {i} finished by {h.finish_reason!r}")
        ids = np.asarray(h.tokens)
        check(bool(((ids >= 0) & (ids < vocab)).all()),
              f"request {i}: token id outside [0, {vocab})")
    eng.cache.check_refcounts()
    check(eng.cache.blocks_in_use == 0,
          f"{eng.cache.blocks_in_use} pages still held after the run")
    check(eng.stats()["finished"] == len(reqs), "not every request finished")
    return handles, wall


def pool_blocks(traffic: Traffic, block_size: int) -> int:
    n = len(traffic.solo) + len(traffic.shared_tails)
    per_seq = -(-(traffic.max_prompt + traffic.new_tokens) // block_size)
    return n * per_seq + 1


# -- one chip -----------------------------------------------------------------


# Prefill logits of the pallas engine against the same engine built with
# backend="reference", same params, same pages, all 24 layers. A wrong
# page, head or layout in a kernel makes the logits unrelated to the
# reference: rel L2 near sqrt(2) (1.40 with a wrong KV head, measured in
# interpret mode).
#
# Exact mode: both backends compute the same math and differ only in
# rounding — the kernels keep fp32 tiles where XLA rounds bf16 between
# ops, 2^-8 relative per op (0.0192 measured in interpret mode at these
# widths and this seed).
EXACT_REL_L2 = 5e-2
# SOLE mode (the served one): the algorithms differ by design. The paged
# kernel runs E2Softmax online (paper Alg. 1), rescaling its running sum
# by the quantized Correction 2^-Log2Exp(dm) at every page boundary; the
# reference is two-pass over all keys. With random weights attention is
# near uniform, so logits sit within a step or two of the 4-bit Log2Exp
# grid, and a quantized Correction or a bf16-vs-fp32 rounding of a logit
# moves a whole page's weights by a factor of two. That is an O(0.2)
# difference (0.2625 measured in interpret mode), well below the ~1.4 of
# a broken kernel.
SOLE_REL_L2 = 0.5


def run_one_chip(cfg, traffic: Traffic, seed: int, *, block_size: int = 16,
                 prefill_chunk: int = 16, decode_batch: int = 8,
                 decode_horizon: int = 8) -> None:
    import jax

    from repro import ops
    from repro.models import api
    from repro.serve.engine import PagedEngine

    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab_size}, "
        f"softmax {cfg.softmax_mode}, norm {cfg.norm_mode}, "
        f"ops_backend {cfg.ops_backend}, quant {cfg.quant.mode}")
    norm = f"residual_{cfg.norm_kind}"
    resolved = {
        "paged_attention": ops.backend_for(cfg, "paged_attention",
                                           cfg.softmax_mode),
        f"{norm} (layers 1.., final norm)": ops.backend_for(
            cfg, norm, cfg.norm_mode),
        f"{cfg.norm_kind} (layer 0)": ops.backend_for(
            cfg, cfg.norm_kind, cfg.norm_mode),
    }
    log("resolved backend per op:", json.dumps(resolved))
    check(all(b == "pallas" for b in resolved.values()),
          f"an op on the served path does not run its kernel: {resolved}")

    t0 = time.perf_counter()
    params, _ = api.init_params(seed, cfg)
    jax.block_until_ready(params)
    log(f"params initialised on {jax.devices()[0].device_kind} in "
        f"{time.perf_counter() - t0:.3f} s")

    reqs = make_requests(cfg, traffic, seed, prefill_chunk)
    max_seq = traffic.max_prompt + traffic.new_tokens
    eng = PagedEngine(cfg, params, num_blocks=pool_blocks(traffic, block_size),
                      block_size=block_size, max_seq_len=max_seq,
                      max_running=len(reqs), decode_batch=decode_batch,
                      prefill_chunk=prefill_chunk,
                      decode_horizon=decode_horizon)
    check(eng.backend == "pallas", f"engine backend {eng.backend!r}")
    horizons = [1 << i for i in range(decode_horizon.bit_length())]
    compile_s = warm_up(eng, horizons)
    log("compile seconds per jitted step (first call, tpu_custom_call in "
        "each):", json.dumps(compile_s))

    handles, wall = serve(eng, reqs)
    stats = eng.stats()
    generated = sum(len(h.tokens) for h in handles)
    log(f"smoke run (not a benchmark): {len(reqs)} requests, {generated} "
        f"tokens generated in {wall:.3f} s wall")
    log("engine.stats():", json.dumps(stats))
    hit = traffic.shared * (len(traffic.shared_tails) - 1)
    check(stats["prefix_hit_tokens"] >= hit,
          f"prefix cache served {stats['prefix_hit_tokens']} prompt tokens, "
          f"expected >= {hit} from the shared prefix")
    check(stats["evictions"] == 0 and stats["preemptions"] == 0,
          "pool pressure in a pool sized for the whole trace")

    prompt = reqs[1][0].prompt[:traffic.compare_len]

    def engine(cfg, backend):
        return PagedEngine(cfg, params,
                           num_blocks=-(-len(prompt) // block_size) + 1,
                           block_size=block_size, max_seq_len=max_seq,
                           max_running=1, decode_batch=1,
                           prefill_chunk=prefill_chunk, backend=backend)

    exact = dataclasses.replace(cfg, softmax_mode="exact", norm_mode="exact")
    for mode, got, mode_cfg, limit in (
            (cfg.softmax_mode, prefill_logits(eng, prompt), cfg, SOLE_REL_L2),
            ("exact", prefill_logits(engine(exact, "pallas"), prompt), exact,
             EXACT_REL_L2)):
        want = prefill_logits(engine(mode_cfg, "reference"), prompt)
        err = rel_l2(got, want)
        agree = float((got.argmax(-1) == want.argmax(-1)).mean())
        log(f"{mode} mode: prefill logits of a {len(prompt)}-token prompt, "
            f"pallas vs reference backend: rel L2 {err:.6g} (limit {limit}),"
            f" argmax agreement {agree:.4f}")
        check(bool(abs(got).max() > 0) and err <= limit,
              f"{mode} mode: pallas logits differ from the reference by "
              f"rel L2 {err:.6g}")


# -- four chips ---------------------------------------------------------------


# The (1, 4) engine against one chip, exact mode, bf16, two layers. The
# programs differ only in where partial sums meet: tensor parallelism
# adds the wo and MLP-down partial products of four head/ff slices
# across chips, one chip adds them in one dot. bf16 rounding of those
# sums (2^-8 relative) bounds the difference; a wrong head map, KV slice
# or layout gives an error of order 1.
FOUR_CHIP_REL_L2 = 2e-2


def _to_bf16(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def run_four_chips(cfg, traffic: Traffic, seed: int, *,
                   block_size: int = 16, prefill_chunk: int = 16,
                   decode_horizon: int = 1, compare_layers: int = 2) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh, make_rules
    from repro.models import api
    from repro.models.layers import _paged_tp_plan
    from repro.serve.engine import PagedEngine

    devices = jax.devices()[:4]
    rules = make_rules(make_mesh((1, 4), ("data", "model"), devices=devices))
    plan = _paged_tp_plan(rules, cfg.n_heads, cfg.n_kv_heads)
    log(f"mesh (data 1, model 4); paged attention plan {plan}")
    check(plan == ("model", True),
          f"{cfg.name} is not in the matched-KV regime on 4 chips: {plan}")
    reqs = make_requests(cfg, traffic, seed, prefill_chunk)
    max_seq = traffic.max_prompt + traffic.new_tokens
    kw = dict(num_blocks=pool_blocks(traffic, block_size),
              block_size=block_size, max_seq_len=max_seq,
              max_running=len(reqs), decode_batch=len(reqs),
              prefill_chunk=prefill_chunk, decode_horizon=decode_horizon)

    # 1. comparison at the same widths, cut to two layers, exact mode
    small = dataclasses.replace(cfg, n_layers=compare_layers,
                                softmax_mode="exact", norm_mode="exact")
    params, axes = api.init_params(seed, small, rules)
    params = _to_bf16(params)   # both engines hold these exact values
    prompt = reqs[0][0].prompt[:traffic.compare_len]
    short = [(dataclasses.replace(r, max_new_tokens=8), a) for r, a in reqs]
    tp = PagedEngine(small, params, rules=rules, param_axes=axes, **kw)
    got = prefill_logits(tp, prompt)
    tp_toks = [h.tokens for h in serve(tp, short)[0]]
    host = jax.device_get(params)
    del tp, params
    one = PagedEngine(small, jax.device_put(host, devices[0]), **kw)
    want = prefill_logits(one, prompt)
    one_toks = [h.tokens for h in serve(one, short)[0]]
    del one, host
    err = rel_l2(got, want)
    same = sum(a == b for a, b in zip(tp_toks, one_toks))
    log(f"{compare_layers}-layer {cfg.name} widths, exact mode: (1,4) vs one "
        f"chip prefill logits rel L2 {err:.6g} (limit {FOUR_CHIP_REL_L2}), "
        f"argmax agreement "
        f"{float((got.argmax(-1) == want.argmax(-1)).mean()):.4f}, "
        f"greedy streams identical {same}/{len(tp_toks)}")
    check(err <= FOUR_CHIP_REL_L2,
          f"(1,4) logits differ from one chip by rel L2 {err:.6g}")

    # 2. the published model, initialised already sharded
    t0 = time.perf_counter()
    params, axes = api.init_params(seed, cfg, rules)
    jax.block_until_ready(params)
    per_dev = {}
    for leaf in jax.tree.leaves(params):
        for s in leaf.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) \
                + s.data.nbytes
    total = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(params))
    log(f"{cfg.name} params initialised sharded in "
        f"{time.perf_counter() - t0:.3f} s: {total / 2**30:.3f} GiB in all, "
        f"per chip {[round(b / 2**30, 3) for b in per_dev.values()]} GiB")
    check(max(per_dev.values()) < total / 2,
          "one chip holds more than half the parameter tree")
    eng = PagedEngine(cfg, params, rules=rules, param_axes=axes, **kw)
    compile_s = warm_up(eng, [1 << i for i in
                              range(decode_horizon.bit_length())])
    log("compile seconds per jitted step (first call, tpu_custom_call in "
        "each):", json.dumps(compile_s))
    handles, wall = serve(eng, reqs)
    log(f"smoke run (not a benchmark): {len(reqs)} requests, "
        f"{sum(len(h.tokens) for h in handles)} tokens generated in "
        f"{wall:.3f} s wall")
    log("engine.stats():", json.dumps(eng.stats()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repo sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    try:
        from repro.configs.base import get_config
        from repro.launch.compile_cache import enable_compile_cache
        log("compile cache:", enable_compile_cache())
        info = device_info()
        chips = 4 if args.four_chips else 1
        require_tpu(info, chips)
        log("device:", json.dumps(info))
        if args.four_chips:
            run_four_chips(get_config("minitron_8b"),
                           Traffic(solo=(64, 160), shared=128,
                                   shared_tails=(32, 96), new_tokens=16),
                           args.seed)
        else:
            run_one_chip(get_config("qwen2_0_5b"), Traffic(), args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
