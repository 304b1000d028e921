"""Serving launcher: batched generation with SOLE active.

The execution backend for every softmax/norm/attention op resolves
through the ``repro.ops`` registry: ``--ops-backend auto`` compiles the
Pallas kernels on TPU and falls back to the pure-jnp reference
elsewhere; ``reference`` / ``pallas`` force one engine (``pallas``
interprets the kernel bodies off-TPU).

Example (CPU smoke):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch mixtral_8x7b --smoke \
    --requests 8 --prompt-len 16 --new-tokens 8

Paged continuous batching — every servable family goes through the one
scheduler/engine queue (dense, moe, ssm, hybrid, encdec; the family's
sequence_state_spec decides pages vs recurrent state slots vs shared
cross pages):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
    --engine paged --ops-backend pallas
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_7b --smoke \
    --engine paged
  PYTHONPATH=src python -m repro.launch.serve --arch whisper_small --smoke \
    --engine paged

Open-loop streaming (Poisson arrivals through the AsyncEngine run
loop, with early exit on --eos-ids and p50/p99 TTFT+ITL reported):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
    --engine paged --open-loop 0.5 --eos-ids 7 --stream

Speculative decoding (paged engine; draft model or model-free n-gram
drafting, batched K+1 verify, bit-for-bit accept-prefix):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
    --engine paged --spec-decode draft:qwen2_0_5b --spec-k 4
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
    --engine paged --spec-decode ngram

Sharded serving over a mesh (data x model; params laid out per the
logical-axis rules, paged attention split over the model axis) plus
data-parallel engine replicas behind one routed front door:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_0_5b --smoke \
    --engine paged --mesh 1,8 --replicas 2 --open-loop 0.5
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh, make_rules
from repro.models import api
from repro.serve.engine import Engine, PagedEngine, Request
from repro.serve.loop import AsyncEngine, ReplicatedAsyncEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--engine", choices=("dense", "paged"), default="dense",
                    help="dense-slot baseline or paged continuous batching")
    ap.add_argument("--decode-horizon", type=int, default=8,
                    help="max fused decode+sample steps per jitted "
                         "dispatch (paged engine; 1 = one host round "
                         "trip per token, sampling still in-jit)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="share identical block-aligned prompt prefixes "
                         "between sequences (paged engine only; default: "
                         "on iff the family's sequence_state_spec "
                         "supports it — forcing it on an unsupported "
                         "family is a hard error)")
    ap.add_argument("--watermark", type=int, default=1,
                    help="free pages held back at admission; higher = "
                         "fewer preemptions, lower = denser packing")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="KV pool pages (0 = sized from the request set)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k highest logits "
                         "(0 = full vocab)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base seed for per-request sampling streams")
    ap.add_argument("--eos-ids", default="",
                    help="comma-separated token ids that end a request "
                         "early (finish reason 'eos')")
    ap.add_argument("--open-loop", type=float, default=0.0, metavar="RATE",
                    help="serve through the AsyncEngine run loop with "
                         "Poisson arrivals at RATE requests per engine "
                         "step (paged engine only; 0 = closed batch)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they surface (open-loop mode)")
    ap.add_argument("--spec-decode", default="", metavar="MODE",
                    help="speculative decoding (paged engine only): "
                         "'ngram' = model-free prompt-lookup drafting, "
                         "'draft:<arch>' = a small draft model sharing "
                         "the target's vocab (e.g. draft:qwen2_0_5b), "
                         "'draft' = self-draft with the target's own "
                         "architecture; output streams stay bit-for-bit "
                         "identical to plain decode")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per lane per verify dispatch "
                         "(the EMA acceptance controller adapts each "
                         "lane's K below this)")
    ap.add_argument("--ops-backend",
                    choices=("auto", "reference", "pallas"), default="auto",
                    help="repro.ops execution backend for softmax/norm/"
                         "attention (auto = pallas on TPU, reference "
                         "elsewhere)")
    ap.add_argument("--quantize", choices=("off", "w8a16", "w8a8"),
                    default="off",
                    help="serve-path quantization: w8a16 packs every "
                         "projection weight to per-channel int8; w8a8 "
                         "additionally feeds the matmuls per-token int8 "
                         "activations straight from the norm ops "
                         "(off = bit-for-bit fp serving)")
    ap.add_argument("--mesh", default="",
                    help="comma-separated mesh shape over (data, model), "
                         "e.g. 1,8 — shards params and paged attention "
                         "per the logical-axis rules")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel PagedEngine replicas behind one "
                         "prefix-routed front door (paged open-loop "
                         "only; params are shared)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    cfg = dataclasses.replace(cfg, ops_backend=args.ops_backend)
    if args.quantize != "off":
        from repro.configs.base import QuantConfig
        cfg = dataclasses.replace(cfg, quant=QuantConfig(mode=args.quantize))
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(dims, ("data", "model")[:len(dims)])
        rules = make_rules(mesh)
    else:
        rules = None

    # with a mesh, params are initialised already laid out per the rules
    params, param_axes = api.init_params(args.seed, cfg, rules)
    rng = np.random.default_rng(args.seed)
    eos_ids = tuple(int(t) for t in args.eos_ids.split(",") if t.strip())
    # encdec requests carry synthetic encoder frames (the paged engine
    # runs the encoder once at admission and parks cross KV in pages).
    spec_state = (api.sequence_state_spec(cfg)
                  if args.engine == "paged" else None)

    def _frames():
        if spec_state is None or not spec_state.cross_tokens:
            return None
        return rng.standard_normal(
            (spec_state.cross_tokens, cfg.d_model)).astype(np.float32)

    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        size=args.prompt_len).astype(np.int32),
                    max_new_tokens=args.new_tokens,
                    temperature=args.temperature, top_k=args.top_k,
                    seed=args.sample_seed + i, eos_ids=eos_ids,
                    frames=_frames())
            for i in range(args.requests)]
    max_len = args.prompt_len + args.new_tokens
    if args.replicas > 1 and (args.engine != "paged"
                              or args.open_loop <= 0):
        raise SystemExit("--replicas requires --engine paged --open-loop")
    if args.spec_decode and args.engine != "paged":
        raise SystemExit("--spec-decode requires --engine paged")
    if args.engine == "paged":
        cross = ((spec_state.cross_tokens + 15) // 16
                 if spec_state is not None else 0)
        blocks = args.num_blocks or max(
            args.requests * ((max_len + 15) // 16 + 1 + cross), 16)
        from repro.serve.spec import spec_config_from_flag
        spec = spec_config_from_flag(args.spec_decode, cfg,
                                     max_k=args.spec_k, seed=args.seed,
                                     smoke=args.smoke)

        def make_engine(p, axes):
            return PagedEngine(cfg, p, num_blocks=blocks, block_size=16,
                               max_seq_len=max_len, max_running=args.batch,
                               decode_batch=args.batch,
                               decode_horizon=args.decode_horizon,
                               rules=rules, param_axes=axes,
                               prefix_cache=args.prefix_cache,
                               watermark=args.watermark,
                               spec_config=spec)

        eng = make_engine(params, param_axes)
        # replicas share the (already device-resident, possibly sharded)
        # param tree; each owns its own KV pool + scheduler.
        engines = [eng] + [make_engine(eng.params, None)
                           for _ in range(args.replicas - 1)]
    else:
        eng = Engine(cfg, params, batch_size=args.batch, max_len=max_len,
                     rules=rules)
    if args.open_loop > 0:
        if args.engine != "paged":
            raise SystemExit("--open-loop requires --engine paged")
        loop = (ReplicatedAsyncEngine(engines) if args.replicas > 1
                else AsyncEngine(eng))
        arrivals = np.cumsum(
            rng.exponential(1.0 / args.open_loop, len(reqs))).astype(int)
        on_token = None
        if args.stream:
            def on_token(h, tok):
                print(f"  req@{h.arrival} -> {tok}")
        t0 = time.perf_counter()
        handles = [loop.add_request(r, arrival=int(a), on_token=on_token)
                   for r, a in zip(reqs, arrivals)]
        loop.run()
        dt = time.perf_counter() - t0
        outs = [h.tokens for h in handles]
        total = sum(len(o) for o in outs)
        st = loop.stats()
        print(f"arch={cfg.name} engine=paged(open-loop) "
              f"replicas={args.replicas} requests={len(reqs)} "
              f"generated={total} tokens "
              f"in {dt:.2f}s ({total/dt:.1f} tok/s, "
              f"softmax={cfg.softmax_mode}, norm={cfg.norm_mode})")
        if args.replicas > 1:
            print(f"routing: {st['routed_by_prefix']} by prefix, "
                  f"{st['routed_by_load']} by load")
            for i, rep in enumerate(st["per_replica"]):
                print(f"  replica {i}: completed={rep['completed']} "
                      f"decode_tokens={rep['engine']['decode_tokens']} "
                      f"prefix_hit_rate="
                      f"{rep['engine']['prefix_hit_rate']}")
            return
        print(f"finish_reasons: {st['finish_reasons']}")
        print(f"TTFT steps p50/p99: {st['ttft_steps']['p50']}/"
              f"{st['ttft_steps']['p99']}  ms: {st['ttft_ms']['p50']}/"
              f"{st['ttft_ms']['p99']}")
        print(f"ITL  steps p50/p99: {st['itl_steps']['p50']}/"
              f"{st['itl_steps']['p99']}  ms: {st['itl_ms']['p50']}/"
              f"{st['itl_ms']['p99']}")
        print("engine stats:", st["engine"])
        return
    t0 = time.perf_counter()
    outs = eng.generate(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    print(f"arch={cfg.name} engine={args.engine} requests={len(reqs)} "
          f"generated={total} tokens "
          f"in {dt:.2f}s ({total/dt:.1f} tok/s, softmax={cfg.softmax_mode}, "
          f"norm={cfg.norm_mode}, ops_backend={cfg.ops_backend}, "
          f"quant={cfg.quant.mode})")
    if args.engine == "paged":
        print("stats:", eng.stats())
    for o in outs[:2]:
        print("sample:", o)


if __name__ == "__main__":
    main()
