"""The system under test, built from a cell: the repo's model config
with the sizes of the cell's config file, ``PagedEngine`` with the
file's engine settings, behind ``AsyncEngine``."""
from __future__ import annotations

import dataclasses
import time

import numpy as np


def arch_config(cell, quant: str = "off"):
    """The repo's ArchConfig named by the config file, with every model
    size of the file applied (the file holds the configuration as run)."""
    from repro.configs.base import ArchConfig, QuantConfig, get_config
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    sizes = {k: v for k, v in cell.model.items() if k in fields}
    return dataclasses.replace(get_config(cell.config["repo_config"]),
                               quant=QuantConfig(quant), **sizes)


def served_backends(cfg) -> dict:
    """The backend each op of the served path resolves to."""
    from repro import ops
    norm = f"residual_{cfg.norm_kind}"
    return {"paged_attention": ops.backend_for(cfg, "paged_attention",
                                               cfg.softmax_mode),
            norm: ops.backend_for(cfg, norm, cfg.norm_mode),
            cfg.norm_kind: ops.backend_for(cfg, cfg.norm_kind,
                                           cfg.norm_mode)}


def build(cell, cfg, params):
    from repro.serve.engine import PagedEngine
    e = cell.engine
    return PagedEngine(cfg, params, num_blocks=e["num_blocks"],
                       block_size=e["block_size"],
                       max_seq_len=e["max_seq_len"],
                       max_running=e["max_running"],
                       decode_batch=e["decode_batch"],
                       prefill_chunk=e["prefill_chunk"],
                       decode_horizon=e["decode_horizon"])


def warm_up(eng, traffic: dict) -> dict:
    """Compile (or load from the cache) every program the mix's batches
    use, through the engine's public ``generate``: a one-chunk prompt
    and a budget of twice the decode horizon run the prefill chunk and
    every horizon the scheduler can pick (8, 4, 2 and 1 for a horizon
    of 8), once as the mix's sampled requests and once as its greedy
    ones. A batch is greedy only if every lane in it is; in a closed
    loop that happens only while the first prompts prefill, when the
    horizon is pinned to 1, so there greedy requests warm that horizon
    alone. Returns seconds per request kind."""
    from repro.serve.engine import Request
    s = traffic["sampling"]
    every = s.get("greedy_every", 0)
    full = 2 * eng.decode_horizon
    kinds = [] if every == 1 else [("sampled", s["temperature"], full)]
    if every:
        kinds.append(("greedy", 0.0,
                      full if traffic["loop"] == "open" else 2))
    secs = {}
    for i, (name, temperature, budget) in enumerate(kinds):
        # prompts differ per kind so that the prefix cache serves none
        prompt = np.full(16, i + 1, np.int32)
        t0 = time.perf_counter()
        eng.generate([Request(prompt=prompt, max_new_tokens=budget,
                              temperature=temperature,
                              top_k=s.get("top_k", 0))])
        secs[name] = round(time.perf_counter() - t0, 3)
    return secs
