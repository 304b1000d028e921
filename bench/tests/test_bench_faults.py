"""A whole run of a cell at a tiny size on the CPU, past the look for a
chip: sound, it is correct; with the timed path broken underneath, the
output check says it is not."""
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import runner, spec  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 512, "qkv_bias": True,
         "mlp_kind": "swiglu", "norm_kind": "layernorm", "pos_kind": "rope",
         "rope_theta": 10000.0, "softmax_mode": "exact", "norm_mode": "exact",
         "exp_bits": 4, "logit_int8": True, "dtype": "bfloat16",
         "kv_cache_dtype": "auto", "ops_backend": "auto"}
ENGINE = {"block_size": 16, "prefill_chunk": 32, "decode_batch": 4,
          "max_running": 4, "decode_horizon": 2, "max_seq_len": 128,
          "num_blocks": 40}
MIX = {"loop": "closed", "first_wave": True,
       "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                         "min": 16, "max": 40},
       "output_tokens": {"dist": "uniform", "min": 8, "max": 16},
       "sampling": {"temperature": 0.7, "top_k": 8, "greedy_every": 2}}
LIMITS = {"max_gap": 0.1, "mean_gap": 0.01}


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    """A tiny cell added the way a later change adds one: new files and
    a BENCHMARK.json entry next to a copy of bench/."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench/configs/tiny.json").write_text(json.dumps(
        {"name": "tiny", "repo_config": "stablelm_1_6b",
         "reference": "dense", "model": MODEL, "engine": ENGINE}))
    (root / "bench/traffic/tiny_gen.json").write_text(json.dumps(MIX))
    (root / "bench/cells/tiny.gen.json").write_text(json.dumps(
        {"clients": 4, "requests_per_client": 3,
         "check": {"tokens": 60, "limits": LIMITS}}))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "tiny.gen", "config": "tiny",
                           "traffic": "tiny_gen", "chips": 1, "why": "t"}]
    bench["end_to_end"] = [dict(m, workloads=["tiny.gen"])
                           for m in bench["end_to_end"]
                           if m["name"] in ("setup_s", "tok_s")]
    bench["per_layer"] = [dict(m, workloads=["tiny.gen"])
                          for m in bench["per_layer"]
                          if m["name"] == "tokens_per_dispatch.longgen"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return spec.load(root, "tiny.gen")


def _run(cell, seed, after_build=None):
    return runner.run_cell(cell, seed, 1.5, False, CPU, time.perf_counter(),
                           after_build=after_build)


def _break_decode(fault):
    """Wrap the engine's decode horizon with one fault."""
    import jax
    import jax.numpy as jnp

    def hook(eng):
        decode = eng._decode_h
        vocab = eng.cfg.vocab_size

        def broken(params, state, token, pos, refs, *rest):
            if fault == "state unchanged":
                copy = jax.tree.map(jnp.copy, state)
                toks, done, _ = decode(params, copy, token, pos, refs, *rest)
                return toks, done, state
            toks, done, state = decode(params, state, token, pos, refs,
                                       *rest)
            if fault == "token altered":
                return (toks + 1) % vocab, done, state
            # half the batch left out: the second half of the lanes
            # gets the first half's tokens
            half = toks.shape[0] // 2
            return toks.at[half:].set(toks[:half]), done, state

        eng._decode_h = broken
    return hook


def test_sound_run_is_correct(cell):
    out = _run(cell, 2 ** 31 + 3)
    assert out["correct"] is True, out["checks"]
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    assert out["metrics"]["tok_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", ["token altered", "state unchanged",
                                   "half the batch left out"])
def test_broken_timed_path_is_not_correct(cell, fault):
    out = _run(cell, 2 ** 31 + 3, after_build=_break_decode(fault))
    assert out["correct"] is False, (fault, out["checks"])
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
    assert all(np.isfinite(c["value"]) for c in out["checks"].values())
