"""The output check's control at a size a test run can hold: the
program's own W8A8 path, one precision step below the bf16 the
configuration states, put in the served path's place on the same
prompts and served tokens, reads well above the served path and fails
the limit that the served path passes. The served tokens come from the
engine's ``generate``, so that the sample does not hang on how fast
the machine runs a timed window."""
import importlib.util
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

from harness import check, runner, serve, spec, traffic  # noqa: E402

# four layers at a quarter of qwen2_0_5b's width, head_dim as published
MODEL = {"n_layers": 4, "d_model": 256, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 64, "d_ff": 512, "vocab_size": 8192, "qkv_bias": True,
         "mlp_kind": "swiglu", "norm_kind": "rmsnorm", "pos_kind": "rope",
         "rope_theta": 1000000.0, "softmax_mode": "exact",
         "norm_mode": "exact", "exp_bits": 4, "logit_int8": True,
         "dtype": "bfloat16", "kv_cache_dtype": "auto", "ops_backend": "auto"}
ENGINE = {"block_size": 16, "prefill_chunk": 32, "decode_batch": 4,
          "max_running": 4, "decode_horizon": 2, "max_seq_len": 128,
          "num_blocks": 40}
SEED = 2
# between the served path (0.00045) and the control (0.0027) at this size
LIMITS = {"mean_gap": 0.001}
MIX = {"loop": "open", "system_prompts": {"count": 2, "tokens": 32},
       "prompt_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                         "min": 8, "max": 40},
       "output_tokens": {"dist": "uniform", "min": 24, "max": 40},
       "sampling": {"temperature": 0.7, "top_k": 8, "greedy_every": 2}}


def _control_module():
    s = importlib.util.spec_from_file_location("bench_control",
                                               BENCH / "control.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_control_reads_above_the_sound_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench/configs/mid.json").write_text(json.dumps(
        {"name": "mid", "repo_config": "qwen2_0_5b", "reference": "dense",
         "model": MODEL, "engine": ENGINE}))
    (tmp_path / "bench/traffic/mid_chat.json").write_text(json.dumps(MIX))
    (tmp_path / "bench/cells/mid.chat.json").write_text(json.dumps(
        {"rate_per_s": 6, "prime_s": 1, "tail_s": 1, "drain_s": 60,
         "check": {"tokens": 300, "limits": LIMITS}}))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": "mid.chat", "config": "mid",
                           "traffic": "mid_chat", "chips": 1, "why": "t"}]
    bench["end_to_end"] = [m for m in bench["end_to_end"]
                           if m["name"] == "setup_s"]
    bench["per_layer"] = []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load(tmp_path, "mid.chat")

    from repro.serve.engine import Request
    params = runner.make_weights(cell, SEED)
    eng = serve.build(cell, serve.arch_config(cell), params)
    reqs = traffic.Generator(MIX, SEED, MODEL["vocab_size"]).open_loop(
        6, 0, 2)
    outs = eng.generate([Request(prompt=r.prompt, max_new_tokens=r.max_new)
                         for r in reqs])
    seqs = [(np.concatenate([r.prompt, o]).astype(np.int32),
             len(r.prompt) - 1, np.asarray(o, np.int32))
            for r, o in zip(reqs, outs)]
    del eng, params
    row = _control_module().compare(cell, SEED, seqs)
    served, ctl = row["served"], row["control"]
    assert row["tokens"] >= 300
    assert ctl["mean_gap"] > 3 * served["mean_gap"], (served, ctl)
    assert check.judge(served, LIMITS)[0] is True, (served, ctl)
    assert check.judge(ctl, LIMITS)[0] is False, (served, ctl)
    assert row["control_correct"] is False
