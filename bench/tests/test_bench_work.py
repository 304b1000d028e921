"""Operation and byte counts against hand counts, and the peaks table."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import device, work  # noqa: E402

# 2 layers, 4 q heads over 2 KV heads of width 8, d_model 16, d_ff 32
MODEL = {"n_layers": 2, "d_model": 16, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 8, "d_ff": 32, "vocab_size": 100}


def test_decode_attention_counts_live_context_once_per_kv_head():
    # one decode query at position 9: 10 live keys
    flops, nbytes = work.attention(MODEL, [(9, 1)])
    # per layer: q.k and w.v, 2 FLOPs a multiply-add, 4 heads x 8 x 10
    assert flops == 2 * (4 * 4 * 8 * 10)
    # per layer: K and V of 10 tokens for 2 KV heads, q in and out, bf16
    assert nbytes == 2 * 2 * (2 * 2 * 8 * 10 + 2 * 4 * 8 * 1)


def test_prefill_chunk_is_causal():
    # rows at positions 4, 5, 6 see 5, 6 and 7 keys
    flops, nbytes = work.attention(MODEL, [(4, 3)])
    assert flops == 2 * (4 * 4 * 8 * (5 + 6 + 7))
    assert nbytes == 2 * 2 * (2 * 2 * 8 * 7 + 2 * 4 * 8 * 3)
    # spans add up
    two = work.attention(MODEL, [(4, 3), (9, 1)])
    assert two == (flops + work.attention(MODEL, [(9, 1)])[0],
                   nbytes + work.attention(MODEL, [(9, 1)])[1])


def test_norm_bytes():
    # 3 rows: first norm reads x, writes out; 4 more norms read x and r
    # and write the sum and out (2 layers x 2 + final)
    _, nbytes = work.norms(MODEL, 3)
    assert nbytes == 3 * 16 * 2 * (2 + 4 * 4)


def test_model_flops():
    per_layer = 16 * (4 + 2 * 2) * 8 + 4 * 8 * 16 + 3 * 16 * 32
    assert work.layer_params(MODEL) == 2 * per_layer
    f = work.model_flops(MODEL, [(9, 1)], head_rows=1)
    assert f == (2 * 2 * per_layer + work.attention(MODEL, [(9, 1)])[0]
                 + 2 * 16 * 100)


def test_roofline_takes_the_larger_bound():
    peak = device.peaks("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert work.roofline_s(197e12, 0, peak) == pytest.approx(1.0)
    assert work.roofline_s(197e12, 2 * 819e9, peak) == pytest.approx(2.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(device.NoChip):
        device.require_chips({"platform": "cpu", "kind": "cpu",
                              "count": 1}, 1)
    with pytest.raises(device.NoChip):
        device.require_chips({"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1}, 4)


def test_served_spans_count_whole_work_inside_the_interval():
    def rec(prompt, submit, walls):
        req = type("Q", (), {"prompt": [0] * prompt})()
        return type("R", (), {"req": req, "submit": submit,
                              "walls": walls})()
    records = [
        # prefilled before the interval; tokens 2 and 3 surface inside
        rec(10, 0.0, [0.5, 0.9, 1.2, 1.8, 2.5]),
        # submitted and prefilled inside it: 3 chunks of 4, 4, 2 rows,
        # first token (from the prefill) and one decode token inside
        rec(10, 1.1, [1.5, 1.9]),
        # submitted inside, first token after: nothing counted
        rec(6, 1.95, [2.2]),
    ]
    decode, prefill, firsts = work.served_spans(records, 1.0, 2.0, 4)
    # token i of a request attends its prompt and i tokens, fed at
    # position prompt + i - 1
    assert sorted(decode) == [(10, 1), (11, 1), (12, 1)]
    assert prefill == [(0, 4), (4, 4), (8, 2)]
    assert firsts == 1
