"""Operation and byte counts against hand counts, and the peaks table."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import device, spec, work  # noqa: E402

DENSE = spec.family_module(BENCH.parent, "dense")
# 2 layers, 4 q heads over 2 KV heads of width 8, d_model 16, d_ff 32
COUNTS = DENSE.work({"n_layers": 2, "d_model": 16, "n_heads": 4,
                    "n_kv_heads": 2, "head_dim": 8, "d_ff": 32,
                    "vocab_size": 100})


def test_decode_attention_counts_live_context_once_per_kv_head():
    # one decode query at position 9: 10 live keys
    flops, nbytes = work.attention(COUNTS, [(9, 1)])
    # per layer: q.k and w.v, 2 FLOPs a multiply-add, 4 heads x 8 x 10
    assert flops == 2 * (4 * 4 * 8 * 10)
    # per layer: K and V of 10 tokens for 2 KV heads, q in and out, bf16
    assert nbytes == 2 * 2 * (2 * 2 * 8 * 10 + 2 * 4 * 8 * 1)


def test_prefill_chunk_is_causal():
    # rows at positions 4, 5, 6 see 5, 6 and 7 keys
    flops, nbytes = work.attention(COUNTS, [(4, 3)])
    assert flops == 2 * (4 * 4 * 8 * (5 + 6 + 7))
    assert nbytes == 2 * 2 * (2 * 2 * 8 * 7 + 2 * 4 * 8 * 3)
    # spans add up
    two = work.attention(COUNTS, [(4, 3), (9, 1)])
    assert two == (flops + work.attention(COUNTS, [(9, 1)])[0],
                   nbytes + work.attention(COUNTS, [(9, 1)])[1])


def test_norm_bytes():
    # 3 rows: first norm reads x, writes out; 4 more norms read x and r
    # and write the sum and out (2 layers x 2 + final)
    _, nbytes = work.norms(COUNTS, 3)
    assert nbytes == 3 * 16 * 2 * (2 + 4 * 4)


def test_model_flops():
    per_layer = 16 * (4 + 2 * 2) * 8 + 4 * 8 * 16 + 3 * 16 * 32
    assert COUNTS["block_params"] == 2 * per_layer
    f = work.model_flops(COUNTS, [(9, 1)], head_rows=1)
    assert f == (2 * 2 * per_layer + work.attention(COUNTS, [(9, 1)])[0]
                 + 2 * 16 * 100)


def test_windowed_layer_reads_at_most_window_keys():
    # a full layer and a layer with a 5-key window, against row-by-row
    # counts: the row at p attends min(p + 1, 5) keys, and a span reads
    # the union of its rows' keys once
    counts = dict(COUNTS, attention=[(4, 2, 8, 0), (4, 2, 8, 5)])
    for start, n in [(0, 3), (2, 6), (4, 1), (9, 1), (3, 12), (20, 4)]:
        rows = range(start, start + n)
        full = sum(p + 1 for p in rows)
        seen = sum(min(p + 1, 5) for p in rows)
        keys = len(set(k for p in rows for k in range(max(0, p - 4), p + 1)))
        flops, nbytes = work.attention(counts, [(start, n)])
        assert flops == 4 * 4 * 8 * (full + seen)
        assert nbytes == 2 * (2 * 2 * 8 * (start + n + keys)
                              + 2 * 2 * 4 * 8 * n)
        assert keys <= 5 + n - 1


STABLELM = json.loads((BENCH / "configs" / "stablelm_1_6b_exact.json")
                      .read_text())["model"]


def test_dense_counts_at_stablelm_sizes_are_the_formula_numbers():
    # the numbers of the per-layer formulas (3 d f MLP weights and
    # attention in every layer, 2 + 8 n_layers norm tensors) at the
    # published sizes, for fixed decode and prefill spans
    c = DENSE.work(STABLELM)
    decode = [(127, 1), (1023, 1), (3070, 1)]
    prefill = [(0, 256), (256, 256), (768, 200)]
    assert c["block_params"] == 1233125376
    assert work.attention(c, decode) == (830275584, 830865408)
    assert work.attention(c, prefill) == (59970945024, 481296384)
    assert work.norms(c, 24) == (0, 19070976)
    assert work.model_flops(c, decode + prefill, head_rows=4) == \
        1825814675456


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
