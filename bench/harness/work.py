"""Operations and bytes each kernel's algorithm needs, from shapes.

Counts follow the algorithm, not an implementation: attention reads
the keys and values of a lane's live context once per KV head (not the
padded page table, not null lanes, not once per q head of a GQA
group); a norm reads its inputs and writes its outputs once in the
model dtype. A kernel that fetches less than it does today therefore
raises its roofline share, and one that fetches more lowers it.

What a token's work is made of comes from the configuration's family
module (``work(model)`` in ``bench/reference/<family>.py``), as a dict:

- ``block_params``: the weights a token multiplies in the blocks (an
  MoE counts the experts a token visits, leading dense layers at their
  own width);
- ``attention``: one ``(q heads, KV heads, head size, window)`` per
  attention layer, window 0 for the whole context;
- ``norms``: how many norms a token passes through, each over
  ``width`` channels of the residual stream;
- ``head_params``: the LM head's weights, multiplied once per row whose
  next token is needed.
"""
from __future__ import annotations

from typing import Iterable, Tuple

ACT_BYTES = 2          # bf16 activations and KV


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def _pairs_and_keys(start: int, n: int, window: int) -> Tuple[int, int]:
    """(query-key pairs, keys read) of rows at positions start .. start
    + n - 1, the row at p attending keys 0..p, or its last ``window``."""
    keys = start + n
    # sum over rows of (p + 1) keys, p = start .. start + n - 1
    pairs = n * start + n * (n + 1) // 2
    if not window or keys <= window:
        return pairs, keys
    # rows see min(p + 1, window) keys: full up to p + 1 = window
    lo = min(start, window)
    pairs = window * (window + 1) // 2 - lo * (lo + 1) // 2 \
        + window * (keys - max(start, window))
    return pairs, min(keys, n + window - 1)


def attention(counts: dict, queries: Iterable[Tuple[int, int]]):
    """(flops, bytes) of paged attention over every attention layer for
    query rows given as (first position, row count) spans of one lane:
    each row at position p attends keys 0..p (causal), or the last
    ``window`` of them, and the span reads the keys and values of its
    context once per KV head."""
    flops = nbytes = 0
    queries = list(queries)
    for h, kv, hd, window in counts["attention"]:
        for start, n in queries:
            pairs, keys = _pairs_and_keys(start, n, window)
            flops += 4 * h * hd * pairs                   # q.k and w.v
            nbytes += ACT_BYTES * (2 * kv * hd * keys     # K and V
                                   + 2 * h * hd * n)      # q in, context out
    return flops, nbytes


def norms(counts: dict, rows: int):
    """(flops, bytes) of every norm of a forward pass over ``rows``
    tokens: the first reads x and writes its output; the others add the
    residual (read x and r, write the sum and the output)."""
    tensors = 2 + 4 * (counts["norms"] - 1)
    return 0, rows * counts["width"] * ACT_BYTES * tensors


def model_flops(counts: dict, queries: Iterable[Tuple[int, int]],
                head_rows: int) -> float:
    """Model FLOPs of the given query spans: 2 per weight per token in
    every block, attention over the live context, and the LM head for
    ``head_rows`` rows (the rows whose next token is needed)."""
    queries = list(queries)
    tokens = sum(n for _, n in queries)
    head = 2 * counts["head_params"] * head_rows
    return (2 * counts["block_params"] * tokens
            + attention(counts, queries)[0] + head)


def served_spans(records, t0: float, t1: float, chunk: int):
    """(decode spans, prompt-chunk spans, first tokens) of the work
    that served what clients saw in (t0, t1], from their requests
    alone: a decode step for every token after a request's first that
    surfaced then (token i attends the prompt and i tokens), and the
    prompt chunks of every request whose whole prefill, from
    submission to first token, lies in the interval. Work that
    straddles an end is left out, so the counts are never high; null
    lanes and discarded draws count for nothing."""
    decode, prefill, firsts = [], [], 0
    for r in records:
        n = len(r.req.prompt)
        decode.extend((n + i - 1, 1) for i, w in enumerate(r.walls)
                      if i and t0 < w <= t1)
        if r.walls and r.submit >= t0 and r.walls[0] <= t1:
            prefill.extend((s, min(chunk, n - s)) for s in range(0, n, chunk))
            firsts += 1
    return decode, prefill, firsts
