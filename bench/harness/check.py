"""The output check that decides ``correct``.

Served tokens of greedy requests are compared with the plain reference
of the cell's configuration, which recomputes each sampled request's
prompt and served tokens from the seed's weights in one causal pass:
for every served token, the gap by which the reference's logit of that
token lies below the reference's best logit at that position. A token
the reference also ranks first has gap 0; a near tie that rounding
resolved the other way has a small gap; a wrong token has a gap of
about the spread of the logits.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def reference_model(cell) -> dict:
    """What the reference needs: the model sizes and the page size the
    served path's online softmax follows."""
    return dict(cell.model, block_size=cell.engine["block_size"])


def sample(records, rng: np.random.Generator, tokens: int,
           window: tuple) -> List:
    """Greedy requests that served tokens in the window, drawn from the
    seed: the one that served the most, then others in random order
    until about ``tokens`` served tokens are in the sample."""
    t0, t1 = window
    pool = [r for r in records if r.req.greedy and len(r.tokens) >= 2
            and r.walls and r.walls[0] <= t1 and r.walls[-1] > t0]
    if not pool:
        return []
    pool.sort(key=lambda r: -len(r.tokens))
    chosen, rest = [pool[0]], pool[1:]
    total = len(pool[0].tokens)
    for i in rng.permutation(len(rest)):
        if total >= tokens:
            break
        chosen.append(rest[i])
        total += len(rest[i].tokens)
    return chosen


def sequences(chosen) -> List[tuple]:
    """(tokens fed, first compared position, served tokens) per sample."""
    out = []
    for rec in chosen:
        served = np.asarray(rec.tokens, np.int32)
        prompt = np.asarray(rec.req.prompt, np.int32)
        seq = np.concatenate([prompt, served])
        out.append((seq, len(prompt) - 1, served))
    return out


def gaps(ref, weights, model: dict, seqs, picks: Dict[str, list]):
    """Concatenated gaps per pick list over all sampled sequences, by the
    family module ``ref``'s ``logit_gaps``; ``picks[name][i]`` are the
    tokens chosen at sequence i's compared positions."""
    out: Dict[str, list] = {name: [] for name in picks}
    for i, (seq, first, _) in enumerate(seqs):
        g = ref.logit_gaps(weights, model, seq, first,
                           {name: p[i] for name, p in picks.items()})
        for name in picks:
            out[name].append(g[name])
    return {name: np.concatenate(v) for name, v in out.items()}


def numbers(g: np.ndarray) -> Dict[str, float]:
    """The numbers compared: the widest gap, the mean gap, and the share
    of compared tokens the reference does not rank first."""
    return {"max_gap": float(g.max()), "mean_gap": float(g.mean()),
            "off_top_share": float((g > 0).mean())}


def judge(values: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}) for every limited number;
    a cell with no limits is not correct."""
    checks = {k: {"value": values[k], "limit": lim}
              for k, lim in limits.items()}
    ok = bool(checks) and all(c["value"] <= c["limit"]
                              for c in checks.values())
    return ok, checks
