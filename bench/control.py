"""Readings that set the limits of the output check: the program's own
numbers over many seeds, and the control's, judged by the cell's limits.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it makes one run of the cell as ``run.py`` does (window,
sampled greedy requests, reference), then puts the control in the
program's place on the same sampled prompts and served tokens: the
program's own W8A8 path (int8 weights and activations, one precision
step below the bf16 the configurations state) through its public
``models.api.forward``, taking its first-ranked token at every compared
position. It prints one JSON line per seed: the program's numbers
(``served``), the control's (``control``), and ``control_correct``,
the control judged by the cell's limits, which has to be false. It
fails, as ``run.py`` does, where JAX finds no TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from harness import spec  # noqa: E402


def first_ranked(params, cfg, seqs):
    """The W8A8 forward's argmax at positions first .. len(seq) - 2 of
    each (seq, first, _) in ``seqs``, from the bf16 ``params``. Inputs
    are padded at the end to a multiple of 512 tokens, which the causal
    forward's earlier positions never see, so that few shapes compile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api
    from repro.sharding.rules import quantize_params
    vocab = cfg.vocab_size
    top = jax.jit(lambda p, t: jnp.argmax(
        api.forward(p, {"tokens": t}, cfg)[0, :, :vocab], -1))
    q = quantize_params(params)
    out = []
    for seq, first, _ in seqs:
        n = len(seq) - 1
        feed = np.zeros((1, -(-n // 512) * 512), np.int32)
        feed[0, :n] = seq[:-1]
        out.append(np.asarray(top(q, jnp.asarray(feed)))[first:n])
    return out


def compare(cell, seed: int, seqs) -> dict:
    """The program's and the control's numbers on the sampled ``seqs``
    of a run of ``seed``, on the weights the configuration's family makes
    from the seed, and the control judged by the cell's limits."""
    from harness import check, runner, serve
    params = runner.make_weights(cell, seed)
    picks = {"served": [x for _, _, x in seqs],
             "control": first_ranked(
                 params, serve.arch_config(cell, quant="w8a8"), seqs)}
    g = check.gaps(cell.family, params, check.reference_model(cell), seqs,
                   picks)
    del params
    gc.collect()
    row = {name: check.numbers(v) for name, v in g.items()}
    row["control_correct"], row["control_checks"] = check.judge(
        row["control"], cell.load["check"]["limits"])
    row["tokens"] = int(len(g["served"]))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = spec.load(ROOT, args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from harness import device, runner
    dev = device.info()
    try:
        device.require_chips(dev, cell.chips)
    except (device.NoChip, KeyError) as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    runner.enable_cache()
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        keep: dict = {}
        t0 = time.perf_counter()
        out = runner.run_cell(cell, seed, args.seconds, False, dev, t0,
                              keep=keep)
        seqs = keep.get("seqs", [])
        row = {"seed": seed, "correct": out["correct"],
               "metrics": out["metrics"], "served": None, "control": None,
               "control_correct": None}
        if seqs:
            row.update(compare(cell, seed, seqs))
        print("CONTROL " + json.dumps(row), flush=True)
        lines.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(json.dumps(r) for r in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
