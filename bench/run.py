"""One run of one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It makes the cell's weights and traffic from ``--seed``, builds the
served path (``PagedEngine`` behind ``AsyncEngine``), warms up every
compiled shape the traffic uses, brings the cell to steady state,
measures for ``--seconds`` and then checks the served tokens against
the configuration's plain reference. The last line of stdout is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, ``breakdown`` (traced runs only) and ``checks``, the
numbers compared with their limits; those also end stderr.

It exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for, or when the program (``src/``) is not in
the checkout. The compile cache is JAX's persistent cache in
``.jax_cache/`` of the checkout, or where JAX_COMPILATION_CACHE_DIR says.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from harness import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("bench: --seed must be a non-negative integer",
              file=sys.stderr)
        return 2
    try:
        cell = spec.load(ROOT, args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is not in this checkout ({ROOT / 'src'})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from harness import device, runner
    dev = device.info()
    try:
        device.require_chips(dev, cell.chips)
    except (device.NoChip, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    runner.enable_cache()
    out = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          dev, T_START)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
