"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cells are listed in ``BENCHMARK.json``.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``, each
number compared for ``correct`` beside its limit.  The same numbers end
standard error.
"""

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# The benchmark's own modules as ``bench.*``, the system under test from src.
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness

    cell = harness.resolve(args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T0)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
