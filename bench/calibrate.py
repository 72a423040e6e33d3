"""Readings that set a cell's limits: the compared numbers of the program
and of the control (the reference computed in float8 in the program's
place), on many seeds in one process.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 3 ... [--units 1]

For each seed it builds the cell's adapter as a run does, runs ``units``
units of work (enough to finish the traffic's longest requests), and prints
one JSON line with both readings.  The benchmark's runs never call it.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def readings(cell, seed: int, units: int, adapter=None) -> dict:
    ad = (adapter or cell.adapter().Adapter)(cell.config_name, cell.config,
                                             cell.traffic, seed)
    ad.setup()
    records = [ad.call() for _ in range(units)]
    ad.free()
    t = time.perf_counter()
    program = ad.readings(records)
    t_ref = time.perf_counter() - t
    control = ad.readings(records, control=True)
    return {"seed": seed, "program": program, "control": control,
            "reference_s": t_ref}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--units", type=int, default=1)
    args = ap.parse_args()

    from bench import harness

    cell = harness.resolve(args.workload)
    harness.devices_for(cell, require_tpu=True)
    harness.use_compile_cache()
    for seed in args.seeds:
        out = readings(cell, seed, args.units)
        out["elapsed_s"] = time.perf_counter() - T0
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
