"""Batched serving example: prefill + decode with KV cache and sampling.

Run: PYTHONPATH=src python examples/serve_lm.py [--arch yi-6b] [--full]
(the reduced config by default; ``--full`` serves the published widths,
which for yi-6b means 12.1 GB of bf16 weights: one TPU v5e chip)
"""

import argparse
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.launch.serve import serve_batch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="the published widths (default: the reduced config)")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(1, 200, size=rng.integers(4, args.prompt_len + 1)))
        for _ in range(args.batch)
    ]
    width = "reduced" if args.smoke else "full width"
    print(f"serving {args.batch} requests (ragged prompts) on {args.arch} [{width}]")
    res = serve_batch(
        args.arch,
        prompts,
        smoke=args.smoke,
        max_new_tokens=args.max_new,
        cache_len=64,
        temperature=args.temperature,
    )
    print(f"prefill {res.prefill_s:.2f}s | decode {res.decode_s:.2f}s "
          f"| {res.tokens_per_s:.1f} tok/s")
    for i, row in enumerate(res.tokens):
        print(f"  req{i}: {row.tolist()}")


if __name__ == "__main__":
    main()
