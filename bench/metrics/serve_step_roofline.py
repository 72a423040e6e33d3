"""Median over the traced serve-step executions of the step's roofline
share, in percent: the least time the chip could take for the step's
operations and bytes (every weight, and the keys and values of the valid
positions, from ``bench/counts``) at its peaks, over the step's device time.

The k-th execution of a batch is the step at position ``k``: prefill runs
one step per prompt position, then decode one per new token."""

from bench import stats

STEP = "jit_serve_step"


def read(ctx):
    steps = [m for m in ctx.trace.modules if m[0] == STEP]
    per_batch = ctx.traffic["prompt_len"] + ctx.traffic["max_new_tokens"]
    if not steps or len(steps) % per_batch:
        return None
    b = ctx.traffic["batch"]
    shares = []
    for k, (_, start, end) in enumerate(steps):
        t = k % per_batch
        floor = max(ctx.counts.step_flops(ctx.config, b, t) / ctx.peak["bf16_flops_per_s"],
                    ctx.counts.step_bytes(ctx.config, b, t) / ctx.peak["hbm_bytes_per_s"])
        shares.append(floor / ((end - start) * 1e-9))
    return 100.0 * stats.median(shares)
