"""Model operations of every token the traced serve steps processed, over
the traced window times the chip's peak, in percent."""

STEP = "jit_serve_step"


def read(ctx):
    steps = [m for m in ctx.trace.modules if m[0] == STEP]
    per_batch = ctx.traffic["prompt_len"] + ctx.traffic["max_new_tokens"]
    if not steps or len(steps) % per_batch:
        return None
    b = ctx.traffic["batch"]
    flops = sum(ctx.counts.step_flops(ctx.config, b, k % per_batch)
                for k in range(len(steps)))
    return 100.0 * flops / (ctx.trace.window_s * ctx.peak["bf16_flops_per_s"])
