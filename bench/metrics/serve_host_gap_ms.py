"""Median device idle time between consecutive executions of the serve
step, in milliseconds: the host's share of each token (sync, sampling,
dispatch) as the device sees it."""

from bench import stats

STEP = "jit_serve_step"


def read(ctx):
    steps = [m for m in ctx.trace.modules if m[0] == STEP]
    if len(steps) < 2:
        return None
    return stats.median([(b[1] - a[2]) * 1e-6 for a, b in zip(steps, steps[1:])])
