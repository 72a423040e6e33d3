"""Median over the traced serve-step executions of the device self time of
the ops under the ``attention`` scope, in milliseconds: every layer's QKV
and output projections, cache update, attention math and residual add.

An op's scope is the innermost of ``repro.models.SCOPES`` in its
``op_name``, read from the HLO text of the compiled step the traced batch
ran on (the record's ``step``)."""

from bench import spans, stats

STEP = "jit_serve_step"


def read(ctx):
    step = ctx.traced[0].get("step") if ctx.traced else None
    if step is None or not isinstance(ctx.trace, spans.SpanReduced):
        return None
    from repro.models import SCOPES

    steps = [m for m in ctx.trace.modules if m[0] == STEP]
    split = spans.step_scope_s(ctx.trace.ops, steps,
                               spans.op_scopes(step.as_text(), SCOPES))
    if not split:
        return None
    return 1e3 * stats.median([s.get("attention", 0.0) for s in split])
