"""Median over the traced batches of the ``serve.decode.stack`` span, in
milliseconds: stacking the copied logits of every token into the batch's
result."""

from bench import spans


def read(ctx):
    return spans.median_span_ms(ctx.trace, "serve.decode.stack")
