"""Median over the traced batches of the ``serve.compile`` span, in
milliseconds: building the batch's cache and lowering and compiling its
step, which ``serve_batch`` does on every call."""

from bench import spans


def read(ctx):
    return spans.median_span_ms(ctx.trace, "serve.compile")
