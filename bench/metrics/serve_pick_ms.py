"""Median over the traced decode tokens of the ``serve.decode.pick`` span,
in milliseconds: choosing each row's next token on the host."""

from bench import spans


def read(ctx):
    return spans.median_span_ms(ctx.trace, "serve.decode.pick")
