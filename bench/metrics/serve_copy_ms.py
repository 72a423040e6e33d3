"""Median over the traced decode tokens of the ``serve.decode.copy`` span,
in milliseconds: the logits' copy to the host, after the device is done."""

from bench import spans


def read(ctx):
    return spans.median_span_ms(ctx.trace, "serve.decode.copy")
