"""Megabytes (10^6 bytes) that one serve step's collective operations
deliver to one chip, read from the compiled step the traced batch ran on
(the record's ``step``) by ``repro.roofline.hlo_parse``: the result size
on one device of every all-reduce, all-gather, reduce-scatter, all-to-all
and collective-permute, an op in the layer scan's body once per iteration.
Nothing is read from the device trace, so the number is the step's, not a
time."""

from repro.roofline.hlo_parse import analyze_module


def read(ctx):
    step = ctx.traced[0].get("step") if ctx.traced else None
    if step is None:
        return None
    return analyze_module(step.as_text()).collective_bytes / 1e6
