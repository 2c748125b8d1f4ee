"""hlo_flops_ratio: the FLOPs XLA's cost analysis counts in the compiled
step, over the model FLOPs of a step (``bench/flops.py``).

It counts the recompute a row plan pays (BP replay, OverL halos) and
XLA's own rematerialisation; XLA leaves padded taps out, so an
unrematerialised column step reads a little under 1.  Cost analysis
counts a loop body once, so a step with a ``while`` loop gives no
reading."""


def read(ctx):
    if " while(" in ctx.hlo_text:
        return None
    flops = ctx.compiled.cost_analysis()["flops"]
    return flops / (ctx.model_flops_per_image * ctx.batch)
