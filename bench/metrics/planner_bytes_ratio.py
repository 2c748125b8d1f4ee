"""planner_bytes_ratio: the bytes XLA reserves for the compiled step
(``memory_analysis()``: temp + arguments + outputs - aliased, through
``repro.obs.audit.memory_metrics``) over the planner's estimate for the
plan it resolved (``est_bytes_per_device``)."""


def read(ctx):
    est = ctx.plan.est_bytes_per_device
    return ctx.compiled_bytes / est if est else None
