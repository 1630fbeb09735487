"""fused_step_roofline (kernels): the time the fused lm-head, confidence
and threshold kernel's calls need at the roofline (per call, from
``bench/counts.py``) over the kernel's device time in the trace."""


def read(ctx):
    return ctx.roofline("fused_step")
