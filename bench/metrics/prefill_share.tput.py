"""prefill_share (admission): the admission program's device time (the
whole-batch prefill of ``make_admit_fn``) as a share of the traced
window, from the trace."""


def read(ctx):
    return ctx.program_share("admission")
