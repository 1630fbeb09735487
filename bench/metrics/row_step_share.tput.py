"""row_step_share.tput (scheduler): the share of the row-steps the slice
program computed that the rows needed — ``EngineStats.seq_steps`` over
denoising forwards x batch size, across the window. Rows wait on the
slowest row of the batch, and dead slots ride along."""


def read(ctx):
    run = ctx.run
    d = {k: run.stats1[k] - run.stats0[k] for k in run.stats0}
    forwards = d["nfe"] - d["prefill_nfe"] - d["slices"]
    if forwards <= 0:
        return None
    return 100.0 * d["seq_steps"] / (forwards * ctx.mix.batch_size)
