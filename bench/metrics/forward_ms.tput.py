"""forward_ms.tput (model step): the slice program's device time in the
traced window over the forwards it ran there (denoising steps and
commits: ``EngineStats`` nfe less the admission prefills)."""


def read(ctx):
    run = ctx.run
    t = ctx.red.program_s.get("slice") if ctx.red is not None else None
    n = (run.stats1["nfe"] - run.stats0["nfe"]
         - (run.stats1["prefill_nfe"] - run.stats0["prefill_nfe"]))
    if not t or n <= 0:
        return None
    return 1e3 * t / n
