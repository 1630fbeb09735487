"""prefill_rows_per_req (admission): the rows the admission forwards
computed per request admitted in the window, ``EngineStats.prefill_rows``
over ``requests``. 1.0 when every boundary admits a power-of-two count of
rows; above it where the row bucket pads. A program without the counter
reads nothing."""


def read(ctx):
    run = ctx.run
    if "prefill_rows" not in run.stats0:
        return None
    requests = run.stats1["requests"] - run.stats0["requests"]
    if requests <= 0:
        return None
    return (run.stats1["prefill_rows"] - run.stats0["prefill_rows"]) / requests
