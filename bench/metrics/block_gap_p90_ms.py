"""block_gap_p90_ms (scheduler): the 90th percentile, over every request
due in the window that was answered, of the gaps between its consecutive
decoded blocks, as the scheduler stamps them (``Response.block_s``: the
end of the wait for the slice that completed each block)."""
import numpy as np


def read(ctx):
    run = ctx.run
    gaps, n = [], 0
    for u in getattr(run, "measured", []):
        b = getattr(run.responses.get(u), "block_s", None)
        if not b:
            continue
        n += 1
        gaps += [(y - x) * 1e3 for x, y in zip(b, b[1:])]
    if not gaps:
        return None
    ctx.notes.append(f"block_gap_p90_ms: n={len(gaps)} gaps of {n} requests")
    return float(np.percentile(gaps, 90))
