"""queue_wait_p90_ms (scheduler): the 90th percentile of the time each
request due in the window waited from its due time to its admission, as
the scheduler reports it (``Response.queue_s``)."""
import numpy as np


def read(ctx):
    run = ctx.run
    q = [run.responses[u].queue_s * 1e3 for u in getattr(run, "measured", [])
         if u in run.responses]
    if not q:
        return None
    ctx.notes.append(f"queue_wait_p90_ms: n={len(q)}")
    return float(np.percentile(q, 90))
