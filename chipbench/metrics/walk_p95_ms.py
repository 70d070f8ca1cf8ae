"""95th percentile of walk latency on the client, from when each walk
was due to its answer, over every walk due in the window; a walk refused
counts as slower than any answered one."""
import numpy as np


def read(ctx):
    lat = [w.done - w.due if w.status == "served" else np.inf for w in ctx.walks]
    if not lat:
        return None
    p95 = float(np.percentile(lat, 95, method="higher"))
    return p95 * 1e3 if np.isfinite(p95) else None
