"""95th percentile of ack latency on the client, from when each update
was due to its ack, over every update due in the window; an update
refused counts as slower than any acked one."""
import numpy as np


def read(ctx):
    lat = [u.done - u.due if u.status == "served" else np.inf
           for u in ctx.updates]
    if not lat:
        return None
    p95 = float(np.percentile(lat, 95, method="higher"))
    return p95 * 1e3 if np.isfinite(p95) else None
