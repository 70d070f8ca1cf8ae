"""95th percentile of latency over the walks answered, from when each
was due: above capacity the walk queue grows through the window."""
import numpy as np


def read(ctx):
    lat = [w.done - w.due for w in ctx.walks if w.status == "served"]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
