"""95th percentile of the time a served walk waited in the server's
queue, from its submission to the dispatcher's last take of it, over the
window's served walks (the tickets' timestamps)."""
import numpy as np

from chipbench import spans


def read(ctx):
    ts = spans.timestamps(ctx.walks, "submitted_at", "taken_at")
    if not ts:
        return None
    waits = [taken - submitted for submitted, taken in ts]
    return 1e3 * float(np.percentile(waits, 95, method="higher"))
