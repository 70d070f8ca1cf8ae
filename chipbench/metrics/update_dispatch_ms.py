"""Seconds in the writer's ``slot_update.dispatch`` span (the fused
update program's call through its counts sync) per update batch acked,
in ms.  It includes waiting behind the walk program in flight;
``update_device_ms`` is the device's own share."""
from chipbench import spans


def read(ctx):
    seconds = spans.span_seconds(ctx, "slot_update.dispatch")
    acked = sum(u.status == "served" for u in ctx.updates)
    if seconds is None or acked == 0:
        return None
    return 1e3 * seconds / acked
