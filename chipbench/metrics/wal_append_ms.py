"""Seconds in the write-ahead log's ``durable.wal_append`` span (encode,
write, flush, fsync) per update batch acked, in ms."""
from chipbench import spans


def read(ctx):
    seconds = spans.span_seconds(ctx, "durable.wal_append")
    acked = sum(u.status == "served" for u in ctx.updates)
    if seconds is None or acked == 0:
        return None
    return 1e3 * seconds / acked
