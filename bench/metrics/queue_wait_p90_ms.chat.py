"""p90 in ms of the queue wait of the requests admitted in the window: from
the request's scheduled arrival to the program's stamp `admitted_at`, when
a fill took it into a slot."""
from bench.e2e import percentile


def read(ctx):
    waits = ctx.get("queue_waits")
    return percentile(waits, 90) * 1e3 if waits else None
