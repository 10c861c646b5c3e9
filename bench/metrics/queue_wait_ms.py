"""Mean wall time a finished request waited in the program's queue, from
`ContinuousBatcher.submit` to the slot it was given
(`Request.wall_queued` to `Request.wall_start`), ms."""

from bench.readers import finished


def read(ctx):
    waits = [r.wall_start - r.wall_queued for r in ctx.served.requests
             if finished(r) and getattr(r, "wall_start", None) is not None
             and getattr(r, "wall_queued", None) is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None
