"""95th percentile (nearest rank) over all requests due in the window of
last-token stamp minus due time; an unfinished request counts as
beyond every percentile."""

from bench.readers import e2e_ms, nearest_rank


def read(ctx):
    return nearest_rank([e2e_ms(ctx, r) for r in ctx.served.requests], 95)
