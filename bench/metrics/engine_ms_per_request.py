"""Host-clock ms the engines spent in the window's calls (group prefill,
decode, backfill join; every candidate), summed over the whole window
and divided by the requests completed: what one request costs the chip,
batching, routing and host overhead included."""

from bench.readers import finished


def read(ctx):
    done = sum(1 for r in ctx.served.requests if finished(r))
    spent = sum(c.t1 - c.t0 for c in ctx.served.calls)
    return 1e3 * spent / done if done and spent > 0 else None
