"""Mean offline accuracy label of the model that served each completed
request (CNNSelect's other axis)."""

from bench.readers import finished


def read(ctx):
    acc = [ctx.cfg["models"][r.model]["accuracy"]
           for r in ctx.served.requests if finished(r)]
    return sum(acc) / len(acc) if acc else None
