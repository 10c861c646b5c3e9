"""Live requests per group prefill over the window, candidates pooled:
the requests the program gave a slot (`Request.wall_start` set), less
those it joined mid-group (`EngineStats.backfill_calls`, one each), over
the group prefills (`EngineStats.prefill_calls`). The loop counts the
same as `LoopStats.group_rows / groups`."""


def read(ctx):
    started = sum(1 for r in ctx.served.requests
                  if getattr(r, "wall_start", None) is not None)
    prefills = sum(s.prefill_calls for s in ctx.stats.values())
    joins = sum(s.backfill_calls for s in ctx.stats.values())
    return (started - joins) / prefills if started and prefills else None
