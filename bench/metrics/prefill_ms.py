"""Host-clock ms per engine group prefill over the window (EngineStats,
every candidate pooled)."""


def read(ctx):
    n = sum(s.prefill_calls for s in ctx.stats.values())
    t = sum(s.prefill_time_s for s in ctx.stats.values())
    return 1e3 * t / n if n else None
