"""Host ms per engine call spent copying the returned logits to the host
(`EngineStats.fetch_time_s` over the window's group prefills, decode
steps and backfill joins, candidates pooled)."""


def read(ctx):
    stats = list(ctx.stats.values())
    if not stats or not all(hasattr(s, "fetch_time_s") for s in stats):
        return None
    calls = sum(s.prefill_calls + s.decode_calls + s.backfill_calls
                for s in stats)
    spent = sum(s.fetch_time_s for s in stats)
    return 1e3 * spent / calls if calls else None
