"""Share of the flash prefill kernel's (row, q block, k block) triples
that its causal/window/valid_from gate ran over the window, candidates
pooled: `EngineStats.attn_blocks_run` over `attn_blocks` (window deltas
of every group prefill and backfill row, summed over attention layers).
Nothing where the program keeps no such counters."""


def read(ctx):
    stats = list(ctx.stats.values())
    if not stats or not all(hasattr(s, "attn_blocks") for s in stats):
        return None
    total = sum(s.attn_blocks for s in stats)
    return sum(s.attn_blocks_run for s in stats) / total if total else None
