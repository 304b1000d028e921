"""Scheduler: decode tokens per decode dispatch over the window (window
delta of the engine's own ``decode_tokens`` / ``decode_dispatches``
counters). Horizons longer than one token raise it; a pending prefill
pins the horizon to one and lowers it."""


def read(ctx):
    a, b = ctx.stats_open, ctx.stats_close
    n = b["decode_dispatches"] - a["decode_dispatches"]
    return (b["decode_tokens"] - a["decode_tokens"]) / n if n else None
