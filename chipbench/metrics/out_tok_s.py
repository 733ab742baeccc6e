"""out_tok_s: output tokens that became visible to the host inside the
window, over the window's seconds (host clock; backlog mixes)."""


def read(ctx):
    if ctx.mix["driver"] != "backlog":
        return None
    t0, t1 = ctx.window
    return sum(r.lanes for r in ctx.steps if t0 <= r.end <= t1) / (t1 - t0)
