"""ttft_p95_ms: 95th percentile over every request due in the window of the
time from its scheduled arrival to its first visible token (host clock).  A
request with no first token by the end of the drain counts at the drain's
end: it has failed and missed every limit."""
from chipbench.report import p95


def read(ctx):
    if ctx.mix["driver"] != "open_loop":
        return None
    due = ctx.due_in_window()
    if not due:
        return None
    return p95([((s.first if s.first is not None else ctx.drain_end)
                 - s.due) * 1e3 for s in due])
