"""tpot_p95_ms: 95th percentile, over every request due in the window that
finished by the end of the drain, of (finish - first token) / (output
tokens - 1) (host clock)."""
from chipbench.report import p95


def read(ctx):
    if ctx.mix["driver"] != "open_loop":
        return None
    vals = [(s.finish - s.first) / (len(s.req.output) - 1) * 1e3
            for s in ctx.due_in_window()
            if s.req.status == "ok" and s.first is not None
            and len(s.req.output) > 1]
    return p95(vals) if vals else None
