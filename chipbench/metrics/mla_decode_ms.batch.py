"""mla_decode_ms.batch: device time of the `mla_decode` kernel (latent
attention over the paged rows) in the traced window over its decode steps
(device trace, step count).  None on a run without the kernel."""

KERNEL = (r"^mla_decode(\.\d+)?$",)


def read(ctx):
    steps = [r for r in ctx.traced_steps if r.lanes]
    if ctx.trace is None or not steps:
        return None
    seconds, n = ctx.trace.ops(*KERNEL)
    if n == 0:
        return None
    return seconds / len(steps) * 1e3
