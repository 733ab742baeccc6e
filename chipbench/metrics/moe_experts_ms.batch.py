"""moe_experts_ms.batch: device time of the `moe_experts` kernel (the held
experts' grouped product) in the traced window over its decode steps
(device trace, step count).  None on a run without the kernel."""

KERNEL = (r"^moe_experts(\.\d+)?$",)


def read(ctx):
    steps = [r for r in ctx.traced_steps if r.lanes]
    if ctx.trace is None or not steps:
        return None
    seconds, n = ctx.trace.ops(*KERNEL)
    if n == 0:
        return None
    return seconds / len(steps) * 1e3
