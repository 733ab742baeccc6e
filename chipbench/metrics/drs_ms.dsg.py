"""drs_ms.dsg: device time of the DRS search kernels (`drs_project`,
`drs_scores`) in the traced window over its decode steps."""

KERNELS = (r"^drs_project(\.\d+)?$", r"^drs_scores(\.\d+)?$")


def read(ctx):
    steps = [r for r in ctx.traced_steps if r.lanes]
    if ctx.trace is None or not steps or not ctx.cfg["dsg"]["enabled"]:
        return None
    seconds, n = ctx.trace.ops(*KERNELS)
    if n == 0:
        return None
    return seconds / len(steps) * 1e3
