"""dsg_ffn_csr_roofline: the least time the chip could take for the sparse
FFN of the traced decode steps over the device time of the `dsg_ffn_csr`
kernel.  FLOPs: every active lane's kept groups; bytes: one lane's kept
groups of the three matrices per layer and step, a lower bound (the union of
the lanes' groups is what an exact count would take)."""
from chipbench import workcount as wc

KERNEL = (r"^dsg_ffn_csr(\.\d+)?$",)


def read(ctx):
    steps = [r for r in ctx.traced_steps if r.lanes]
    if ctx.trace is None or not steps or not ctx.cfg["dsg"]["enabled"]:
        return None
    seconds, n = ctx.trace.ops(*KERNEL)
    if n == 0:
        return None
    lanes = sum(r.lanes for r in steps)
    least = wc.least_seconds(wc.ffn_csr_flops(ctx.cfg, lanes),
                             wc.ffn_csr_bytes(ctx.cfg, len(steps), lanes),
                             ctx.peaks)
    return 100.0 * least / seconds
