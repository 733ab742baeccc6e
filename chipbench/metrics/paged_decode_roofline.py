"""paged_decode_roofline: the least time the chip could take for the paged
decode attention of the traced steps (K and V at each lane's true depth, q and
out, at the published peaks) over the device time of the `paged_decode`
kernel."""
from chipbench import workcount as wc

KERNEL = (r"^paged_decode(\.\d+)?$",)


def read(ctx):
    steps = [r for r in ctx.traced_steps if r.lanes]
    if ctx.trace is None or not steps:
        return None
    seconds, n = ctx.trace.ops(*KERNEL)
    if n == 0:
        return None
    lanes = sum(r.lanes for r in steps)
    depth = sum(r.depth_sum for r in steps)
    least = wc.least_seconds(wc.attn_flops(ctx.cfg, depth),
                             wc.attn_bytes(ctx.cfg, lanes, depth), ctx.peaks)
    return 100.0 * least / seconds
