"""moe_experts_roofline: the least time the chip could take for the held
experts' products of the traced window over the device time of the
`moe_experts` kernel.  The work is what the engine counted on its step and
admission spans (`moe_rows`, `moe_experts_hit`; active lanes and true prompt
tokens only): the three matrices of every expert hit, each routed row read
and written, and 6 x hidden x moe_intermediate FLOPs a row (the model
module's `moe_flops`, `moe_bytes`).  None on a run without the kernel or
the counts: a dense model, or a program that records neither."""
from chipbench import bench
from chipbench import workcount as wc

KERNEL = (r"^moe_experts(\.\d+)?$",)
SPANS = ("repro.engine.step", "repro.engine.admit")


def read(ctx):
    if ctx.trace is None or not ctx.traced_steps:
        return None
    seconds, n = ctx.trace.ops(*KERNEL)
    if n == 0:
        return None
    try:
        from repro.serving import telemetry
    except ImportError:                 # a program that records no spans
        return None
    t0, t1 = ctx.traced_steps[0].start, ctx.traced_steps[-1].end
    rows = hit = 0
    for rec in telemetry.recorders():
        for s in rec.spans(t0, t1):
            if s.name in SPANS:
                rows += s.attrs.get("moe_rows", 0)
                hit += s.attrs.get("moe_experts_hit", 0)
    if not hit:
        return None
    model = bench.model(ctx.cfg)
    least = wc.least_seconds(model.moe_flops(ctx.cfg, rows),
                             model.moe_bytes(ctx.cfg, rows, hit), ctx.peaks)
    return 100.0 * least / seconds
