"""mfu.batch: the whole step's share of the chip's bf16 peak: the FLOPs the
configuration's arithmetic requires for the traced window's decode tokens
(weights at DSG's kept share, attention at each token's true depth, DRS on
refresh steps) and prefills, over the traced window and the peak."""
from chipbench import workcount as wc


def read(ctx):
    if ctx.trace is None or not ctx.traced_steps:
        return None
    flops = 0.0
    for r in ctx.traced_steps:
        flops += wc.decode_flops(ctx.cfg, r.lanes, r.depth_sum,
                                 r.refresh_lanes)
        flops += sum(wc.prefill_flops(ctx.cfg, p) for p in r.prompt_lens)
    return 100.0 * flops / (ctx.trace.window_s
                            * ctx.peaks["bf16_flops_per_s"])
