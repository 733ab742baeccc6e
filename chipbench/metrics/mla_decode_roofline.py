"""mla_decode_roofline: the least time the chip could take for the latent
attention decode of the traced window over the device time of the
`mla_decode` kernel.  The work is what the engine counted on its dispatch
spans (`kv_rows`: keys read at the lanes' true depths) and step spans
(`lanes`), by the model module's counts: one cached row a key (the new one
written), each lane's query and output, and 2 x heads x (row + latent)
FLOPs a key, every layer (`attn_flops`, `attn_bytes`).  None on a run
without the kernel or the counts: a K/V model, or a program that records
no `kv_rows`."""
from chipbench import workcount as wc

KERNEL = (r"^mla_decode(\.\d+)?$",)


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
    rows = lanes = 0
    for rec in telemetry.recorders():
        for s in rec.spans(t0, t1):
            if s.name == "repro.engine.dispatch":
                rows += s.attrs.get("kv_rows", 0)
            elif s.name == "repro.engine.step":
                lanes += s.attrs.get("lanes", 0)
    if not rows:
        return None
    least = wc.least_seconds(wc.attn_flops(ctx.cfg, rows),
                             wc.attn_bytes(ctx.cfg, lanes, rows), ctx.peaks)
    return 100.0 * least / seconds
