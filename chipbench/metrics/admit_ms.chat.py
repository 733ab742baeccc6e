"""admit_ms.chat: mean wall time of the engine's admissions in the traced
window, its `repro.engine.admit` spans: prefill dispatch, page splice, first
token pick and the wait for it (program spans, read in process through
`repro.serving.telemetry`).  `prefill_ms.chat` gives the device's part."""


def read(ctx):
    if not ctx.traced_steps:
        return None
    try:
        from repro.serving import telemetry
    except ImportError:                 # a program that records no spans
        return None
    t0, t1 = ctx.traced_steps[0].start, ctx.traced_steps[-1].end
    admits = [s.seconds for rec in telemetry.recorders()
              for s in rec.spans(t0, t1, "repro.engine.admit")]
    return 1e3 * sum(admits) / len(admits) if admits else None
