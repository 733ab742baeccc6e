"""host_ms.batch: the engine's host time per decode step, over the decode steps
of the traced window: the length of a `repro.engine.step` span less the time
in the device waits inside it (`repro.engine.sync`, `repro.engine.first_token`)
(program spans, read in process through `repro.serving.telemetry`)."""

WAITS = ("repro.engine.sync", "repro.engine.first_token")


def read(ctx):
    if not ctx.traced_steps:
        return None
    try:
        from repro.serving import telemetry
    except ImportError:                 # a program that records no spans
        return None
    t0, t1 = ctx.traced_steps[0].start, ctx.traced_steps[-1].end
    host = []
    for rec in telemetry.recorders():
        spans = rec.spans(t0, t1)
        waits = [s for s in spans if s.name in WAITS]
        for s in spans:
            if s.name == "repro.engine.step" and s.attrs.get("lanes"):
                host.append(s.seconds - sum(
                    w.seconds for w in waits
                    if s.t0 <= w.t0 and w.t1 <= s.t1))
    return 1e3 * sum(host) / len(host) if host else None
