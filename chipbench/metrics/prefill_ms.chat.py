"""prefill_ms.chat: device time of the engine's admission programs in the
traced window (the 1-lane prefill, the splice of its pages into the pool and
the first token's pick) over the admissions in it (device trace, admission
count)."""

#: XLA module names of the admission programs, as a TPU v5e trace shows them
PROGRAMS = (r"^jit__prefill(_dsg)?$", r"^jit__paged_merge$", r"^jit__argmax$")


def read(ctx):
    admits = sum(r.admits for r in ctx.traced_steps)
    if ctx.trace is None or not admits:
        return None
    seconds, n = ctx.trace.modules(*PROGRAMS)
    if n == 0:
        return None
    return seconds / admits * 1e3
