"""decode_step_ms.batch: device time of the engine's jitted decode programs
over the decode steps in the traced window (device trace, step count)."""

#: XLA module names of the decode programs, as a TPU v5e trace shows them:
#: ServingEngine's jitted _decode_greedy / _decode_sample and their DSG twins
PROGRAMS = (r"^jit__(decode|dsg)_(greedy|sample)$",)


def read(ctx):
    steps = [r for r in ctx.traced_steps if r.lanes]
    if ctx.trace is None or not steps:
        return None
    seconds, n = ctx.trace.modules(*PROGRAMS)
    if n == 0:
        return None
    return seconds / len(steps) * 1e3
