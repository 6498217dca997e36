"""Mean host time of the benchmark's span around ``session.on_step`` per
training step in the window, in ms (the session's cadence work on the step
thread: flushes, admitted sweeps, incidents)."""


def read(ctx):
    return ctx.spans.mean_ms("on_step")
