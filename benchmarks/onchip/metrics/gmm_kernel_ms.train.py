"""Device time of the four named GMM Pallas kernels (``gmm_score``,
``gmm_best``, ``gmm_stats``, ``gmm_update``: `program_trace.GMM_KERNEL_OPS`
on the trace's ``XLA Ops`` line) in the traced window, in ms per second of
window. Unlike ``gmm_device_ms.train`` it leaves out the jitted wrappers'
ops around the kernels; a program whose kernels carry no names reads
nothing."""
from program_trace import named_kernel_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    s = named_kernel_seconds(ctx.trace)
    return 1e3 * s / ctx.trace.window_s if s > 0 else None
