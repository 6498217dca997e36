"""Device time of the detection plane's GMM programs (the Pallas kernels
and the jitted wrappers that hold them, `gmm_kernels.py`) in the traced
window, in ms per second of window."""
from gmm_kernels import kernel_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    s = kernel_seconds(ctx.trace)
    return 1e3 * s / ctx.trace.window_s if s > 0 else None
