"""Share of their roofline that the four named GMM kernels reach in the
window, in %: the least time of every kernel call recorded in the window
(``counts.gmm_kernel_counts``, as ``gmm_roofline.train`` counts it) over
the named kernels' device time (`program_trace.named_kernel_seconds`), so
without the wrappers' ops that ``gmm_roofline.train`` divides by."""
from counts import gmm_kernel_counts, roofline_seconds
from peaks import peaks
from program_trace import named_kernel_seconds


def read(ctx):
    if ctx.trace is None or not ctx.gmm_shapes:
        return None
    s = named_kernel_seconds(ctx.trace)
    if s <= 0:
        return None
    peak = peaks(ctx.device_kind)
    least = 0.0
    for kind, rows, D, K in ctx.gmm_shapes:
        c = gmm_kernel_counts(kind, rows, D, K)
        least += roofline_seconds(c["flops"], c["bytes"], peak)["seconds"]
    return 100.0 * least / s
