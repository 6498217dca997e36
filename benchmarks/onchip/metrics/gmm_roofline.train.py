"""Share of their roofline that the GMM kernels reach in the window, in %:
the least time the chip needs for the operations and bytes of every kernel
call recorded in the window (``counts.gmm_kernel_counts``; at D = 3-4 the
bytes set the bound) over the kernels' device time in the trace."""
from counts import gmm_kernel_counts, roofline_seconds
from gmm_kernels import kernel_seconds
from peaks import peaks


def read(ctx):
    if ctx.trace is None or not ctx.gmm_shapes:
        return None
    s = kernel_seconds(ctx.trace)
    if s <= 0:
        return None
    peak = peaks(ctx.device_kind)
    least = sum(roofline_seconds(**_counts(kind, rows, D, K), peak=peak)
                ["seconds"] for kind, rows, D, K in ctx.gmm_shapes)
    return 100.0 * least / s


def _counts(kind, rows, D, K):
    c = gmm_kernel_counts(kind, rows, D, K)
    return {"flops": c["flops"], "nbytes": c["bytes"]}
