"""Which device programs of a trace are the detection plane's GMM work:
the jitted Pallas kernels (``gmm_update_pallas``, ``gmm_stats_pallas``) and
the jitted wrappers that hold the scoring kernels (``score_samples``,
``total_log_likelihood``), by their names on the trace's ``XLA Modules``
line. These are the calls `jobs.GmmRecorder` records, so time and counts
cover the same work; a wrapper's time includes its few ops around the
kernel (casts, the log-sum-exp and mean of ``total_log_likelihood``), until
the program names its ``pallas_call``s."""
from __future__ import annotations

import re

GMM_MODULES = re.compile(
    r"^jit_(gmm_\w+_pallas|score_samples|total_log_likelihood)\(")


def kernel_seconds(trace) -> float:
    return sum(s for n, s in trace.module_seconds.items()
               if GMM_MODULES.search(n))
