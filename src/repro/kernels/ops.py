"""Jit'd dispatch layer: Pallas kernels on TPU, jnp oracles elsewhere.

``backend`` override: "auto" (default), "pallas" (forced, interpret-mode on
CPU — used by the allclose tests), "jnp" (oracle). On a TPU every op runs
the compiled kernel; nothing on that path falls back to the interpreter.

Every op takes an optional ``nvalid`` row count: callers that pad N to a
fixed power-of-two bucket (see `repro.detect.cache`) pass the true row count
so both backends mask the padding identically and one compiled executable
serves every window size in the bucket.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.gmm_score import gmm_best_pallas, gmm_score_pallas
from repro.kernels.gmm_stats import gmm_stats_pallas, gmm_update_pallas

# jit'd oracle wrappers: the CPU path runs these inside EM loops, where
# eager dispatch per jnp op would dominate the math
_stats_ref = jax.jit(ref.gmm_stats_ref)
_update_ref = jax.jit(ref.gmm_update_ref)


def kernel_mode(backend: str = "auto") -> str:
    """Where an op runs: "pallas" (compiled kernel, on a TPU), "interpret"
    (the kernel body on the Pallas interpreter — only for an explicit
    ``backend="pallas"`` off-TPU, the parity tests' seam) or "jnp" (oracle)."""
    on_tpu = jax.default_backend() == "tpu"
    if backend == "pallas":
        return "pallas" if on_tpu else "interpret"
    if backend == "auto" and on_tpu:
        return "pallas"
    return "jnp"


def gmm_score(X, means, prec_chol, *, backend: str = "auto", block_n: int = 1024):
    mode = kernel_mode(backend)
    if mode != "jnp":
        return gmm_score_pallas(X, means, prec_chol, block_n=block_n,
                                interpret=mode == "interpret")
    return ref.gmm_score_ref(X, means, prec_chol)


def gmm_best(X, means, prec_chol, *, backend: str = "auto", block_n: int = 1024):
    mode = kernel_mode(backend)
    if mode != "jnp":
        return gmm_best_pallas(X, means, prec_chol, block_n=block_n,
                               interpret=mode == "interpret")
    return ref.gmm_best_ref(X, means, prec_chol)


def gmm_stats(X, log_weights, means, prec_chol, *, nvalid=None,
              backend: str = "auto", block_n: int = 1024):
    """E-step sufficient statistics (nk, sx, sxx, ll_sum); rows at index
    >= ``nvalid`` are padding."""
    mode = kernel_mode(backend)
    if mode != "jnp":
        return gmm_stats_pallas(X, log_weights, means, prec_chol,
                                nvalid=nvalid, block_n=block_n,
                                interpret=mode == "interpret")
    if nvalid is None:
        return _stats_ref(X, log_weights, means, prec_chol)
    return _stats_ref(X, log_weights, means, prec_chol, nvalid)


def gmm_update(X, log_weights, means, prec_chol, *, nvalid=None,
               backend: str = "auto", block_n: int = 1024):
    """One fused EM iteration: (nk, means_new, cov_new, ll_sum) in a single
    pass over X — the caller only re-parameterises cov and renormalises
    weights. Rows at index >= ``nvalid`` are padding."""
    mode = kernel_mode(backend)
    if mode != "jnp":
        return gmm_update_pallas(X, log_weights, means, prec_chol,
                                 nvalid=nvalid, block_n=block_n,
                                 interpret=mode == "interpret")
    if nvalid is None:
        return _update_ref(X, log_weights, means, prec_chol)
    return _update_ref(X, log_weights, means, prec_chol, nvalid)
