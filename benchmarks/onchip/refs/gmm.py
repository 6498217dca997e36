"""Plain reference of the GMM detector's kernels: per-component Gaussian log
densities, the best component, and the E-step / EM-update statistics.

Copied from the program's ``kernels/ref.py`` (the oracles its Pallas kernels
are tested against) so that a later change there cannot move the yardstick.
``precision`` sets the arithmetic of the products: ``"highest"`` (float32)
is the reference; ``"high"`` is its control, three bfloat16 passes written
out (each operand split into a bfloat16 high part and a bfloat16 remainder,
the remainders' product dropped), so that it reads the same on any backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LOG2PI = float(np.log(2.0 * np.pi))
HIGHEST = jax.lax.Precision.HIGHEST


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def einsum(eq: str, a, b, precision: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    (ah, al), (bh, bl) = _split(a), _split(b)
    return sum(jnp.einsum(eq, x, y, precision=HIGHEST)
               for x, y in ((ah, bh), (ah, bl), (al, bh)))


def score(X, means, prec_chol, precision: str = "highest"):
    """(N, K) log N(x | mu_k, Sigma_k), with Sigma_k^-1 = U_k U_k^T."""
    X = X.astype(jnp.float32)
    U = prec_chol.astype(jnp.float32)
    xu = einsum("nd,kde->nke", X, U, precision)
    mu_u = einsum("kd,kde->ke", means, U, precision)
    z = xu - mu_u[None]
    quad = jnp.sum(z * z, axis=-1)
    logdet = jnp.sum(jnp.log(jnp.abs(
        jnp.diagonal(U, axis1=-2, axis2=-1))), axis=-1)
    return -0.5 * (X.shape[-1] * LOG2PI + quad) + logdet[None, :]


def best(X, means, prec_chol, precision: str = "highest"):
    lp = score(X, means, prec_chol, precision)
    return jnp.max(lp, axis=1), jnp.argmax(lp, axis=1).astype(jnp.int32)


def loglik(X, log_weights, means, prec_chol, precision: str = "highest"):
    """Mean over the rows of log sum_k w_k N(x | mu_k, Sigma_k)."""
    log_r = log_weights[None, :].astype(jnp.float32) + score(
        X, means, prec_chol, precision)
    m = jnp.max(log_r, axis=1, keepdims=True)
    return jnp.mean(m[:, 0] + jnp.log(jnp.sum(jnp.exp(log_r - m), axis=1)))


def stats(X, log_weights, means, prec_chol, nvalid=None,
          precision: str = "highest"):
    """(nk, sx, sxx, ll_sum); rows at index >= nvalid are padding."""
    X = X.astype(jnp.float32)
    log_p = score(X, means, prec_chol, precision)
    log_r = log_weights[None, :].astype(jnp.float32) + log_p
    m = jnp.max(log_r, axis=1, keepdims=True)
    norm = m + jnp.log(jnp.sum(jnp.exp(log_r - m), axis=1, keepdims=True))
    resp = jnp.exp(log_r - norm)
    if nvalid is not None:
        valid = (jnp.arange(X.shape[0]) < nvalid).astype(jnp.float32)
        resp = resp * valid[:, None]
        norm = norm * valid[:, None]
    nk = jnp.sum(resp, axis=0)
    sx = einsum("nk,nd->kd", resp, X, precision)
    sxx = einsum("nkd,ne->kde", resp[:, :, None] * X[:, None, :], X,
                 precision)
    return nk, sx, sxx, jnp.sum(norm)


def update(X, log_weights, means, prec_chol, nvalid=None,
           precision: str = "highest"):
    """One EM iteration: (nk, means_new, cov_new, ll_sum)."""
    nk, sx, sxx, ll = stats(X, log_weights, means, prec_chol, nvalid,
                            precision)
    denom = nk + 1e-10
    mu = sx / denom[:, None]
    cov = sxx / denom[:, None, None] - jnp.einsum("kd,ke->kde", mu, mu,
                                                  precision=HIGHEST)
    return nk, mu, cov, ll
