"""Pallas TPU kernel: fused GMM log-density / Definition-1 scoring.

The anomaly-detection hot path: for every event feature vector x (N rows,
N ~ millions/hour in production) compute log N(x | mu_k, Sigma_k) for all K
components — and, in the fused variant, the best-component log density and
arg-max the detector thresholds (paper Algorithm 2) — in ONE pass over X.

TPU mapping: N is tiled into VMEM-resident blocks (block_n x D); the K
(mu, U) parameter tensors are tiny (K, D <= 128) and stay in VMEM across the
whole grid. Each component's (block_n, D) @ (D, D) contraction runs on the
MXU; the reduction over D and max over K run on the VPU. The loop over K is
static and every value stays 2-D with D or K on the lanes: Mosaic cannot
split the lane dimension, so a single (D, K*D) dot reshaped to (bn, K, D) is
not an option. HBM traffic is exactly N*D reads + N*K (or 2N) writes — the
kernel is memory-roofline-bound, which is why fusing the three stages
(density, max, argmax) matters: the unfused jnp version reads/writes the
(N, K) intermediate three times.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LOG2PI = float(np.log(2.0 * np.pi))
# f32 MXU passes: the detector thresholds log densities, so the kernel must
# agree with the f32 oracle, not with a single bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST
CHUNK_ROWS = 512


def log_densities(x, mu_u_ref, u_ref, logdet_ref):
    """(bn, K) log N(x | mu_k, Sigma_k) for one (bn, D) f32 block.

    mu_u: (K, D) = mu_k @ U_k; u: (K, D, D); logdet: (1, K)."""
    mu_u = mu_u_ref[...]
    K, D = mu_u.shape
    quad = []
    for k in range(K):
        z = jnp.dot(x, u_ref[k].astype(jnp.float32), precision=HIGHEST,
                    preferred_element_type=jnp.float32) - mu_u[k:k + 1]
        quad.append(jnp.sum(z * z, axis=-1, keepdims=True))  # (bn, 1)
    return (-0.5 * (D * LOG2PI + jnp.concatenate(quad, axis=-1))
            + logdet_ref[...])


def for_each_chunk(block_rows: int, body) -> None:
    """Run ``body(r, rows)`` over a block in row chunks starting at ``r``.

    The per-chunk values are (rows, D) or (rows, K) with D, K on the lanes,
    each padded to 128 lanes in VMEM: chunking bounds them, so a 4096-row
    block stays inside the default scoped VMEM. A block that is not a whole
    number of chunks is one chunk."""
    rows = CHUNK_ROWS if block_rows % CHUNK_ROWS == 0 else block_rows

    def step(c, carry):
        body(pl.multiple_of(c * rows, rows), rows)
        return carry

    jax.lax.fori_loop(0, block_rows // rows, step, 0)


def _score_kernel(x_ref, mu_u_ref, u_ref, logdet_ref, out_ref):
    """x: (bn, D); mu_u: (K, D); u: (K, D, D); logdet: (1, K); out: (bn, K)."""
    def chunk(r, rows):
        x = x_ref[pl.ds(r, rows), :].astype(jnp.float32)
        out_ref[pl.ds(r, rows), :] = log_densities(x, mu_u_ref, u_ref,
                                                   logdet_ref)

    for_each_chunk(x_ref.shape[0], chunk)


def _best_kernel(x_ref, mu_u_ref, u_ref, logdet_ref, best_ref, arg_ref):
    """best, arg: (1, bn) lane-dense rows of this block's outputs."""
    def chunk(r, rows):
        x = x_ref[pl.ds(r, rows), :].astype(jnp.float32)
        logp = log_densities(x, mu_u_ref, u_ref, logdet_ref)  # (rows, K)
        best_ref[:, pl.ds(r, rows)] = jnp.max(logp, axis=-1)[None, :]
        arg_ref[:, pl.ds(r, rows)] = jnp.argmax(
            logp, axis=-1).astype(jnp.int32)[None, :]

    for_each_chunk(x_ref.shape[0], chunk)


def component_terms(means, prec_chol):
    """(mu_u (K, D), logdet (1, K)) in f32 — the per-component constants
    every GMM kernel keeps in VMEM."""
    prec_chol = prec_chol.astype(jnp.float32)
    mu_u = jnp.einsum("kd,kde->ke", means.astype(jnp.float32), prec_chol,
                      precision=HIGHEST)
    logdet = jnp.sum(jnp.log(jnp.abs(
        jnp.diagonal(prec_chol, axis1=-2, axis2=-1))), axis=-1)
    return mu_u, logdet[None, :]


def _common(X, means, prec_chol, block_n):
    N, D = X.shape
    K = means.shape[0]
    n_blocks = pl.cdiv(N, block_n)
    pad = n_blocks * block_n - N
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
    mu_u, logdet = component_terms(means, prec_chol)
    full = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    in_specs = [
        pl.BlockSpec((block_n, D), lambda i: (i, 0)),
        full(K, D),
        full(K, D, D),
        full(1, K),
    ]
    return X, mu_u, logdet, n_blocks, in_specs, N, D, K, pad


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gmm_score_pallas(X, means, prec_chol, *, block_n: int = 1024,
                     interpret: bool = False):
    """(N, D) x (K, D) x (K, D, D) -> (N, K) log densities."""
    X, mu_u, logdet, n_blocks, in_specs, N, D, K, pad = _common(
        X, means, prec_chol, block_n)
    out = pl.pallas_call(
        _score_kernel,
        name="gmm_score",
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_n, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N + pad, K), jnp.float32),
        interpret=interpret,
    )(X, mu_u, prec_chol, logdet)
    return out[:N]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gmm_best_pallas(X, means, prec_chol, *, block_n: int = 1024,
                    interpret: bool = False):
    """Fused Definition-1 scoring: (best log density (N,), argmax (N,) int32)."""
    X, mu_u, logdet, n_blocks, in_specs, N, D, K, pad = _common(
        X, means, prec_chol, block_n)
    best, arg = pl.pallas_call(
        _best_kernel,
        name="gmm_best",
        grid=(n_blocks,),
        in_specs=in_specs,
        # (n_blocks, 1, block_n): each block writes one lane-dense row. A 1-D
        # (N,) output is tiled by up to 1024 rows in HBM, which a smaller
        # block_n cannot match
        out_specs=[pl.BlockSpec((None, 1, block_n), lambda i: (i, 0, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((n_blocks, 1, block_n), jnp.float32),
                   jax.ShapeDtypeStruct((n_blocks, 1, block_n), jnp.int32)],
        interpret=interpret,
    )(X, mu_u, prec_chol, logdet)
    return best.reshape(-1)[:N], arg.reshape(-1)[:N]
