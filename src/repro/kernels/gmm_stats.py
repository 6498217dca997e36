"""Pallas TPU kernels: fused GMM E-step sufficient statistics + fused E+M
update.

Streaming EM: one pass over X computes (N_k, sum_k gamma x, sum_k gamma xx^T,
sum log-likelihood) with VMEM-resident accumulators, never materialising the
(N, K) responsibility matrix in HBM. This converts the EM E+M data movement
from 4 HBM passes (logp, resp, resp@X, cov einsum) to exactly one read of X —
the TPU-native restructuring of the paper's sklearn EM (DESIGN.md §5).

`gmm_update_pallas` goes one step further and fuses the M-step itself into
the final grid block: the same single pass over X returns the *updated*
means and covariances (plus nk and the data log-likelihood), so one EM
iteration is exactly one kernel launch + a tiny (K, D, D) host-side Cholesky.

Both kernels take an ``nvalid`` row count (a (1, 1) int32 in SMEM) so
callers can pad N to a fixed power-of-two bucket (see `repro.detect.cache`)
and reuse one compiled executable across the sliding-window sizes a
streaming detector sees. As in `gmm_score`, every value stays 2-D with D or
K on the lanes and the loops over K are static: Mosaic refuses reshapes
that split or merge the lane dimension.

The grid dimension over N-blocks is sequential on TPU, so the accumulator
pattern (init at program_id==0, += afterwards, finalise at the last block)
is race-free by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gmm_score import (HIGHEST, component_terms, for_each_chunk,
                                     log_densities)


def _t_dot(a, b):
    """a^T @ b for (bn, P) x (bn, Q) -> (P, Q), f32 on the MXU."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _accumulate_estep(i, x_ref, logw_ref, mu_u_ref, u_ref, logdet_ref,
                      nvalid_ref, nk_ref, sx_ref, sxx_ref, ll_ref):
    """Shared E-step body: accumulate (nk, sx, sxx, ll) for one N-block.

    nk is kept as a (K, 1) column and ll as (1, 1) so the M-step can
    broadcast them against (K, D) rows without a lane/sublane relayout."""
    bn = x_ref.shape[0]
    K = u_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        nk_ref[...] = jnp.zeros_like(nk_ref)
        sx_ref[...] = jnp.zeros_like(sx_ref)
        sxx_ref[...] = jnp.zeros_like(sxx_ref)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    def chunk(r, rows):
        x = x_ref[pl.ds(r, rows), :].astype(jnp.float32)  # (rows, D)
        logr = (log_densities(x, mu_u_ref, u_ref, logdet_ref)
                + logw_ref[...])  # (rows, K)
        m = jnp.max(logr, axis=-1, keepdims=True)
        norm = m + jnp.log(jnp.sum(jnp.exp(logr - m), axis=-1,
                                   keepdims=True))
        resp = jnp.exp(logr - norm)  # (rows, K)

        # mask padding rows (global row id >= nvalid)
        row = i * bn + r + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        valid = (row < nvalid_ref[0, 0]).astype(jnp.float32)
        resp = resp * valid
        norm = norm * valid

        nk_ref[...] += _t_dot(resp, jnp.ones((rows, 1), jnp.float32))
        sx_ref[...] += _t_dot(resp, x)  # (K, D)
        for k in range(K):
            sxx_ref[k] += _t_dot(resp[:, k:k + 1] * x, x)  # (D, D)
        ll_ref[...] += jnp.sum(norm, axis=0, keepdims=True)

    for_each_chunk(bn, chunk)


def _stats_kernel(x_ref, logw_ref, mu_u_ref, u_ref, logdet_ref, nvalid_ref,
                  nk_ref, sx_ref, sxx_ref, ll_ref):
    i = pl.program_id(0)
    _accumulate_estep(i, x_ref, logw_ref, mu_u_ref, u_ref, logdet_ref,
                      nvalid_ref, nk_ref, sx_ref, sxx_ref, ll_ref)


def _update_kernel(x_ref, logw_ref, mu_u_ref, u_ref, logdet_ref, nvalid_ref,
                   nk_ref, mean_ref, cov_ref, ll_ref):
    """Fused E+M: accumulate stats, then finalise the M-step in the last
    grid block (mean_ref carries sx until then, cov_ref carries sxx)."""
    i = pl.program_id(0)
    _accumulate_estep(i, x_ref, logw_ref, mu_u_ref, u_ref, logdet_ref,
                      nvalid_ref, nk_ref, mean_ref, cov_ref, ll_ref)

    @pl.when(i == pl.num_programs(0) - 1)
    def _m_step():
        # 1/nk materialised over (K, D): row k then broadcasts down the
        # sublanes of (D, D). A broadcast of the (K, 1) column itself would
        # leave a lane-replicated layout that Mosaic cannot broadcast again
        inv = (1.0 / (nk_ref[...] + 1e-10)) * jnp.ones(mean_ref.shape,
                                                        jnp.float32)
        mu = mean_ref[...] * inv  # (K, D)
        mean_ref[...] = mu
        for k in range(mu.shape[0]):
            mu_k = mu[k:k + 1]  # (1, D)
            cov_ref[k] = cov_ref[k] * inv[k:k + 1] - _t_dot(mu_k, mu_k)


def _prepare(X, means, prec_chol, nvalid, block_n):
    """Shared launch prep: pad X to whole blocks, precompute mu_u/logdet."""
    N = X.shape[0]
    n_blocks = max(1, pl.cdiv(N, block_n))
    pad = n_blocks * block_n - N
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
    mu_u, logdet = component_terms(means, prec_chol)
    if nvalid is None:
        nvalid = N
    nvalid = jnp.asarray(nvalid, jnp.int32).reshape(1, 1)
    return X, mu_u, logdet, nvalid, n_blocks


def _launch(kernel, name, X, log_weights, mu_u, prec_chol, logdet, nvalid,
            n_blocks, block_n, interpret):
    K, D = mu_u.shape
    full = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))
    nk, sx, sxx, ll = pl.pallas_call(
        kernel,
        name=name,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            full(1, K), full(K, D), full(K, D, D), full(1, K),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[full(K, 1), full(K, D), full(K, D, D), full(1, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((K, 1), jnp.float32),
            jax.ShapeDtypeStruct((K, D), jnp.float32),
            jax.ShapeDtypeStruct((K, D, D), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(X, log_weights.astype(jnp.float32).reshape(1, K), mu_u, prec_chol,
      logdet, nvalid)
    return nk[:, 0], sx, sxx, ll[0, 0]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gmm_stats_pallas(X, log_weights, means, prec_chol, *, nvalid=None,
                     block_n: int = 1024, interpret: bool = False):
    """One-pass E-step stats: (nk (K,), sx (K,D), sxx (K,D,D), ll ()).

    ``nvalid`` (int, <= N) marks rows past it as padding — pass bucketed,
    zero-padded X with the true row count to reuse one compiled shape."""
    X, mu_u, logdet, nvalid, n_blocks = _prepare(X, means, prec_chol,
                                                 nvalid, block_n)
    return _launch(_stats_kernel, "gmm_stats", X, log_weights, mu_u,
                   prec_chol, logdet, nvalid, n_blocks, block_n, interpret)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gmm_update_pallas(X, log_weights, means, prec_chol, *, nvalid=None,
                      block_n: int = 1024, interpret: bool = False):
    """Fused EM iteration: one pass over X returns the M-step outputs
    (nk (K,), means_new (K,D), cov_new (K,D,D), ll ()). The caller only
    re-parameterises cov_new (Cholesky) and renormalises weights —
    O(K D^2) host work against one kernel launch."""
    X, mu_u, logdet, nvalid, n_blocks = _prepare(X, means, prec_chol,
                                                 nvalid, block_n)
    return _launch(_update_kernel, "gmm_update", X, log_weights, mu_u,
                   prec_chol, logdet, nvalid, n_blocks, block_n, interpret)
