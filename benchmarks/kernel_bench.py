"""GMM kernel benchmark (beyond paper): jnp-oracle CPU timings + the TPU
roofline model for the Pallas kernels (this container is CPU-only, so TPU
numbers are analytic: bytes/flops vs 197 TFLOP/s / 819 GB/s).

The fused single-pass design matters: scoring N events against K components
moves N*D input bytes once; the unfused jnp pipeline moves the (N, K)
intermediate 3x (densities -> max -> argmax) plus X twice.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_result
from repro.kernels import ref
from repro.roofline import PEAKS, V5E


def roofline_time(nbytes: float, flops: float) -> float:
    hw = PEAKS[V5E]
    return max(nbytes / hw["hbm_bw"], flops / hw["peak_flops"])


def run():
    rows = []
    for (N, D, K) in [(100_000, 4, 4), (1_000_000, 4, 4), (1_000_000, 8, 8),
                      (4_000_000, 8, 8)]:
        key = jax.random.PRNGKey(0)
        X = jax.random.normal(key, (N, D), dtype=jnp.float32)
        means = jax.random.normal(key, (K, D))
        U = jnp.broadcast_to(jnp.eye(D), (K, D, D))

        score = jax.jit(ref.gmm_score_ref)
        best = jax.jit(ref.gmm_best_ref)
        _ = jax.block_until_ready(score(X, means, U))
        t0 = time.perf_counter()
        _ = jax.block_until_ready(score(X, means, U))
        t_score = time.perf_counter() - t0
        _ = jax.block_until_ready(best(X, means, U))
        t0 = time.perf_counter()
        _ = jax.block_until_ready(best(X, means, U))
        t_best = time.perf_counter() - t0

        flops = 2.0 * N * K * D * (D + 1)  # (x@U per comp) + quad reduce
        in_bytes = 4.0 * N * D
        fused_bytes = in_bytes + 8.0 * N  # read X once, write (best, argmax)
        unfused_bytes = in_bytes * 2 + 4.0 * N * K * 3
        tpu_fused = roofline_time(fused_bytes, flops)
        tpu_unfused = roofline_time(unfused_bytes, flops)
        rows.append({
            "N": N, "D": D, "K": K,
            "cpu_jnp_score_s": t_score, "cpu_jnp_best_s": t_best,
            "tpu_roofline_fused_s": tpu_fused,
            "tpu_roofline_unfused_s": tpu_unfused,
            "fused_speedup_model": tpu_unfused / tpu_fused,
            "events_per_s_tpu_model": N / tpu_fused,
        })
    print("\nKernel bench — GMM scoring (Definition-1 hot path)")
    print(f"{'N':>9s} {'D':>3s} {'K':>3s} {'cpu_jnp(s)':>11s} "
          f"{'tpu_fused(s)':>13s} {'tpu_unfused(s)':>14s} {'model_speedup':>13s}")
    for r in rows:
        print(f"{r['N']:9d} {r['D']:3d} {r['K']:3d} "
              f"{r['cpu_jnp_best_s']:11.4f} {r['tpu_roofline_fused_s']:13.6f} "
              f"{r['tpu_roofline_unfused_s']:14.6f} "
              f"{r['fused_speedup_model']:13.2f}x")
    save_result("kernel_bench", {"rows": rows})
    return rows


if __name__ == "__main__":
    run()
