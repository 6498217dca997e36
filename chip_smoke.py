#!/usr/bin/env python
"""Smoke test of the monitored main path on a TPU, in one process.

    python chip_smoke.py               # one chip: kernel parity, train, serve
    python chip_smoke.py --four-chips  # data-parallel train on a 2x2 mesh
                                       # against the same batches on one chip

Phases (one chip):

* device   — platform, kind and count as JAX reports them; anything but a
             TPU exits non-zero before any work.
* kernels  — `ops.gmm_best/gmm_score/gmm_stats/gmm_update` (compiled Pallas
             kernels) against `kernels/ref.py` on the same seeded data at two
             detection-plane bucket shapes; fails above PARITY_RTOL.
* train    — GPT-2 at its published widths through `repro.launch.train`
             (seq 1024, batch 8, bf16) with the stream monitor, long enough
             for two detection sweeps; losses finite, sweeps admitted, and
             the detection plane's GMM calls ran the compiled kernels.
* serve    — GPT-2 through `repro.launch.serve` on the continuous engine,
             16 requests under a batch-mode SLO monitor; every request
             finishes with at least one token.

With ``--four-chips`` only the mesh phase runs: GPT-2 through
``train --data-mesh 4`` for a few steps, then the same seeded batches on one
chip. Its losses must agree within LOSS_RTOL, every state leaf must live on
all four devices, the batch must be split over the data axis, and the
collective probe must have read an all-reduce from the compiled program.

Weights are random (seeded). Any failed check raises: the exit code is then
non-zero and the result line is not printed. The last line of a passing run
is one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
No timing is reported here.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# f32 parity of the compiled kernels with the jnp oracle (both at f32 matmul
# precision): max |kernel - ref| over max(1, max |ref|), per output
PARITY_RTOL = 1e-4
# data-parallel vs one-chip losses in bf16 compute: |a - b| / max(1, |b|)
LOSS_RTOL = 1e-2
# (rows, D, K, nvalid, block_n): a small and the largest detection bucket
PARITY_SHAPES = [(1024, 3, 3, 700, 1024), (65536, 4, 5, 50000, 4096)]
MODEL_ARGS = ["--arch", "gpt2"]  # published widths (configs/gpt2.py)
SEQ, BATCH = 1024, 8


def _train(*extra: str):
    from repro.launch import train

    return train.run(MODEL_ARGS + ["--seq", str(SEQ), "--batch", str(BATCH),
                                   *extra])


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")


def phase(name: str) -> None:
    print(f"[smoke] === {name} ===", flush=True)


def device_phase():
    import jax

    phase("device")
    devs = jax.devices()
    dev = devs[0]
    print(f"[smoke] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devs)}", flush=True)
    require(dev.platform == "tpu",
            f"no TPU: JAX's first device is on {dev.platform!r}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def _gmm_data(rows, D, K, seed):
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(k1, (rows, D), jnp.float32)
    means = jax.random.normal(k2, (K, D), jnp.float32)
    A = 0.3 * jax.random.normal(k3, (K, D, D), jnp.float32)
    cov = jnp.einsum("kde,kfe->kdf", A, A) + 0.5 * jnp.eye(D)
    L = jnp.linalg.cholesky(cov)
    U = jnp.swapaxes(jax.scipy.linalg.solve_triangular(
        L, jnp.broadcast_to(jnp.eye(D), (K, D, D)), lower=True), -1, -2)
    logw = jnp.full((K,), -math.log(K), jnp.float32)
    return X, logw, means, U


def _rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


def kernel_phase() -> None:
    import jax
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.gmm_stats import gmm_update_pallas

    phase("kernels")
    mode = ops.kernel_mode()
    print(f"[smoke] ops dispatch: {mode}", flush=True)
    require(mode == "pallas", f"GMM ops dispatch to {mode!r}, not compiled "
                              "Pallas kernels")
    for i, (rows, D, K, nvalid, bn) in enumerate(PARITY_SHAPES):
        X, logw, means, U = _gmm_data(rows, D, K, seed=i)
        hlo = gmm_update_pallas.lower(X, logw, means, U, nvalid=nvalid,
                                      block_n=bn).compile().as_text()
        require("tpu_custom_call" in hlo,
                "gmm_update compiled without a tpu_custom_call")
        got = {
            "score": ops.gmm_score(X, means, U, block_n=bn),
            "best": ops.gmm_best(X, means, U, block_n=bn),
            "stats": ops.gmm_stats(X, logw, means, U, nvalid=nvalid,
                                   block_n=bn),
            "update": ops.gmm_update(X, logw, means, U, nvalid=nvalid,
                                     block_n=bn),
        }
        with jax.default_matmul_precision("float32"):
            want = {
                "score": ref.gmm_score_ref(X, means, U),
                "best": ref.gmm_best_ref(X, means, U),
                "stats": ref.gmm_stats_ref(X, logw, means, U, nvalid),
                "update": ref.gmm_update_ref(X, logw, means, U, nvalid),
            }
        errs = {
            "score": _rel_err(got["score"], want["score"]),
            "best": _rel_err(got["best"][0], want["best"][0]),
        }
        for op in ("stats", "update"):
            errs[op] = max(_rel_err(g, w) for g, w in zip(got[op], want[op]))
        # argmax may differ only where the top two components tie
        lp = np.asarray(want["score"])
        mism = np.asarray(got["best"][1]) != np.asarray(want["best"][1])
        top2 = np.sort(lp[mism], axis=1)[:, -2:]
        ties_ok = bool(np.allclose(top2[:, 0], top2[:, 1], atol=1e-3))
        print(f"[smoke] parity N={rows} D={D} K={K} nvalid={nvalid} "
              f"block_n={bn}: max rel err "
              + " ".join(f"{k}={v:.3e}" for k, v in errs.items())
              + f" argmax_mismatch={int(mism.sum())} (tol {PARITY_RTOL:g})",
              flush=True)
        for op, err in errs.items():
            require(err <= PARITY_RTOL,
                    f"{op} parity {err:.3e} > {PARITY_RTOL:g} at N={rows}")
        require(ties_ok, "argmax disagrees away from a tie")


def train_phase() -> None:
    from repro.detect.cache import SHAPE_CACHE
    from repro.kernels import ops

    phase("train")
    spec = {"mode": "stream"}
    calls_before = sum(SHAPE_CACHE.stats()[k] for k in ("hits", "misses"))
    run = _train("--steps", "80", "--log-every", "10",
                 "--monitor-spec", json.dumps(spec))
    calls = sum(SHAPE_CACHE.stats()[k] for k in ("hits", "misses")) \
        - calls_before
    admitted = run.report.overhead["detect_plane"]["sweeps_admitted"]
    print(f"[smoke] train: {len(run.losses)} steps, loss "
          f"{run.losses[0]:.4f} -> {run.losses[-1]:.4f}, stream sweeps "
          f"admitted={admitted}, detection-plane kernel calls={calls} "
          f"({ops.kernel_mode()})", flush=True)
    require(run.exit_code == 0, f"train exited {run.exit_code}")
    require(len(run.losses) == 80, f"{len(run.losses)} of 80 steps ran")
    require(all(math.isfinite(x) for x in run.losses), "non-finite loss")
    require(admitted >= 1, "no stream detection sweep was admitted")
    require(calls > 0, "the detection plane made no GMM kernel call")
    print("[smoke] note: the device probe's util/power/temp columns are "
          "modelled by TpuTelemetryModel, not read from the chip", flush=True)


def serve_phase() -> None:
    from repro.launch import serve

    phase("serve")
    spec = {"mode": "batch", "slo": {"ttft_s": 0.5, "queue_wait_s": 0.25}}
    run = serve.run(MODEL_ARGS + ["--num-requests", "16",
                                  "--monitor-spec", json.dumps(spec)])
    tokens = [r.tokens_out for r in run.finished]
    print(f"[smoke] serve: {len(run.finished)}/{run.requested} requests "
          f"finished, tokens per request min={min(tokens, default=0)} "
          f"total={sum(tokens)}", flush=True)
    require(run.exit_code == 0, f"serve exited {run.exit_code}")
    require(len(run.finished) == run.requested,
            f"{len(run.finished)} of {run.requested} requests finished")
    require(min(tokens) >= 1, "a request finished without a token")


def four_chip_phase() -> None:
    import jax

    phase("four chips: data mesh vs one chip")
    require(len(jax.devices()) == 4,
            f"--four-chips needs 4 devices, found {len(jax.devices())}")
    steps = ("--steps", "4", "--log-every", "1")
    mesh = _train(*steps, "--data-mesh", "4",
                  "--monitor-spec", json.dumps({"mode": "batch"}))
    one = _train(*steps)
    diffs = [abs(a - b) / max(1.0, abs(b))
             for a, b in zip(mesh.losses, one.losses)]
    print(f"[smoke] losses mesh={mesh.losses} one_chip={one.losses} "
          f"max rel diff={max(diffs):.3e} (tol {LOSS_RTOL:g})", flush=True)
    placed = {len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves(mesh.state)}
    batch_shards = {s.shard_shape((BATCH, SEQ)) for s in
                    jax.tree.leaves(mesh.compiled.input_shardings[0][1])}
    print(f"[smoke] state leaves on {sorted(placed)} device(s); batch shard "
          f"shapes {sorted(batch_shards)}; collectives "
          f"{ {op: mesh.collectives.count(op) for op in set(mesh.collectives)} }",
          flush=True)
    require(mesh.exit_code == 0 and one.exit_code == 0, "a train run failed")
    require(len(diffs) == 4 and max(diffs) <= LOSS_RTOL,
            "mesh and one-chip losses disagree")
    require(placed == {4}, "a state leaf is not on all 4 devices")
    require(batch_shards == {(BATCH // 4, SEQ)},
            "batch not split over 'data'")
    require("all-reduce" in mesh.collectives,
            "the collective probe registered no all-reduce")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip data-mesh phase")
    args = ap.parse_args()

    device = device_phase()
    import jax

    from repro.detect.cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def on_event(name, **kw):
        key = name.rsplit("/", 1)[-1]
        if key in cache_events:
            cache_events[key] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"[smoke] compile cache: {cache_dir}", flush=True)

    if args.four_chips:
        four_chip_phase()
    else:
        kernel_phase()
        train_phase()
        serve_phase()
    print(f"[smoke] compile cache events: {cache_events}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
