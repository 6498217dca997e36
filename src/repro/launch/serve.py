"""Serving driver: a continuous-batching request plane under generated load,
with optional eACGM monitoring and per-request SLO accounting.

    PYTHONPATH=src python -m repro.launch.serve --arch gpt2 --reduced \
        --qps 20 --num-requests 64 \
        --monitor-spec '{"mode": "batch", "slo": {"ttft_s": 0.5}}'

The driver runs the slot-based `ContinuousBatchingEngine`: requests arrive
from a deterministic multi-tenant `LoadGenerator` (``--qps``, ``--tenants``,
``--arrival-seed``), join mid-flight as slots free up, and publish their
lifecycle records to the monitor's request probe. With a ``slo`` block on
the monitor spec, breaches close as SLO incidents and are diagnosed on the
request plane (docs/serving.md). Ctrl-C flushes: the session finalises and
the report/board/metrics stay valid for whatever was served.

``--static-batch`` keeps the legacy fixed-batch `ServeEngine` path (one
``generate`` call, no request accounting) for A/B comparison — the same
pair `benchmarks/serve_bench.py` measures.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import get_arch, reduced
from repro.detect.cache import enable_persistent_cache
from repro.models.model import Runtime, init_params
from repro.serve import (ContinuousBatchingEngine, LoadGenerator, Request,
                         RequestQueue, ServeEngine)
from repro.session import MonitorReport, MonitorSpec, Session, SinkSpec

# historical tuning of the serve driver (legacy-flag path only)
LEGACY_SPEC_DEFAULTS = {
    "probe_options": {"python": {"sample_every": 25},
                      "device": {"interval": 0.05}},
    "detector": {"min_events": 48},
}


def _parse_range(arg: str, name: str) -> tuple:
    parts = [int(p) for p in arg.split(",") if p]
    if len(parts) == 1:
        return (parts[0], parts[0])
    if len(parts) != 2 or parts[0] > parts[1]:
        raise SystemExit(f"--{name} wants 'N' or 'LO,HI', got {arg!r}")
    return (parts[0], parts[1])


@dataclasses.dataclass
class ServeRun:
    """What one `run` produced: the exit code `main` returns, plus the
    served requests a caller may check (the smoke test on the chip does)."""

    exit_code: int
    requested: int  # --num-requests (0 = a --steps horizon)
    finished: List[Request]  # continuous engine only
    report: Optional[MonitorReport]  # None when monitoring is off


def main(argv=None) -> int:
    return run(argv).exit_code


def run(argv=None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-len", type=int, default=512,
                    help="KV-cache length (one shared decode index)")
    # request-plane load (continuous engine, the default path)
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent request slots (continuous engine)")
    ap.add_argument("--qps", type=float, default=20.0,
                    help="offered load, requests per second of engine time")
    ap.add_argument("--num-requests", type=int, default=64,
                    help="stop after this many requests have been generated "
                         "and served (0 = run --steps engine steps)")
    ap.add_argument("--steps", type=int, default=0,
                    help="engine-step horizon when --num-requests is 0")
    ap.add_argument("--arrival-seed", type=int, default=-1,
                    help="load-generator seed (default: --seed); arrivals "
                         "are a pure function of (seed, step)")
    ap.add_argument("--tenants", default="0.5,0.3,0.2",
                    help="comma-separated tenant arrival weights")
    ap.add_argument("--prompt-len", default="4,24",
                    help="prompt-length range 'LO,HI' (or a single int; "
                         "also the legacy --static-batch prompt length)")
    ap.add_argument("--max-new", default="4,16",
                    help="generation-budget range 'LO,HI' per request")
    # legacy fixed-batch path
    ap.add_argument("--static-batch", action="store_true",
                    help="run the legacy fixed-batch ServeEngine instead")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    MonitorSpec.add_cli_args(ap)
    ap.add_argument("--monitor", action="store_true",
                    help="[deprecated] = --monitor-spec '{\"mode\":\"batch\"}'")
    ap.add_argument("--stream-monitor", action="store_true",
                    help="[deprecated] = --monitor-spec "
                         "'{\"mode\":\"stream\"}'")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve monitor self-metrics on this port "
                         "(= a \"prometheus\" sink; 0 = ephemeral)")
    ap.add_argument("--board-out", default="",
                    help="write a live HTML status board here "
                         "(= a \"board\" sink)")
    args = ap.parse_args(argv)
    enable_persistent_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if not cfg.has_decode:
        print(f"{cfg.name} is encoder-only: no decode step")
        return ServeRun(0, args.num_requests, [], None)
    rt = Runtime(mesh=None, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)

    spec = MonitorSpec.from_args(args, legacy_defaults=LEGACY_SPEC_DEFAULTS)
    if spec.mode != "off":
        if not args.static_batch and "request" not in spec.probes:
            spec.probes = list(spec.probes) + ["request"]
        if args.metrics_port >= 0:
            spec.sinks.append(SinkSpec(
                kind="prometheus",
                options={"serve": True, "port": args.metrics_port}))
        if args.board_out:
            spec.sinks.append(SinkSpec(kind="board", path=args.board_out))
    session = Session(spec)
    if not session.off and args.metrics_port >= 0:
        print(f"[monitor] metrics endpoint: "
              f"{session.sink('prometheus').url}/metrics")

    finished: List[Request] = []
    if args.static_batch:
        rc = _run_static(args, cfg, rt, params, session, spec)
    else:
        rc, finished = _run_continuous(args, cfg, rt, params, session)
    report = None
    if not session.off:
        report = session.result()
        print(report.render())
    return ServeRun(rc, args.num_requests, finished, report)


def _run_continuous(args, cfg, rt, params, session):
    engine = ContinuousBatchingEngine(
        cfg, rt, params, slots=args.slots, max_len=args.max_len,
        temperature=args.temperature, seed=args.seed)
    # warm traffic outside the monitor: the first run compiles the slot
    # step, the second measures the steady per-step wall time that converts
    # --qps into a per-step arrival rate
    warm = LoadGenerator(rate=10.0, num_requests=args.slots,
                         seed=args.seed, prompt_len=(2, 2), max_new=(4, 4),
                         vocab_size=cfg.vocab_size)
    engine.run(warm, drain=True)
    timed = LoadGenerator(rate=float(args.slots),
                          num_requests=2 * args.slots, seed=args.seed + 1,
                          prompt_len=(2, 2), max_new=(16, 16),
                          vocab_size=cfg.vocab_size)
    base = engine.decode_steps
    t0 = time.perf_counter()
    engine.run(timed, drain=True)
    steps = max(engine.decode_steps - base, 1)
    step_s = max((time.perf_counter() - t0) / steps, 1e-6)
    engine.reset()

    weights = tuple(float(w) for w in args.tenants.split(",") if w)
    load = LoadGenerator(
        rate=args.qps * step_s,
        num_requests=args.num_requests or None,
        seed=args.arrival_seed if args.arrival_seed >= 0 else args.seed,
        tenants=weights,
        prompt_len=_parse_range(args.prompt_len, "prompt-len"),
        max_new=_parse_range(args.max_new, "max-new"),
        vocab_size=cfg.vocab_size)
    if args.num_requests > 0:
        n_steps = None  # run() stops once the load drains
    elif args.steps > 0:
        n_steps = args.steps
    else:
        raise SystemExit("--num-requests 0 needs a --steps horizon")
    print(f"[serve] {args.slots} slots, ~{1 / step_s:.0f} steps/s -> "
          f"rate {load.rate:.3f} req/step for --qps {args.qps:g}")

    queue = RequestQueue()
    t0 = time.perf_counter()
    with session.monitoring():
        # Ctrl-C inside the monitoring context: the session still finalises
        # (the SLO monitor flushes pending breaches) and closes its sinks
        try:
            engine.run(load, n_steps=n_steps, queue=queue,
                       on_step=None if session.off else session.on_step)
        except KeyboardInterrupt:
            print("\n[serve] interrupted; flushing monitor artifacts")
    wall = time.perf_counter() - t0

    fin = engine.finished
    if fin:
        waits = np.array([r.queue_wait for r in fin])
        ttfts = np.array([r.ttft for r in fin])
        tpots = np.array([r.tpot for r in fin if r.tokens_out > 1])
        tokens = sum(r.tokens_out for r in fin)
        print(f"[serve] {len(fin)} requests, {tokens} tokens in "
              f"{wall:.2f}s ({tokens / wall:.1f} tok/s, "
              f"{len(fin) / wall:.1f} req/s)")
        print(f"[serve] wait p50/p95: {np.median(waits):.3f}/"
              f"{np.quantile(waits, 0.95):.3f}s  ttft p50/p95: "
              f"{np.median(ttfts):.3f}/{np.quantile(ttfts, 0.95):.3f}s  "
              f"tpot p50: "
              f"{np.median(tpots) if len(tpots) else 0.0:.4f}s")
        by_tenant: dict = {}
        for r in fin:
            by_tenant[r.tenant] = by_tenant.get(r.tenant, 0) + 1
        print(f"[serve] per tenant: "
              f"{ {t: n for t, n in sorted(by_tenant.items())} } "
              f"(queue: {len(queue)} waiting, {queue.rejected} rejected)")
    else:
        print("[serve] no requests finished")
    if not session.off:
        stats = session.serve_stats()
        if stats:
            print("[monitor] serve:", {k: round(v, 4)
                                       for k, v in sorted(stats.items())})
    return 0, fin


def _run_static(args, cfg, rt, params, session, spec) -> int:
    engine = ServeEngine(cfg=cfg, rt=rt, params=params,
                         batch_size=args.batch, max_len=args.max_len,
                         temperature=args.temperature, seed=args.seed)
    plen = _parse_range(args.prompt_len, "prompt-len")[0]
    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, plen)).astype(np.int32)
    out, dt = None, 0.0
    with session.monitoring():
        try:
            engine._step = session.observe_step_fn(engine._step)
            if spec.mode == "stream":
                # calibration traffic: a short clean generate fits the
                # per-layer baselines (decode steps are homogeneous)
                engine.generate(prompts, 24)
                fitted = session.warmup()
                print(f"[monitor] warmed layers: "
                      f"{[l.value for l in fitted]}")
            t0 = time.time()
            out = engine.generate(prompts, args.tokens)
            dt = time.time() - t0
        except KeyboardInterrupt:
            print("\n[monitor] interrupted; flushing monitor artifacts")
    if out is not None:
        total_tokens = args.batch * (args.tokens + plen - 1)
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({total_tokens / dt:.1f} tok/s decode)")
        print("sample:", out[0, : plen + 8].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
