"""The monitored training job, driven by the benchmark's own loop.

The loop stands for user code: it calls only the program's public entries
(``make_train_step``, ``TrainState``, ``make_optimizer_for``, ``Session`` with
``observe_step_fn`` and ``on_step``, ``FaultInjector``) and, like
``launch/train.py``, per step runs the step, reads the loss as a float and
calls ``session.on_step``.

Set-up builds one compiled step with its state from the seed, drives it
through its first three steps (the ones the reference checks) with the
window's own call and feed, warms the monitor (stream warmup fit, admitted
sweeps, the GMM shapes the window reaches), and hands that same state to the
window. In the window, fault bursts start at the first step after seeded due
times; ``detect_p50_s`` (printed with the run's counts) is the median time
from a burst's first step to the ``on_step`` outcome that reports an
incident covering it.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import statistics
import time
from typing import Any, Dict, List, Optional

import numpy as np

from jobs import (GmmRecorder, compile_counter, gmm_gap, leaf_gaps,
                  leaf_norms, model_config, step_memory, warm_gmm,
                  worst_leaf_gap)
from harness import Cell, Check, RunResult, memory_peak_bytes
from tracing import WINDOW_SPAN, Spans
from weights import base_key, make_params, numpy_rng

SPAN_NAMES = ("job_step", "on_step", "inject")
CHECK_STEPS = 3
_REF_GRADS: Dict[Any, Any] = {}


@dataclasses.dataclass
class Context:
    """What the per-layer readers of a train cell read."""

    cell: Cell
    window_s: float
    steps: int
    tokens: int
    spans: Spans
    gmm_shapes: List
    device_kind: str
    trace: Any = None


def make_feed(seed: int, n_batches: int, batch: int, seq: int, vocab: int,
              zipf: List[float]):
    """``n_batches`` distinct batches on the device, from the seed, in one
    call. Every row draws Zipf-distributed ranks with its own exponent from
    ``zipf`` = [lo, hi] over one seeded ranking of the vocabulary, so rows
    differ in how predictable they are; labels are the next tokens."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(key):
        k_perm, k_exp, k_u = jax.random.split(key, 3)
        perm = jax.random.permutation(k_perm, vocab)
        rows = n_batches * batch
        s = jax.random.uniform(k_exp, (rows, 1), minval=zipf[0],
                               maxval=zipf[1])
        ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)[None, :]
        cdf = jnp.cumsum(ranks ** -s, axis=1)
        cdf = cdf / cdf[:, -1:]
        u = jax.random.uniform(k_u, (rows, seq + 1))
        idx = jax.vmap(jnp.searchsorted)(cdf, u)
        toks = perm[jnp.minimum(idx, vocab - 1)].astype(jnp.int32)
        return toks.reshape(n_batches, batch, seq + 1)

    toks = build(base_key(seed, stream=2))
    return [{"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:]}
            for i in range(n_batches)]


def burst_due_times(fb: Dict[str, Any], seconds: float, seed: int
                    ) -> List[float]:
    """Seconds after the window opens at which fault bursts are due: the
    first at ``first_s``, then gaps drawn from ``gap_s`` = [lo, hi], none
    later than ``tail_s`` before the window closes, so that every burst has
    that long to be reported."""
    rng = numpy_rng(seed, stream=3)
    due, t = [], float(fb["first_s"])
    while t <= seconds - fb["tail_s"]:
        due.append(t)
        t += float(rng.uniform(*fb["gap_s"]))
    return due


def run(cell: Cell, seed: int, seconds: float, t_start: float,
        profiler=None, fault: Optional[str] = None,
        control: bool = False) -> RunResult:
    """One run of the cell. ``fault`` breaks the timed path (tests and
    calibration only). With ``control`` the lower-precision control takes
    the program's place in the compared numbers, which then decide
    ``correct``; the program's own go to ``info["program"]`` (calibration
    only)."""
    import jax
    import jax.numpy as jnp

    from repro.config import TrainConfig
    from repro.core.chaos import Fault, FaultInjector
    from repro.detect.cache import SHAPE_CACHE
    from repro.models.model import Runtime, init_params
    from repro.session import MonitorSpec, Session
    from repro.train.step import (TrainState, make_optimizer_for,
                                  make_train_step)

    tr, sz = cell.traffic, cell.config["sizes"]
    o = tr["optimizer"]
    B, S = tr["batch"], tr["seq"]
    cfg = model_config(cell.config)
    rt = Runtime(mesh=None,
                 compute_dtype=jnp.dtype(cell.config["precision"]["compute"]))
    opt = make_optimizer_for(TrainConfig(
        learning_rate=o["lr"], warmup_steps=o["warmup_steps"],
        total_steps=o["total_steps"], weight_decay=o["weight_decay"],
        grad_clip=o["grad_clip"], optimizer="adamw", schedule="cosine"))
    layout = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    params = make_params(layout, seed)
    state = TrainState(params=params, opt_state=opt.init(params),
                       step=jnp.zeros((), jnp.int32))
    del params
    feed = make_feed(seed, tr["feed_batches"], B, S, sz["vocab_size"],
                     tr["zipf"])
    step = make_train_step(cfg, rt, opt)
    if fault == "unchanged":  # a step that returns its state unchanged
        step_fn = jax.jit(lambda s, b: (s, step(s, b)[1]))
    elif fault == "half_batch":  # half the batch left out of the mean
        step_fn = jax.jit(lambda s, b: step(
            s, jax.tree.map(lambda x: x[: B // 2], b)), donate_argnums=(0,))
    else:
        step_fn = jax.jit(step, donate_argnums=(0,))
    compiled = step_fn.lower(state, feed[0]).compile()
    info: Dict[str, Any] = {"step_memory": step_memory(compiled)}

    spec = MonitorSpec.from_dict(tr["monitor"])
    monitored = spec.mode != "off"
    if monitored:
        warm_gmm(tr["gmm_warm"])
    counter = compile_counter()
    recorder = GmmRecorder()
    session = Session(spec)
    spans = Spans()
    injector = FaultInjector([])
    fb = tr.get("faults")
    bursts: List[Dict[str, Any]] = []
    sweep_lags: List[float] = []
    losses: List[float] = []
    with session.monitoring():
        fn = session.observe_step_fn(
            compiled, lowered=compiled,
            flops_per_step=6.0 * cfg.active_param_count() * B * S,
            mem_gb=sum(x.size * x.dtype.itemsize for x in
                       jax.tree.leaves(state.params)) / 2 ** 30)
        params0 = jax.tree.map(jnp.copy, state.params)
        n = 0

        def one_step():
            nonlocal state, n
            with spans("inject"):
                if monitored:
                    injector.apply(n, session.collector)
            with spans("job_step"):
                state, metrics = fn(state, feed[n % len(feed)])
                loss = float(metrics["loss"])
            with spans("on_step"):
                out = session.on_step(n)
            n += 1
            return loss, out

        # the first steps, through the window's own call and feed
        for i in range(CHECK_STEPS):
            losses.append(one_step()[0])
            if i == 0:
                g1 = jax.tree.map(lambda m: m / (1 - o["b1"]),
                                  state.opt_state["mu"])
                g1_norms = leaf_norms(g1)
                del g1
        dp_norms = leaf_norms(jax.tree.map(jnp.subtract, state.params,
                                           params0))
        del params0
        # warm-up: the monitor's warmup fit and first admitted sweeps
        while n < tr["warm_steps"] or (
                monitored and session.sweeps_admitted < tr["warm_sweeps"]):
            one_step()
        shape_misses0 = SHAPE_CACHE.stats()["misses"]
        info["setup_compiles"] = dict(counter.setup_counts)
        if profiler is not None:
            profiler.start()
        recorder.install()
        gc_pauses = _GcPauses()
        recorder.on = counter.on = spans.recording = gc_pauses.on = True
        admitted = session.sweeps_admitted
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        deadline = t0 + seconds
        due = [t0 + t for t in burst_due_times(fb, seconds, seed)] \
            if monitored and fb else []
        n0 = n
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            while True:
                now = time.perf_counter()
                if now >= deadline:
                    break
                if due and now >= due[0]:
                    due.pop(0)
                    injector.faults.append(Fault(
                        fb["kind"], n, n + fb["burst_steps"],
                        fb["magnitude"]))
                    bursts.append({"start": n, "t": now, "detect": None})
                _, out = one_step()
                t_out = time.perf_counter()
                if session.sweeps_admitted > admitted:
                    admitted = session.sweeps_admitted
                    sweep_lags.append(session.async_lag_seconds)
                for inc in out.incidents:
                    if inc.kind != "anomaly":
                        continue
                    steps = set(inc.steps)
                    for b in bursts:
                        if b["detect"] is None and steps.intersection(
                                range(b["start"],
                                      b["start"] + fb["burst_steps"])):
                            b["detect"] = t_out - b["t"]
            t1 = time.perf_counter()
        recorder.on = counter.on = spans.recording = gc_pauses.on = False
        recorder.uninstall()
        gc_pauses.uninstall()
        tsum = profiler.stop() if profiler is not None else None
        steps_in_window = n - n0
        if monitored:
            injector.clear(session.collector)
        info["window_compiles"] = dict(counter.counts)
        info["window_shape_misses"] = SHAPE_CACHE.stats()["misses"] \
            - shape_misses0
    memory = memory_peak_bytes(cell.chips)
    info["memory_stats"] = jax.devices()[0].memory_stats()
    window_s = t1 - t0
    ctx = Context(cell=cell, window_s=window_s, steps=steps_in_window,
                  tokens=steps_in_window * B * S, spans=spans,
                  gmm_shapes=recorder.shapes(),
                  device_kind=jax.devices()[0].device_kind, trace=tsum)
    e2e = {"setup_s": setup_s, "tokens_per_s": ctx.tokens / window_s}
    if bursts:
        latest = max([b["detect"] for b in bursts if b["detect"] is not None],
                     default=0.0)
        times = [b["detect"] if b["detect"] is not None
                 else max(t1 - b["t"], latest) for b in bursts]
        info["detect_p50_s"] = statistics.median(times)
        attempted, failed = len(bursts), sum(b["detect"] is None
                                             for b in bursts)
    else:
        attempted, failed = steps_in_window, 0
    js = np.sort(spans.durations.get("job_step", [0.0])) * 1e3
    slow = js[js > 2 * np.median(js)]
    info["job_step_ms"] = {"p50": float(np.median(js)),
                           "p99": float(np.quantile(js, 0.99)),
                           "max": float(js[-1]), "over_2x_p50": len(slow),
                           "over_2x_p50_s": float(slow.sum() / 1e3)}
    info["stalls"] = stall_report(spans, recorder.timeline, gc_pauses.spans,
                                  t0)
    info.update(memory_peak_bytes=memory, steps_in_window=steps_in_window,
                bursts=bursts, losses=losses, sweep_lags_s=sweep_lags)
    del state, compiled, fn, feed
    lim = cell.limits["numbers"]
    with _highest():
        ref_out = reference_run(cell, seed, layout)
        nums = compare((losses, g1_norms, dp_norms), ref_out)
        if control:
            ctrl = compare(reference_run(cell, seed, layout, mm="fp8"),
                           ref_out)
    if recorder.calls:
        nums.update(_gmm_numbers(gmm_gap(recorder.calls)))
        if control:
            ctrl.update(_gmm_numbers(gmm_gap(recorder.calls, control=True)))
    # numbers without a limit are read and printed, not compared (PERF.md)
    info["readings"] = nums
    if control:  # the control in the program's place decides `correct`
        info["program"], info["control"], nums = nums, ctrl, ctrl
    checks = [Check(k, nums[k], lim[k]["limit"]) for k in lim]
    info["gmm_calls_checked"] = len(recorder.calls)
    return RunResult(attempted=attempted, failed=failed, end_to_end=e2e,
                     checks=checks, context=ctx, info=info)


def reference_run(cell: Cell, seed: int, layout, mm: str = "highest"):
    """The first ``CHECK_STEPS`` steps of the job by the plain references:
    (losses, per-leaf norms of the first clipped gradient, per-leaf norms
    of the parameters' change over the steps)."""
    import jax
    import jax.numpy as jnp

    from refs import adamw
    from refs import dense_transformer as ref

    tr, sz = cell.traffic, cell.config["sizes"]
    o = tr["optimizer"]
    B, S = tr["batch"], tr["seq"]
    rows = tr["ref_rows_per_chunk"]
    feed = make_feed(seed, tr["feed_batches"], B, S, sz["vocab_size"],
                     tr["zipf"])[:CHECK_STEPS]
    params = jax.tree.map(lambda p: p.astype(jnp.float32),
                          make_params(layout, seed))
    p0 = params
    key = (cell.config["name"], json.dumps(sz, sort_keys=True), mm)
    if key not in _REF_GRADS:  # one compile per process, not per seed
        _REF_GRADS[key] = jax.jit(jax.value_and_grad(
            lambda p, t, l: ref.loss_sum(p, t, l, sz, mm)))
    grad = _REF_GRADS[key]
    state = adamw.init(params)
    losses, g1 = [], None
    for i in range(CHECK_STEPS):
        tot, grads = 0.0, None
        for r in range(0, B, rows):
            ls, g = grad(params, feed[i]["tokens"][r:r + rows],
                         feed[i]["labels"][r:r + rows])
            tot += float(ls)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        ntok = B * S
        grads = jax.tree.map(lambda g: g / ntok, grads)
        losses.append(tot / ntok)
        params, state, clipped = adamw.update(params, grads, state, i + 1, o)
        if i == 0:
            g1 = leaf_norms(clipped)
    dp = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return losses, g1, dp


def compare(prog, ref_out) -> Dict[str, float]:
    """The three training numbers of a run against the reference's."""
    losses, g1, dp = prog
    r_losses, r_g1, r_dp = ref_out
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, r_losses))
    grad_gap, _ = worst_leaf_gap(g1, r_g1)
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: a rule on the reference gradient leaves them out
    med = float(np.median(list(r_g1.values())))
    keep = {k for k, v in r_g1.items() if v >= 1e-3 * med}
    change_gap, _ = worst_leaf_gap(dp, r_dp, keep)
    gaps = leaf_gaps(dp, r_dp, keep)
    worst = sorted(gaps, key=lambda n: -gaps[n])[:3]
    return {"loss_rel_gap": loss_gap, "grad_norm_gap": grad_gap,
            "grad_median_gap": float(np.median(list(
                leaf_gaps(g1, r_g1).values()))),
            "change_norm_gap": change_gap,
            "change_median_gap": float(np.median(list(gaps.values()))),
            "change_worst_leaves": [[n, gaps[n]] for n in worst]}


class _GcPauses:
    """(start, seconds) of every garbage collection while ``on`` is set."""

    def __init__(self) -> None:
        self.on = False
        self.spans: List[tuple] = []
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, _info) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.on:
            self.spans.append((self._t0, time.perf_counter() - self._t0))

    def uninstall(self) -> None:
        gc.callbacks.remove(self._cb)


def stall_report(spans, timeline, gc_spans, t0: float, top: int = 4
                 ) -> Dict[str, Any]:
    """Where the window's longest host spans fell (seconds after the window
    opened) and what of the detection plane's GMM work and the garbage
    collector's overlapped them: printed with the run's counts, to find
    the cause of a slow run."""
    def overlapping(a: float, b: float) -> List[List[Any]]:
        return [[n, round(s - t0, 3), round(d, 3)] for n, s, d in timeline
                if s < b and s + d > a and d > 0.05]

    out: Dict[str, Any] = {}
    for name in ("job_step", "on_step"):
        rows = sorted(zip(spans.durations.get(name, []),
                          spans.starts.get(name, [])), reverse=True)[:top]
        out[name] = [{"at_s": round(s - t0, 3), "ms": round(1e3 * d, 1),
                      "gmm": overlapping(s, s + d)} for d, s in rows]
    out["gmm_longest"] = [[n, round(s - t0, 3), round(d, 3)] for n, s, d in
                          sorted(timeline, key=lambda x: -x[2])[:top]]
    gcd = [d for _, d in gc_spans]
    out["gc"] = {"count": len(gcd), "total_s": sum(gcd),
                 "max_s": max(gcd, default=0.0)}
    return out


def _gmm_numbers(gaps: Dict[str, float]) -> Dict[str, float]:
    return {f"gmm_{k}_rel_err": v for k, v in gaps.items()}


def _highest():
    import jax

    return jax.default_matmul_precision("highest")
