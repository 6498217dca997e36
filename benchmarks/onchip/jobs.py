"""Pieces the drivers share: the program's model configuration built from a
configuration file, per-leaf norms, compile counting, and the recorder of the
GMM kernel calls that the detection plane makes in the window."""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

ACTIVATIONS = {"gelu_tanh": "gelu", "silu": "silu"}


def model_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file: the registered
    architecture ``config["arch"]`` with the file's sizes. Sizes the program
    fixes itself (the padded vocabulary, the norm epsilon) must agree."""
    from repro.config import get_arch, padded_vocab

    sz = config["sizes"]
    cfg = dataclasses.replace(
        get_arch(config["arch"]), n_layers=sz["n_layers"],
        d_model=sz["d_model"], n_heads=sz["n_heads"],
        n_kv_heads=sz["n_kv_heads"], head_dim=sz["head_dim"],
        d_ff=sz["d_ff"], vocab_size=sz["vocab_size"], norm_kind=sz["norm"],
        act=ACTIVATIONS[sz["act"]], glu=sz["glu"], use_rope=sz["rope"],
        rope_theta=float(sz.get("rope_theta", 10_000.0)),
        tie_embeddings=sz["tie_embeddings"])
    if cfg.family != "dense" or cfg.attn_kind != "gqa" or cfg.sliding_window:
        raise ValueError(f"{config['name']}: only dense full-attention "
                         "decoders have a plain reference here")
    if padded_vocab(cfg) != sz["padded_vocab"] or sz["norm_eps"] != 1e-5:
        raise ValueError(f"{config['name']}: padded vocabulary or norm "
                         "epsilon differ from what the program runs")
    return cfg


def leaf_norms(tree) -> Dict[str, float]:
    """L2 norm of every leaf, with leaves under ``layers`` split along their
    leading layer axis (``layers.3.attn.q.kernel``). One device call."""
    import jax
    import jax.numpy as jnp

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = [".".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]

    @jax.jit
    def norms(leaves):
        out = []
        for name, x in zip(names, leaves):
            x = x.astype(jnp.float32)
            if name.startswith("layers."):
                out.append(jnp.sqrt(jnp.sum(jnp.square(x),
                                            axis=tuple(range(1, x.ndim)))))
            else:
                out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
        return out

    res: Dict[str, float] = {}
    for name, v in zip(names, norms([x for _, x in flat])):
        v = np.asarray(v, np.float64)
        if name.startswith("layers."):
            rest = name[len("layers."):]
            for i, x in enumerate(v):
                res[f"layers.{i}.{rest}"] = float(x)
        else:
            res[name] = float(v[0])
    return res


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep=None) -> Dict[str, float]:
    """|prog - ref| of every leaf, against the larger of its reference norm
    and the median leaf's."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
            for n in names}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   keep=None) -> Tuple[float, str]:
    """The largest of `leaf_gaps`; returns (gap, leaf)."""
    worst, at = 0.0, ""
    for n, g in leaf_gaps(prog, ref, keep).items():
        if not g <= worst:  # NaN is the worst
            worst, at = g, n
    return worst, at


def step_memory(compiled) -> Dict[str, int]:
    """What the compiler reserves for one call of a compiled program, in
    bytes: its arguments, outputs, outputs aliased onto donated arguments,
    temporaries and code (``{}`` where the backend gives no analysis)."""
    ma = compiled.memory_analysis()
    keys = ("argument", "output", "alias", "temp", "generated_code")
    return {} if ma is None else {
        k: int(getattr(ma, f"{k}_size_in_bytes")) for k in keys}


class CompileCounter:
    """Counts the JAX compile and compile-cache events while ``on`` is set
    (the measured window should see none). JAX's listeners cannot be
    removed, so a process makes one (`compile_counter`) and resets it."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits",
              "/jax/compilation_cache/cache_misses")

    def __init__(self) -> None:
        import jax

        self.on = False
        self.counts: Dict[str, int] = {}
        self.setup_counts: Dict[str, int] = {}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def reset(self) -> "CompileCounter":
        self.on = False
        self.counts, self.setup_counts = {}, {}
        return self

    def _count(self, name: str) -> None:
        if name in self.EVENTS:
            d = self.counts if self.on else self.setup_counts
            d[name.rsplit("/", 1)[1]] = d.get(name.rsplit("/", 1)[1], 0) + 1

    def _event(self, name: str, **kw) -> None:
        self._count(name)

    def _duration(self, name: str, secs: float, **kw) -> None:
        self._count(name)


def warm_gmm(w: Dict[str, Any]) -> None:
    """Compile (or load from the cache) the detection plane's GMM programs
    at every bucket and feature shape the window can reach, before the
    monitor attaches: scoring and E-step passes per row bucket, the
    fixed-size EM refit, and an incremental fold's M-step."""
    import jax
    import jax.numpy as jnp

    from repro.core.gmm import (GMMParams, fit_gmm_streaming, fold_stats,
                                params_from_stats, score_samples,
                                stats_from_batch, total_log_likelihood)

    for D in w["D"]:
        for K in w["K"]:
            params = GMMParams(jnp.full((K,), -np.log(K), jnp.float32),
                               jnp.zeros((K, D), jnp.float32),
                               jnp.broadcast_to(jnp.eye(D), (K, D, D)))
            for rows in w["buckets"]:
                X = np.zeros((rows, D), np.float32)
                jax.block_until_ready(score_samples(X, params))
                stats_from_batch(X, params, nvalid=rows // 2)
            X = np.random.default_rng(0).normal(
                size=(w["fit_rows"], D)).astype(np.float32)
            p, _ = fit_gmm_streaming(X, jax.random.PRNGKey(0),
                                     n_components=K, n_iters=2,
                                     reg=w["reg"])
            float(total_log_likelihood(X, p))
            st, _ = stats_from_batch(X, p)  # an incremental fold's M-step
            params_from_stats(fold_stats(st, st, 0.5), w["reg"])


_COUNTER: List[CompileCounter] = []


def compile_counter() -> CompileCounter:
    """The process's `CompileCounter`, reset."""
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0].reset()


@dataclasses.dataclass
class GmmCall:
    kind: str  # best | loglik | stats | update
    inputs: Tuple
    nvalid: Any
    outputs: Tuple


class GmmRecorder:
    """Keeps the inputs and outputs of the detection plane's GMM kernel
    calls made while ``on`` is set, by wrapping the names the program calls
    them through (``repro.stream.online.score_samples`` and
    ``total_log_likelihood``, ``repro.kernels.ops.gmm_stats`` and
    ``gmm_update``). The wrapped calls still run the program's kernels;
    recording adds a list append. ``timeline`` keeps (name, start, seconds)
    on the host clock of those calls and of the EM fits
    (``online.fit_gmm_streaming``), to place the detection plane's work
    against the job's steps."""

    def __init__(self) -> None:
        self.on = False
        self.calls: List[GmmCall] = []
        self.timeline: List[Tuple[str, float, float]] = []
        self._lock = threading.Lock()
        self._undo: List[Callable[[], None]] = []

    def _wrap(self, module, attr: str,
              record: Optional[Callable] = None) -> None:
        orig = getattr(module, attr)

        def wrapped(*args, **kw):
            t0 = time.perf_counter()
            out = orig(*args, **kw)
            if self.on:
                span = (attr, t0, time.perf_counter() - t0)
                call = record(args, kw, out) if record else None
                with self._lock:
                    self.timeline.append(span)
                    if call is not None:
                        self.calls.append(call)
            return out

        setattr(module, attr, wrapped)
        self._undo.append(lambda: setattr(module, attr, orig))

    def install(self) -> None:
        from repro.kernels import ops
        from repro.stream import online

        self._wrap(online, "score_samples", lambda a, kw, out: GmmCall(
            "best", (a[0], a[1].means, a[1].prec_chol), None, (out[0],)))
        self._wrap(online, "total_log_likelihood", lambda a, kw, out: GmmCall(
            "loglik", (a[0], a[1].log_weights, a[1].means, a[1].prec_chol),
            None, (out,)))
        for kind in ("stats", "update"):
            self._wrap(ops, f"gmm_{kind}", lambda a, kw, out, kind=kind:
                       GmmCall(kind, tuple(a[:4]), kw.get("nvalid"),
                               tuple(out)))
        self._wrap(online, "fit_gmm_streaming")

    def uninstall(self) -> None:
        while self._undo:
            self._undo.pop()()

    def shapes(self) -> List[Tuple[str, int, int, int]]:
        """(kind, rows, D, K) of every recorded call."""
        return [(c.kind, int(c.inputs[0].shape[0]), int(c.inputs[0].shape[1]),
                 int(c.inputs[-2].shape[0] if c.kind == "best"
                     else c.inputs[2].shape[0])) for c in self.calls]


def gmm_gap(calls: List[GmmCall], control: bool = False) -> Dict[str, float]:
    """Worst error of the recorded kernel outputs against the plain GMM
    reference on the same inputs (`answer_gap`): ``score`` over the scoring
    calls (best component, and the mean log-likelihood through the K-wide
    kernel), ``em`` over the E-step/EM calls. With ``control`` the reference
    at ``high`` precision takes the kernels' place: the control's
    reading."""
    import jax.numpy as jnp

    from refs import gmm as ref

    def outputs(c: GmmCall, precision: str):
        X = jnp.asarray(c.inputs[0])
        if c.kind == "best":
            return (ref.best(X, *c.inputs[1:], precision)[0],)
        if c.kind == "loglik":
            return (ref.loglik(X, *c.inputs[1:], precision),)
        fn = ref.stats if c.kind == "stats" else ref.update
        return fn(X, *c.inputs[1:4], nvalid=c.nvalid, precision=precision)

    gaps = {"score": 0.0, "em": 0.0}
    for c in calls:
        key = "score" if c.kind in ("best", "loglik") else "em"
        got = outputs(c, "high") if control else c.outputs
        for g, w in zip(got, outputs(c, "highest")):
            err = answer_gap(g, w)
            if not err <= gaps[key]:
                gaps[key] = err
    return gaps


def answer_gap(got, want) -> float:
    """max |got - want| over max(1, max finite |want|). An answer equal to
    the reference's, the same infinity or NaN where the reference reads one
    (a row of features that is NaN scores NaN in both), agrees; a NaN or an
    infinity where the reference reads something else makes the gap NaN or
    infinite, which no limit admits."""
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    same = (g == w) | (np.isnan(g) & np.isnan(w))
    with np.errstate(invalid="ignore"):
        d = np.where(same, 0.0, np.abs(g - w))
    scale = np.max(np.abs(w[np.isfinite(w)]), initial=1.0)
    return float(np.max(d, initial=0.0) / scale)
