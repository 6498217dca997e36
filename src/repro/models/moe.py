"""Mixture-of-Experts FFN with capacity-based dropless-ish dispatch.

Two execution paths share the same local dispatch math:

* local (no mesh / tests): sort -> capacity-pad -> grouped GEMM -> combine.
* sharded (production): ``shard_map`` over the whole mesh. Tokens are resharded
  flat across the dispatch axes; each device builds its (E, C_loc, d) send
  buffer, an ``all_to_all`` over the "model" axis moves token blocks to the
  devices owning each expert shard (expert parallelism), a grouped GEMM runs
  the local experts, and the inverse all_to_all + combine restores token order.
  When the token count is too small to shard over "model" (decode), tokens stay
  replicated across "model" and each device computes only its expert shard,
  combined with a psum — the all-reduce variant of EP.

Collectives emitted (visible in the dry-run HLO): all-to-all (dispatch/return)
or all-reduce (decode combine) — the TPU analogue of NCCL alltoall in GPU MoE.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.layers import activation, fanin_init
from repro.models.ffn import init_ffn, ffn_forward


def init_moe(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 5)
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    p: Dict[str, Any] = {
        "router": {"kernel": fanin_init(ks[0], (d, e))},
        "experts": {
            "up": fanin_init(ks[1], (e, d, f)),
            "down": fanin_init(ks[2], (e, f, d)),
        },
    }
    if cfg.glu:
        p["experts"]["gate"] = fanin_init(ks[3], (e, d, f))
    if cfg.n_shared_experts:
        p["shared"] = init_ffn(ks[4], cfg, f * cfg.n_shared_experts)
    return p


# ---------------------------------------------------------------------------
# Local dispatch (runs per-device in the sharded path, globally otherwise)
# ---------------------------------------------------------------------------

def _expert_ffn(experts: Dict[str, jnp.ndarray], cfg: ModelConfig, xs: jnp.ndarray):
    """xs: (E_local, C, d) -> (E_local, C, d). Grouped GEMM via batch matmul."""
    act = activation(cfg.act)
    up = jnp.einsum("ecd,edf->ecf", xs, experts["up"].astype(xs.dtype))
    if "gate" in experts:
        gate = jnp.einsum("ecd,edf->ecf", xs, experts["gate"].astype(xs.dtype))
        h = act(gate) * up
    else:
        h = act(up)
    return jnp.einsum("ecf,efd->ecd", h, experts["down"].astype(xs.dtype))


def _dispatch(x, top_idx, E: int, C: int):
    """Scatter tokens into per-expert capacity slots.

    x: (T, d); top_idx: (T, k) int32. Returns (buf (E, C, d), slot (T*k,),
    keep (T*k,), token_of (T*k,), order (T*k,)) where slot indexes
    buf.reshape(E*C, d) and order is the expert-sorted permutation.
    """
    T, k = top_idx.shape
    flat = top_idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    token_of = order // k
    counts = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k, dtype=jnp.int32) - offsets[sorted_e]
    keep = pos < C
    slot = jnp.where(keep, sorted_e * C + pos, E * C)  # E*C = drop bucket
    buf = jnp.zeros((E * C + 1, x.shape[-1]), x.dtype).at[slot].set(x[token_of])
    return buf[: E * C].reshape(E, C, -1), slot, keep, token_of, order


def _combine(ys, slot, keep, token_of, top_w, order_k, T: int):
    """Inverse of _dispatch with routing weights applied. ys: (E, C, d)."""
    d = ys.shape[-1]
    flat_w = top_w.reshape(-1)[order_k]  # weights in sorted order
    rows = jnp.concatenate([ys.reshape(-1, d),
                            jnp.zeros((1, d), ys.dtype)], axis=0)[slot]
    rows = rows * jnp.where(keep, flat_w, 0.0).astype(rows.dtype)[:, None]
    return jnp.zeros((T, d), ys.dtype).at[token_of].add(rows)


def _moe_local(x, top_idx, top_w, experts, cfg: ModelConfig, C: int):
    """Fully local MoE on (T, d) tokens."""
    T, k = top_idx.shape
    buf, slot, keep, token_of, order = _dispatch(x, top_idx, cfg.n_experts, C)
    ys = _expert_ffn(experts, cfg, buf)
    return _combine(ys, slot, keep, token_of, top_w, order, T)


# ---------------------------------------------------------------------------
# Sharded dispatch (shard_map over the mesh)
# ---------------------------------------------------------------------------

def _moe_sharded_a2a(x, top_idx, top_w, experts, cfg, C, model_axis):
    """Tokens sharded over all axes incl. model; all_to_all expert exchange."""
    E = cfg.n_experts
    T, k = top_idx.shape
    buf, slot, keep, token_of, order = _dispatch(x, top_idx, E, C)
    # (E, C, d) -> (E_loc, M*C, d): expert shards move to their owners
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1, tiled=True)
    ys = _expert_ffn(experts, cfg, buf)
    ys = jax.lax.all_to_all(ys, model_axis, split_axis=1, concat_axis=0, tiled=True)
    return _combine(ys, slot, keep, token_of, top_w, order, T)


def _moe_sharded_replicated(x, top_idx, top_w, experts, cfg, C, model_axis):
    """Tokens replicated over the model axis (decode); experts stay sharded
    over `model_axis` (E_loc per device); contributions combined with a psum
    — the all-reduce variant of expert parallelism."""
    E = cfg.n_experts
    T, k = top_idx.shape
    e_loc = experts["up"].shape[0]
    rank = jax.lax.axis_index(model_axis)
    buf, slot, keep, token_of, order = _dispatch(x, top_idx, E, C)
    buf_loc = jax.lax.dynamic_slice_in_dim(buf, rank * e_loc, e_loc, axis=0)
    ys_loc = _expert_ffn(experts, cfg, buf_loc)
    # scatter local expert outputs back into the full (E, C, d) layout
    ys = jnp.zeros((E, C, ys_loc.shape[-1]), ys_loc.dtype)
    ys = jax.lax.dynamic_update_slice_in_dim(ys, ys_loc, rank * e_loc, axis=0)
    y = _combine(ys, slot, keep, token_of, top_w, order, T)
    return jax.lax.psum(y, model_axis)


def moe_dispatch_compute(x_flat, top_idx, top_w, experts, cfg: ModelConfig, rt) -> jnp.ndarray:
    """x_flat: (T, d) global token stream. rt: models.model.Runtime."""
    T = x_flat.shape[0]
    cf = rt.moe_capacity_factor
    if rt.mesh is None or rt.strategy == "dp":
        # dp strategy: experts are ZeRO-sharded like any other weight and
        # gathered at use; dispatch stays local per data shard
        C = _capacity(T, cfg.moe_top_k, cfg.n_experts, cf)
        return _moe_local(x_flat, top_idx, top_w, experts, cfg, C)

    mesh = rt.mesh
    batch_axes = rt.batch_axes
    model_axis = rt.model_axis
    n_batch = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    n_model = mesh.shape[model_axis]
    if cfg.n_experts % n_model != 0:  # experts not shardable: let GSPMD decide
        C = _capacity(T, cfg.moe_top_k, cfg.n_experts, cf)
        return _moe_local(x_flat, top_idx, top_w, experts, cfg, C)

    token_axes = batch_axes if (batch_axes and T % n_batch == 0) else ()
    use_a2a = bool(token_axes) and T % (n_batch * n_model) == 0
    if use_a2a:
        tok = token_axes + (model_axis,)
        T_loc = T // (n_batch * n_model)
        body = functools.partial(
            _moe_sharded_a2a, cfg=cfg,
            C=_capacity(T_loc, cfg.moe_top_k, cfg.n_experts, cf),
            model_axis=model_axis)
    else:
        tok = token_axes or None
        T_loc = T // n_batch if token_axes else T
        body = functools.partial(
            _moe_sharded_replicated, cfg=cfg,
            C=_capacity(T_loc, cfg.moe_top_k, cfg.n_experts, cf),
            model_axis=model_axis)

    expert_spec = jax.tree.map(lambda _: P(model_axis), experts)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(tok, None), P(tok, None), P(tok, None), expert_spec),
        out_specs=P(tok, None),
        check_vma=False,
    )
    return fn(x_flat, top_idx, top_w, experts)


def _capacity(T_loc: int, k: int, E: int, cf: float) -> int:
    c = int(math.ceil(T_loc * k / E * cf))
    return max(8, (c + 7) // 8 * 8)


# ---------------------------------------------------------------------------
# Full MoE layer
# ---------------------------------------------------------------------------

def moe_forward(params, cfg: ModelConfig, rt, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d). Returns (y, aux_loss)."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    logits = (xf.astype(jnp.float32) @ params["router"]["kernel"]).astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_w, top_idx = jax.lax.top_k(gates, cfg.moe_top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # Switch/GShard load-balance aux loss: E * sum_e f_e * P_e
    E = cfg.n_experts
    density = jnp.mean(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), axis=(0, 1))
    p_mean = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(density * p_mean)

    y = moe_dispatch_compute(xf, top_idx.astype(jnp.int32), top_w.astype(x.dtype),
                             params["experts"], cfg, rt)
    y = y.reshape(B, S, d)
    pin = rt.mesh is not None and rt.remat != "none"
    if pin:
        # TRAINING programs: reshard the shard_map output back to the
        # canonical activation layout HERE — without the explicit constraint
        # GSPMD falls back to "involuntary full rematerialization"
        # (replicate-then-slice) in the backward when the residual add meets
        # model-sharded consumers: an all-gather of the full (B, S, d)
        # activation per MoE layer. Pure-forward (prefill/serve) programs are
        # better off letting GSPMD keep the token sharding through the
        # residual stream, so the pin is train-only.
        y = rt.shard(y, P(rt.batch_spec(B), None, None))
    if cfg.n_shared_experts:
        xs = rt.shard(x, P(rt.batch_spec(B), None, None)) if pin else x
        y = y + ffn_forward(params["shared"], cfg, xs)
    return y, aux
