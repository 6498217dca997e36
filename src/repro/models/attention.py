"""Attention: GQA / sliding-window / MLA, full-sequence (blocked, online-softmax)
and single-token decode with KV caches (full, rolling-buffer, MLA-latent).

The full-sequence path has two implementations of one algorithm. On a TPU,
a call that the kernel can take (MHA, Dk == Dv, no window, no query offset,
Sq == Sk a multiple of 128, no mesh) runs the Pallas flash kernel
(``splash_attention``): causal blocks above the diagonal are skipped and no
score block leaves VMEM. Everything else, and every run off a TPU, scans over
KV blocks with an online softmax (``_flash``): O(S * kv_block) memory, which
makes the 32k prefill dry-run cells feasible. That scan writes the whole
S x S score matrix (and p, dp, ds at the same size) once kv_block >= S.
``PATH_COUNTS`` counts the calls traced on each path.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.config import ModelConfig
from repro.models.layers import apply_norm, apply_rope, fanin_init

NEG_INF = -1e30

# attention calls traced per path ("pallas" / "blocked"): counted once per
# trace, not per execution
PATH_COUNTS: collections.Counter = collections.Counter()


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    d, hd = cfg.d_model, cfg.head_dim
    if cfg.attn_kind == "mla":
        return {
            "q_down": {"kernel": fanin_init(ks[0], (d, cfg.q_lora_rank))},
            "q_norm": {"scale": jnp.ones((cfg.q_lora_rank,), jnp.float32)},
            "q_up": {"kernel": fanin_init(
                ks[1], (cfg.q_lora_rank, cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)))},
            "kv_down": {"kernel": fanin_init(ks[2], (d, cfg.kv_lora_rank + cfg.qk_rope_dim))},
            "kv_norm": {"scale": jnp.ones((cfg.kv_lora_rank,), jnp.float32)},
            "kv_up": {"kernel": fanin_init(
                ks[3], (cfg.kv_lora_rank, cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim)))},
            "out": {"kernel": fanin_init(ks[4], (cfg.n_heads * cfg.v_head_dim, d))},
        }
    return {
        "q": {"kernel": fanin_init(ks[0], (d, cfg.n_heads * hd))},
        "k": {"kernel": fanin_init(ks[1], (d, cfg.n_kv_heads * hd))},
        "v": {"kernel": fanin_init(ks[2], (d, cfg.n_kv_heads * hd))},
        "out": {"kernel": fanin_init(ks[3], (cfg.n_heads * hd, d))},
    }


# ---------------------------------------------------------------------------
# Blocked full-sequence attention (online softmax over KV blocks)
# ---------------------------------------------------------------------------

def _kv_blocks(k, v, kv_block):
    B, Sk, KV, Dk = k.shape
    Dv = v.shape[-1]
    n_blocks = (Sk + kv_block - 1) // kv_block
    pad = n_blocks * kv_block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, n_blocks, kv_block, KV, Dk).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, kv_block, KV, Dv).transpose(1, 0, 2, 3, 4)
    return kb, vb, jnp.arange(n_blocks) * kv_block


def _block_mask(pos_q, pos_k, Sk, causal, window):
    mask = pos_k[None, :] < Sk  # kv padding
    if causal:
        mask = mask & (pos_k[None, :] <= pos_q[:, None])
    if window:
        mask = mask & (pos_q[:, None] - pos_k[None, :] < window)
    return mask  # (Sq, bk)


def _flash_fwd_impl(q, k, v, causal, window, q_offset, kv_block, scale,
                    scores_bf16):
    B, Sq, H, Dk = q.shape
    _, Sk, KV, Dv = v.shape
    G = H // KV
    score_t = jnp.bfloat16 if scores_bf16 else jnp.float32
    kv_block = min(kv_block, Sk)
    kb, vb, starts = _kv_blocks(k, v, kv_block)
    qg = q.reshape(B, Sq, KV, G, Dk)
    pos_q = q_offset + jnp.arange(Sq)

    def body(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, start = blk
        pos_k = start + jnp.arange(kv_block)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_blk,
                       preferred_element_type=score_t)
        s = s.astype(jnp.float32) * scale
        mask = _block_mask(pos_q, pos_k, Sk, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, KV, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, starts))
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv).astype(q.dtype)
    return out, m, l_safe


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, q_offset, kv_block, scale, scores_bf16):
    out, _, _ = _flash_fwd_impl(q, k, v, causal, window, q_offset, kv_block,
                                scale, scores_bf16)
    return out


def _flash_vjp_fwd(q, k, v, causal, window, q_offset, kv_block, scale,
                   scores_bf16):
    out, m, l = _flash_fwd_impl(q, k, v, causal, window, q_offset, kv_block,
                                scale, scores_bf16)
    # O(S) residuals only — the whole point. The naive scan-of-softmax
    # backward saves every (Sq, kv_block) probability block (full S x S
    # matrices in HBM); this flash-style VJP recomputes them blockwise.
    return out, (q, k, v, out, m, l)


def _flash_vjp_bwd(causal, window, q_offset, kv_block, scale, scores_bf16,
                   res, g):
    q, k, v, out, m, l = res
    B, Sq, H, Dk = q.shape
    _, Sk, KV, Dv = v.shape
    G = H // KV
    score_t = jnp.bfloat16 if scores_bf16 else jnp.float32
    kv_block = min(kv_block, Sk)
    kb, vb, starts = _kv_blocks(k, v, kv_block)
    qg = q.reshape(B, Sq, KV, G, Dk)
    do = g.reshape(B, Sq, KV, G, Dv)
    og = out.reshape(B, Sq, KV, G, Dv)
    # D_i = sum_v dO_i * O_i  (flash-attention-2 backward)
    D = jnp.einsum("bqkgv,bqkgv->bkgq", do.astype(jnp.float32),
                   og.astype(jnp.float32))
    pos_q = q_offset + jnp.arange(Sq)

    def body(dq_acc, blk):
        k_blk, v_blk, start = blk
        pos_k = start + jnp.arange(kv_block)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_blk,
                       preferred_element_type=score_t)
        s = s.astype(jnp.float32) * scale
        mask = _block_mask(pos_q, pos_k, Sk, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - m[..., None]) / l[..., None]  # exact softmax weights
        dv_blk = jnp.einsum("bkgqs,bqkgv->bskv", p.astype(do.dtype), do,
                            preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqkgv,bskv->bkgqs", do, v_blk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - D[..., None])  # (B,KV,G,Sq,bk) f32
        ds = ds.astype(q.dtype)
        dq_blk = jnp.einsum("bkgqs,bskd->bqkgd", ds, k_blk,
                            preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bkgqs,bqkgd->bskd", ds, qg,
                            preferred_element_type=jnp.float32)
        return dq_acc + dq_blk, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, Sq, KV, G, Dk), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (kb, vb, starts))
    n_blocks = kb.shape[0]
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, n_blocks * kv_block, KV, Dk)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, n_blocks * kv_block, KV, Dv)
    dq = (dq * scale).reshape(B, Sq, H, Dk)
    dk = dk[:, :Sk] * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv[:, :Sk].astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _takes_kernel(q, v, *, window, q_offset, mesh) -> bool:
    """Whether the Pallas flash kernel can take this call."""
    _, Sq, H, Dk = q.shape
    _, Sk, KV, Dv = v.shape
    return (jax.default_backend() == "tpu" and window == 0 and q_offset == 0
            and Sq == Sk and H == KV and Dk == Dv and Sq % 128 == 0
            and mesh is None)


def _splash(q, k, v, causal, scale, interpret=False):
    """(B, S, H, D) MHA through ``splash_attention``: bf16 operands, f32
    softmax statistics and accumulators. The largest of 512/256/128 that
    divides S sets every block, and one fused kernel gives dq, dk and dv:
    the fastest of a sweep on a TPU v5e at GPT-2's train shape (PERF.md)."""
    _, S, H, _ = q.shape
    blk = next(b for b in (512, 256, 128) if S % b == 0)
    head_mask = splash.CausalMask((S, S)) if causal else splash.FullMask((S, S))
    kernel = splash.make_splash_mha_single_device(
        splash.MultiHeadMask([head_mask] * H),
        block_sizes=splash.BlockSizes(
            block_q=blk, block_kv=blk, block_q_dkv=blk, block_kv_dkv=blk,
            use_fused_bwd_kernel=True),
        interpret=interpret)
    heads = lambda x: x.transpose(0, 2, 1, 3)  # (B,S,H,D) <-> (B,H,S,D)
    return heads(jax.vmap(kernel)(heads(q * scale), heads(k), heads(v)))


def blocked_attention(
    q: jnp.ndarray,  # (B, Sq, H, hd_qk)
    k: jnp.ndarray,  # (B, Sk, KV, hd_qk)
    v: jnp.ndarray,  # (B, Sk, KV, hd_v)
    *,
    causal: bool,
    window: int = 0,
    q_offset: int = 0,
    kv_block: int = 1024,
    scale: Optional[float] = None,
    scores_bf16: bool = False,
    mesh=None,
) -> jnp.ndarray:
    """Flash-style attention: the Pallas kernel where it can take the call
    (``_takes_kernel``), else the online-softmax scan with its block-recompute
    custom VJP. ``mesh``: the call's mesh, if it is sharded."""
    Dk = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dk)
    if _takes_kernel(q, v, window=window, q_offset=q_offset, mesh=mesh):
        PATH_COUNTS["pallas"] += 1
        return _splash(q, k, v, causal, scale)
    PATH_COUNTS["blocked"] += 1
    return _flash(q, k, v, causal, window, q_offset, kv_block, float(scale),
                  scores_bf16)


# ---------------------------------------------------------------------------
# GQA / SWA full-sequence forward
# ---------------------------------------------------------------------------

def gqa_forward(params, cfg: ModelConfig, x, *, kv_block: int = 1024, rt=None):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = (x @ params["q"]["kernel"].astype(x.dtype)).reshape(B, S, cfg.n_heads, hd)
    k = (x @ params["k"]["kernel"].astype(x.dtype)).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ params["v"]["kernel"].astype(x.dtype)).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.use_rope:
        pos = jnp.arange(S)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    seq_shard = _seq_shard_spec(rt, cfg, B, S)
    if seq_shard is not None:
        # heads don't divide the model axis: shard attention over the QUERY
        # sequence instead (K/V replicated across model ranks) — removes the
        # 16x replicated attention compute for e.g. 9-head smollm
        q = rt.shard(q, seq_shard)
    o = blocked_attention(q, k, v, causal=cfg.causal,
                          window=cfg.sliding_window, kv_block=kv_block,
                          scores_bf16=bool(rt and rt.attn_scores_bf16),
                          mesh=rt and rt.mesh)
    if seq_shard is not None:
        o = rt.shard(o, seq_shard)
    return o.reshape(B, S, cfg.n_heads * hd) @ params["out"]["kernel"].astype(x.dtype)


def _seq_shard_spec(rt, cfg: ModelConfig, B: int, S: int):
    from jax.sharding import PartitionSpec as P

    if (rt is None or rt.mesh is None or not rt.attn_seq_shard
            or rt.strategy != "tp" or S <= 1):
        return None
    msize = rt.mesh.shape.get(rt.model_axis, 1)
    if cfg.n_heads % msize == 0 or S % msize != 0:
        return None
    return P(rt.batch_spec(B), rt.model_axis, None, None)


# ---------------------------------------------------------------------------
# MLA full-sequence forward (naive materialisation: MXU-friendly at prefill)
# ---------------------------------------------------------------------------

def mla_forward(params, cfg: ModelConfig, x, *, kv_block: int = 1024, rt=None):
    B, S, _ = x.shape
    H, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    pos = jnp.arange(S)

    cq = x @ params["q_down"]["kernel"].astype(x.dtype)
    cq = _rms(cq, params["q_norm"]["scale"])
    q = (cq @ params["q_up"]["kernel"].astype(x.dtype)).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)

    ckv = x @ params["kv_down"]["kernel"].astype(x.dtype)
    c_kv, k_rope = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c_kv = _rms(c_kv, params["kv_norm"]["scale"])
    k_rope = apply_rope(k_rope[:, :, None, :], pos, cfg.rope_theta)  # (B,S,1,dr)

    kv = (c_kv @ params["kv_up"]["kernel"].astype(x.dtype)).reshape(B, S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

    o = blocked_attention(q_full, k, v, causal=cfg.causal, kv_block=kv_block,
                          scale=1.0 / math.sqrt(dn + dr),
                          scores_bf16=bool(rt and rt.attn_scores_bf16),
                          mesh=rt and rt.mesh)
    return o.reshape(B, S, H * dv) @ params["out"]["kernel"].astype(x.dtype)


def _rms(x, scale, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (x * scale).astype(dt)


def attention_forward(params, cfg: ModelConfig, x, *, kv_block: int = 1024,
                      rt=None):
    if cfg.attn_kind == "mla":
        return mla_forward(params, cfg, x, kv_block=kv_block, rt=rt)
    return gqa_forward(params, cfg, x, kv_block=kv_block, rt=rt)


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Cache pytree for one attention layer (shapes only matter for dry-run)."""
    if cfg.attn_kind == "mla":
        return {
            "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
        }
    W = cfg.sliding_window or 0
    slots = min(W, max_len) if W else max_len
    return {
        "k": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, slots, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.full((slots,), -1, jnp.int32),  # absolute position per slot
    }


def gqa_decode(params, cfg: ModelConfig, x, cache, index, start=None):
    """x: (B, 1, d); index: scalar int32 absolute position. Returns (out, cache).

    ``start``: optional (B,) int32 per-sequence first valid absolute position.
    Continuous-batching serving reuses cache rows across requests; a sequence
    that joined the batch at position ``start[b]`` must not attend to slots
    written by the slot's previous occupant (positions < start[b])."""
    B = x.shape[0]
    hd = cfg.head_dim
    q = (x @ params["q"]["kernel"].astype(x.dtype)).reshape(B, 1, cfg.n_heads, hd)
    k = (x @ params["k"]["kernel"].astype(x.dtype)).reshape(B, 1, cfg.n_kv_heads, hd)
    v = (x @ params["v"]["kernel"].astype(x.dtype)).reshape(B, 1, cfg.n_kv_heads, hd)
    if cfg.use_rope:
        pos = index + jnp.zeros((1,), jnp.int32)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)  # rotate at write time

    slots = cache["k"].shape[1]
    slot = jnp.where(cfg.sliding_window > 0, index % slots, index)
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, 1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, 1)
    pos_buf = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], index[None].astype(jnp.int32), slot, 0)

    G = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    valid = (pos_buf >= 0) & (pos_buf <= index)
    if cfg.sliding_window:
        valid = valid & (index - pos_buf < cfg.sliding_window)
    if start is None:
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    else:
        valid = valid[None, :] & (pos_buf[None, :] >= start[:, None])  # (B,S)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, 1, cfg.n_heads * hd).astype(x.dtype)
    out = o @ params["out"]["kernel"].astype(x.dtype)
    return out, {"k": k_cache, "v": v_cache, "pos": pos_buf}


def mla_decode(params, cfg: ModelConfig, x, cache, index, start=None):
    """Weight-absorbed MLA decode (DeepSeek-V2 §absorption): scores and values
    computed directly against the latent cache — no per-head K/V materialised.
    ``start``: optional (B,) per-sequence first valid position (see gqa_decode)."""
    B = x.shape[0]
    H, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    pos = index + jnp.zeros((1,), jnp.int32)

    cq = _rms(x @ params["q_down"]["kernel"].astype(x.dtype), params["q_norm"]["scale"])
    q = (cq @ params["q_up"]["kernel"].astype(x.dtype)).reshape(B, 1, H, dn + dr)
    q_nope, q_rope = q[..., :dn], apply_rope(q[..., dn:], pos, cfg.rope_theta)

    ckv = x @ params["kv_down"]["kernel"].astype(x.dtype)
    c_kv = _rms(ckv[..., :r], params["kv_norm"]["scale"])  # (B,1,r)
    k_rope = apply_rope(ckv[..., None, r:], pos, cfg.rope_theta)[:, :, 0]  # (B,1,dr)

    ckv_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), index, 1)
    kr_cache = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), index, 1)

    w_kv = params["kv_up"]["kernel"].reshape(r, H, dn + dv)
    w_uk, w_uv = w_kv[..., :dn], w_kv[..., dn:]
    # absorb W_uk into the query: q_lat (B,H,r)
    q_lat = jnp.einsum("bohn,rhn->bhr", q_nope, w_uk.astype(x.dtype))
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv_cache, preferred_element_type=jnp.float32)
         + jnp.einsum("bohp,bsp->bhs", q_rope, kr_cache, preferred_element_type=jnp.float32))
    s = s / math.sqrt(dn + dr)
    S = ckv_cache.shape[1]
    valid = jnp.arange(S) <= index
    if start is None:
        s = jnp.where(valid[None, None, :], s, NEG_INF)
    else:
        valid = valid[None, :] & (jnp.arange(S)[None, :] >= start[:, None])
        s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", p.astype(ckv_cache.dtype), ckv_cache,
                       preferred_element_type=jnp.float32)
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), w_uv.astype(x.dtype))
    out = o.reshape(B, 1, H * dv) @ params["out"]["kernel"].astype(x.dtype)
    return out, {"c_kv": ckv_cache, "k_rope": kr_cache}


def attention_decode(params, cfg: ModelConfig, x, cache, index, start=None):
    if cfg.attn_kind == "mla":
        return mla_decode(params, cfg, x, cache, index, start=start)
    return gqa_decode(params, cfg, x, cache, index, start=start)
