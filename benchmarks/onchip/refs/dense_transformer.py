"""Plain reference of a dense decoder-only transformer (GPT-2, OLMo).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: the
whole causal score matrix, no kernels, no cache, no batching tricks. It reads
the weights by the names of the program's parameter layout (``embed.table``,
``final_norm``, and ``layers`` stacked along a leading layer axis with
``norm1``, ``attn.{q,k,v,out}.kernel``, ``norm2``,
``ffn.{up,gate,down}.kernel``), and its sizes from a configuration file. It
imports nothing of the program.

``mm`` selects the arithmetic of every matrix product: ``"highest"`` (the
reference) or ``"fp8"`` (the control: both operands rounded to float8 e4m3
with a per-tensor scale, then multiplied exactly; gradients pass the
rounding unchanged, as in float8 training with scaled casts).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x):
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def einsum(mm: str, eq: str, a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mm == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif mm != "highest":
        raise ValueError(f"unknown matmul arithmetic {mm!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def norm(p: Dict[str, Any], x, kind: str, eps: float):
    if kind == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return x * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        x = x * p["scale"] + p["bias"]
    return x


def rope(x, theta: float):
    """Rotary embedding over (B, S, H, hd), rotating the two halves of each
    head (GPT-NeoX / OLMo layout), positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def act(name: str, x):
    if name == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if name == "silu":
        return jax.nn.silu(x)
    raise ValueError(f"unknown activation {name!r}")


def block(p: Dict[str, Any], x, sz: Dict[str, Any], mm: str):
    B, S, d = x.shape
    H, KV, hd = sz["n_heads"], sz["n_kv_heads"], sz["head_dim"]
    h = norm(p["norm1"], x, sz["norm"], sz["norm_eps"])
    q = einsum(mm, "bsd,de->bse", h, p["attn"]["q"]["kernel"]
               ).reshape(B, S, H, hd)
    k = einsum(mm, "bsd,de->bse", h, p["attn"]["k"]["kernel"]
               ).reshape(B, S, KV, hd)
    v = einsum(mm, "bsd,de->bse", h, p["attn"]["v"]["kernel"]
               ).reshape(B, S, KV, hd)
    if sz["rope"]:
        q, k = rope(q, sz["rope_theta"]), rope(k, sz["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = einsum(mm, "bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = einsum(mm, "bhqk,bkhd->bqhd", a, v).reshape(B, S, H * hd)
    x = x + einsum(mm, "bse,ed->bsd", o, p["attn"]["out"]["kernel"])
    h = norm(p["norm2"], x, sz["norm"], sz["norm_eps"])
    up = einsum(mm, "bsd,df->bsf", h, p["ffn"]["up"]["kernel"])
    if sz["glu"]:
        gate = einsum(mm, "bsd,df->bsf", h, p["ffn"]["gate"]["kernel"])
        up = act(sz["act"], gate) * up
    else:
        up = act(sz["act"], up)
    return x + einsum(mm, "bsf,fd->bsd", up, p["ffn"]["down"]["kernel"])


def logits(params: Dict[str, Any], tokens, sz: Dict[str, Any],
           mm: str = "highest"):
    """(B, S) token ids -> (B, S, vocab_size) float32 logits."""
    table = params["embed"]["table"].astype(jnp.float32)
    x = table[tokens]
    layers = params["layers"]
    step = jax.checkpoint(lambda p, h: block(p, h, sz, mm))
    for i in range(sz["n_layers"]):
        x = step(jax.tree.map(lambda a: a[i].astype(jnp.float32), layers), x)
    x = norm(params.get("final_norm", {}), x, sz["norm"], sz["norm_eps"])
    out = einsum(mm, "bsd,vd->bsv", x, table)
    return out[..., : sz["vocab_size"]]


def loss_sum(params: Dict[str, Any], tokens, labels, sz: Dict[str, Any],
             mm: str = "highest"):
    """Summed next-token cross-entropy over every position of the rows."""
    lg = logits(params, tokens, sz, mm)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll)
