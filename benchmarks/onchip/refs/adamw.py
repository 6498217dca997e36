"""Plain reference of the training job's optimizer: AdamW with gradients
clipped by their global norm and a warmup-then-cosine learning rate.

The hyperparameters come from the traffic file's ``optimizer`` block. The
decoupled weight decay applies to every stored parameter of rank 2 or more,
as the job states it: in the stacked layer layout that includes the per-layer
norm gains and biases. Float32 throughout; imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def learning_rate(step: int, o: Dict[str, Any]) -> float:
    """Rate of update number ``step`` (1-based)."""
    peak, warm, total = o["lr"], o["warmup_steps"], o["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    f = o["final_lr_frac"]
    return peak * (f + (1 - f) * 0.5 * (1 + math.cos(math.pi * t)))


def init(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"mu": jax.tree.map(z, params), "nu": jax.tree.map(z, params)}


def clip(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def update(params, grads, state, step: int, o: Dict[str, Any]):
    """One AdamW update; returns (params, state, clipped grads)."""
    b1, b2, eps, wd = o["b1"], o["b2"], o["eps"], o["weight_decay"]
    grads = clip(grads, o["grad_clip"])
    lr = learning_rate(step, o)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                      grads)
    b1c, b2c = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, m, v):
        u = (m / b1c) / (jnp.sqrt(v / b2c) + eps)
        if p.ndim >= 2:
            u = u + wd * p
        return p - lr * u

    params = jax.tree.map(upd, params, mu, nu)
    return params, {"mu": mu, "nu": nu}, grads
