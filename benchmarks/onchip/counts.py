"""Operations and bytes that the benchmark's work needs, from its shapes.

Model counts follow ``repro.roofline.model_flops`` (6 N per trained token;
N = parameters touched per token, embedding included), copied here with the
parameter count worked out from a configuration file's sizes so that no
program change moves them.
"""
from __future__ import annotations

from typing import Dict


def param_count(sizes: Dict) -> int:
    """Parameters of a dense decoder with the sizes of a configuration file
    (padded vocabulary rows included, as the model stores them)."""
    d, L = sizes["d_model"], sizes["n_layers"]
    hd = sizes["head_dim"]
    attn = d * hd * (2 * sizes["n_heads"] + 2 * sizes["n_kv_heads"])
    ffn = d * sizes["d_ff"] * (3 if sizes["glu"] else 2)
    norm = {"layernorm": 2 * d, "rmsnorm": d, "layernorm_np": 0}[
        sizes["norm"]]
    embed = sizes["padded_vocab"] * d
    head = 0 if sizes["tie_embeddings"] else embed
    return L * (attn + ffn + 2 * norm) + norm + embed + head


def train_flops_per_token(sizes: Dict) -> float:
    """6 N: forward and backward of every parameter, once per token."""
    return 6.0 * param_count(sizes)


def gmm_kernel_counts(kind: str, rows: int, D: int, K: int
                      ) -> Dict[str, float]:
    """FLOPs and HBM bytes that one GMM kernel call needs at its shapes.

    ``best``/``score``: per row and component a (D,)@(D,D) product and a
    squared norm (2 D^2 + 3 D); reads N*D f32, writes 2N (best) or N*K
    values; ``loglik`` (the mean log-likelihood) runs the ``score`` kernel.
    ``stats``/``update``: the same densities plus the responsibility
    softmax and the (K, D), (K, D, D) moment sums (2 D + 2 D^2 per row and
    component); reads N*D, writes O(K D^2). Padding rows are counted: the
    kernel reads and computes them."""
    dens = rows * K * (2 * D * D + 3 * D)
    kind = "score" if kind == "loglik" else kind
    if kind in ("best", "score"):
        out = 2 * rows if kind == "best" else rows * K
        return {"flops": float(dens), "bytes": 4.0 * (rows * D + out)}
    if kind in ("stats", "update"):
        moments = rows * K * (2 * D + 2 * D * D + 4)
        small = K * (1 + D + D * D) + 1
        return {"flops": float(dens + moments),
                "bytes": 4.0 * (rows * D + 2 * small)}
    raise KeyError(f"unknown GMM kernel {kind!r}")


def roofline_seconds(flops: float, nbytes: float, peak: Dict[str, float]
                     ) -> Dict[str, float]:
    """Least time the chip needs for this work, and which bound sets it."""
    t_c = flops / peak["flops_bf16"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
