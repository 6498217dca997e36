"""Three-term roofline analysis from compiled dry-run artifacts.

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / link_bw

HLO_FLOPs/bytes come from ``compiled.cost_analysis()`` (the per-device SPMD
program). Collective bytes are NOT in cost_analysis — they are summed from the
collective ops' operand sizes in the compiled HLO text (see
repro.hloanalysis).

Hardware constants come from `PEAKS`, keyed by ``device_kind``; the dry
run models TPU v5e pods, so it reads the v5e entry.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from repro.config import ModelConfig, ShapeConfig

# Published per-chip peaks, keyed by jax's ``Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GiB HBM at
# 819 GB/s. link_bw is the bandwidth model's per-link ICI figure, not a
# published peak.
V5E = "TPU v5 lite"
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {
        "peak_flops": 197e12,  # bf16 FLOP/s / chip
        "hbm_bw": 819e9,  # B/s / chip
        "hbm_gib": 16.0,
        "link_bw": 50e9,  # B/s / ICI link
    },
}


def device_peaks(device_kind: Optional[str] = None) -> Dict[str, float]:
    """Peaks of ``device_kind``; by default of the chip this process runs
    on. A kind missing from `PEAKS` is an error, never a default. Off a TPU
    the probes simulate a v5e, so they get the v5e entry by name."""
    if device_kind is None:
        import jax

        dev = jax.devices()[0]
        device_kind = dev.device_kind if dev.platform == "tpu" else V5E
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    collective_by_op: Dict[str, float]
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    useful_ratio: float
    bottleneck: str
    memory_analysis: Dict[str, float]
    notes: str = ""

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @property
    def step_time_s(self) -> float:
        """Roofline step-time estimate = max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilisation at the roofline-estimated step time."""
        denom = self.step_time_s * self.n_devices * PEAKS[V5E]["peak_flops"]
        return self.model_flops / denom if denom else 0.0


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6·N_active·D (train) or 2·N_active·tokens (single forward/decode)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens
    # decode: one token per sequence + KV-cache attention reads
    tokens = shape.global_batch
    attn_extra = 0.0
    if cfg.n_heads and cfg.attn_kind != "none":
        span = min(shape.seq_len, cfg.sliding_window or shape.seq_len)
        n_attn = cfg.n_layers if cfg.attn_every == 0 else (
            cfg.n_layers // cfg.attn_every)
        hd = cfg.head_dim if cfg.attn_kind != "mla" else (
            cfg.kv_lora_rank + cfg.qk_rope_dim)
        heads = cfg.n_heads
        attn_extra = 4.0 * tokens * n_attn * heads * hd * span
    return 2.0 * n_active * tokens + attn_extra


def analyze(*, arch: str, shape_name: str, mesh_desc: str, n_devices: int,
            cost: Dict[str, float], hlo_text: str,
            memory_analysis: Optional[Dict[str, float]],
            cfg: ModelConfig, shape: ShapeConfig, notes: str = "",
            pod_axis_devices: int = 1) -> RooflineReport:
    """Derive the three roofline terms from the compiled per-device program.

    FLOPs/bytes/collective-bytes come from the trip-count-corrected HLO parse
    (repro.hloanalysis) — XLA's cost_analysis counts scan bodies once, which
    undercounts scanned-layer models by ~n_layers; the raw XLA numbers are
    kept in the report for reference.
    """
    from repro.hloanalysis import HloCostModel

    model = HloCostModel(hlo_text)
    flops = model.flops
    byts = model.bytes_out
    coll = dict(model.collective_bytes)
    coll_total = sum(coll.values())
    hw = PEAKS[V5E]
    compute_s = flops / hw["peak_flops"]
    memory_s = byts / hw["hbm_bw"]
    collective_s = coll_total / hw["link_bw"]
    mf = model_flops(cfg, shape)
    total_hlo = flops * n_devices
    useful = mf / total_hlo if total_hlo else 0.0
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    notes = (notes + f"; xla_cost_flops={cost.get('flops', 0):.3e} "
             f"xla_cost_bytes={cost.get('bytes accessed', 0):.3e} "
             f"(scan bodies counted once by XLA)")
    return RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_desc, n_devices=n_devices,
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes=coll_total, collective_by_op=coll,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        model_flops=mf, useful_ratio=useful, bottleneck=bottleneck,
        memory_analysis=memory_analysis or {}, notes=notes)


def memory_analysis_dict(compiled) -> Optional[Dict[str, float]]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    if out:
        args = out.get("argument_size_in_bytes", 0.0)
        alias = out.get("alias_size_in_bytes", 0.0)
        out["peak_bytes_per_device"] = (args - alias
                                        + out.get("output_size_in_bytes", 0.0)
                                        + out.get("temp_size_in_bytes", 0.0))
    return out
