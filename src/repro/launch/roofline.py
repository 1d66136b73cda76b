"""Roofline terms from the dry-run's compiled artifact.

    compute term    = FLOPs / peak FLOP/s
    memory term     = HBM bytes / peak HBM bytes/s
    collective term = wire bytes / ICI bytes/s per link

FLOPs / bytes / collective bytes come from the trip-count-aware HLO analysis
(repro.launch.hloanalysis) of the SPMD-partitioned module: per-device values,
so `chips` is already folded in — the terms below divide by per-chip peaks
only. MODEL_FLOPS is the analytic useful-work count (6*N_active*D for
training; attention terms added explicitly) used for the waste ratio.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.configs.base import ArchConfig, ShapeConfig, phys_vocab
from repro.launch.hloanalysis import HLOAnalysis



@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float               # bf16 FLOP/s
    hbm_bw: float              # HBM bytes/s
    ici_link_bw: float         # interchip bytes/s per link
    hbm_bytes: int


#: Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
#: 819 GB/s, 1,600 Gbit/s of interchip interconnect (50 GB/s on each of a
#: chip's four links).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_link_bw=50e9,
                             hbm_bytes=16 * 2 ** 30),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


# ----------------------------------------------------------------------
# Analytic model FLOPs (useful work)
# ----------------------------------------------------------------------

def param_counts(cfg: ArchConfig) -> Dict[str, float]:
    """Total and active (per-token) parameter counts."""
    d = cfg.d_model
    V = phys_vocab(cfg.vocab_size)
    emb = V * d * (1 if cfg.tie_embeddings else 2)
    per_layer_attn = 0.0
    if cfg.attention != "none" and cfg.num_heads:
        hd = cfg.head_dim
        per_layer_attn = d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd \
            + cfg.num_heads * hd * d
    mlp = 3 * d * cfg.d_ff if cfg.d_ff else 0.0
    moe_total = moe_active = 0.0
    if cfg.moe is not None:
        e = cfg.moe
        moe_total = 3 * d * e.d_ff * e.num_experts + d * e.num_experts
        moe_active = 3 * d * e.d_ff * e.experts_per_token + d * e.num_experts
    ssm = 0.0
    if cfg.ssm is not None:
        di, n, h = cfg.ssm_d_inner, cfg.ssm.state_size, cfg.ssm_num_heads
        ssm = 2 * d * di + 2 * d * n + d * h + di * d

    if cfg.is_ssm:
        layer_total = layer_active = ssm
        n_layers = cfg.num_layers
        total = emb + n_layers * ssm
        active = total
    elif cfg.is_interleaved:
        n_attn = cfg.layer_types.count("attention")
        total = active = emb + (cfg.num_layers - n_attn) * ssm \
            + n_attn * per_layer_attn + cfg.num_layers * mlp
        layer_total = layer_active = ssm + mlp
    elif cfg.is_hybrid:
        groups = cfg.num_layers // cfg.shared_attention_every
        shared = per_layer_attn + mlp
        total = emb + cfg.num_layers * ssm + shared
        # shared block executes once per group
        active = emb + cfg.num_layers * ssm + shared * groups
        layer_total = layer_active = ssm
    else:
        layer_total = per_layer_attn + (moe_total or mlp)
        layer_active = per_layer_attn + (moe_active or mlp)
        n_dec = cfg.num_layers
        total = emb + n_dec * layer_total
        active = emb + n_dec * layer_active
        if cfg.is_encdec:
            enc_layer = per_layer_attn + mlp
            cross = per_layer_attn
            total += cfg.encoder_layers * enc_layer + n_dec * cross
            active += cfg.encoder_layers * enc_layer + n_dec * cross
    if cfg.frontend is not None:
        total += cfg.frontend.embed_dim * d
        active += cfg.frontend.embed_dim * d
    return {"total": total, "active": active}


def model_flops(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """Analytic useful FLOPs per executed step, GLOBAL (all chips).

    train:   6 * N_active * tokens  (+ attention quadratic term)
    prefill: 2 * N_active * tokens  (+ attention term)
    decode:  2 * N_active * batch   (+ attention over the cache)
    """
    counts = param_counts(cfg)
    N = counts["active"]
    B, S = shape.global_batch, shape.seq_len
    d_attn = cfg.num_heads * cfg.head_dim if cfg.num_heads else 0

    def attn_term(tokens, ctx, layers):
        # 2 * (QK^T) + 2 * (PV) = 4 * tokens * ctx * d_attn per layer
        if not d_attn:
            return 0.0
        eff_ctx = min(ctx, cfg.window) if cfg.attention == "swa" else ctx
        return 4.0 * tokens * eff_ctx * layers * d_attn

    if cfg.is_hybrid:
        attn_layers = cfg.num_layers // cfg.shared_attention_every
    elif cfg.is_interleaved:
        attn_layers = cfg.layer_types.count("attention")
    elif cfg.attention == "none":
        attn_layers = 0
    else:
        attn_layers = cfg.num_layers + (cfg.encoder_layers or 0)

    if shape.kind == "train":
        toks = B * S
        flops = 6.0 * N * toks + 3.0 * attn_term(toks, S / 2, attn_layers)
        return flops * max(1, 1)      # microbatching doesn't change totals
    if shape.kind == "prefill":
        toks = B * S
        return 2.0 * N * toks + attn_term(toks, S / 2, attn_layers)
    # decode: one token per sequence against a seq_len cache
    toks = B * 1
    return 2.0 * N * toks + attn_term(toks, S, attn_layers)


# ----------------------------------------------------------------------
# Roofline report
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    hlo_flops_global: float
    useful_ratio: float
    step_time_s: float                 # max of the three terms
    mfu: float                         # model_flops / (chips*peak*step_time)
    memory_fit_gib: float
    note: str = ""

    def as_dict(self):
        return dataclasses.asdict(self)


def build_roofline(cfg: ArchConfig, shape: ShapeConfig, mesh_name: str,
                   chips: int, hlo: HLOAnalysis, memory_bytes: float,
                   peaks: ChipPeaks, note: str = "") -> Roofline:
    compute_s = hlo.dot_flops / peaks.flops
    memory_s = hlo.hbm_bytes / peaks.hbm_bw
    collective_s = hlo.collective_wire_bytes / peaks.ici_link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_global = hlo.dot_flops * chips
    step = max(terms.values())
    mfu = mf / (chips * peaks.flops * step) if step > 0 else 0.0
    return Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck, model_flops=mf, hlo_flops_global=hlo_global,
        useful_ratio=mf / hlo_global if hlo_global else 0.0,
        step_time_s=step, mfu=mfu,
        memory_fit_gib=memory_bytes / 2 ** 30, note=note)
