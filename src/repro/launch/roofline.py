"""Three-term roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Terms per (arch x shape x mesh), all in seconds-per-step on TPU v5e
(peaks from ``PEAKS``, keyed by device kind):

    compute    = HLO_FLOPs_per_chip   / peak_FLOPs     (197 TFLOP/s bf16)
    memory     = HLO_bytes_per_chip   / HBM_bw         (819 GB/s)
    collective = coll_bytes_per_chip  / ICI_link_bw    (~50 GB/s/link)

``cost_analysis`` is per-chip under SPMD (all chips run the same program),
so the spec's HLO_FLOPs/(chips x peak) is exactly per-chip/peak.  The
collective bytes come from parsing the post-SPMD optimized HLO (operand
bytes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute), with while-body counts recovered by the R=1/R=2
extrapolation in dryrun.py.

MODEL_FLOPS uses the paper-standard 6*N_active*D (train) or 2*N_active*D
(serve) with N from the LOGICAL architecture (unpadded) — the ratio
MODEL_FLOPS / HLO_FLOPs therefore exposes padding + remat + redundancy
waste.
"""
from __future__ import annotations

import argparse
import json
from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from ..config import SHAPES, resolve
from ..configs import get_config


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator."""

    flops_bf16: float        # FLOP/s
    hbm_bw: float            # bytes/s
    hbm_bytes: float         # device memory
    ici_bw: float            # bytes/s per chip-to-chip link
    source: str


# Keyed by ``jax.Device.device_kind``.  A device missing from the table
# has no peaks: callers report "not measured", never another chip's roof.
_V5E = DevicePeaks(
    flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9,
    ici_bw=50e9,             # 1,600 Gbit/s per chip over 4 links
    source='Google Cloud documentation, "TPU v5e"')
PEAKS: Dict[str, DevicePeaks] = {"TPU v5 lite": _V5E}     # v5e's kind


def device_peaks(device_kind: str) -> Optional[DevicePeaks]:
    """Peaks of ``device_kind``, or None when the table does not know it."""
    return PEAKS.get(device_kind)


def overlap_hidden_fraction(hidden_s: float, exposed_s: float) -> float:
    """Fraction of device time hidden behind host work by ahead-of-time
    dispatch: ``hidden / (hidden + exposed)``.

    ``hidden_s`` is the summed in-flight window (dispatch returned, sync
    not yet entered — the device computing while the host schedules
    other launches) and ``exposed_s`` the summed ``block_until_ready``
    waits the host actually paid.  0.0 at ``inflight=1`` (nothing
    overlaps), → 1.0 when completion never blocks.  Returns 0.0 when
    both terms are ~0 (no launches)."""
    total = float(hidden_s) + float(exposed_s)
    if total <= 0.0:
        return 0.0
    return float(hidden_s) / total


def logical_param_counts(arch: str) -> Dict[str, float]:
    """(total, active) parameter counts from the UNPADDED architecture."""
    cfg = get_config(arch)
    rcfg = resolve(cfg, tp=1)
    total = float(rcfg.param_count())
    active = float(rcfg.active_param_count())
    if cfg.family in ("ssm",):
        # xLSTM blocks: ~10 d^2 per mLSTM block, ~10 d^2 per sLSTM block
        d, l, v = cfg.d_model, cfg.num_layers, cfg.vocab_size
        total = active = l * 10 * d * d + v * d
    if cfg.family == "hybrid":
        d, l, v = cfg.d_model, cfg.num_layers, cfg.vocab_size
        n_rec = sum(1 for k in cfg.layer_kinds() if k == "rglru")
        n_att = cfg.num_layers - n_rec
        rec = 6 * d * d                      # in/gate/out + lru gates
        att = d * (cfg.num_heads + 2 * cfg.num_kv_heads
                   + cfg.num_heads) * (cfg.head_dim or d // cfg.num_heads)
        mlp = 3 * d * cfg.d_ff
        total = active = n_rec * (rec + mlp) + n_att * (att + mlp) + v * d
    return {"total": total, "active": active}


def model_flops(arch: str, shape_name: str) -> float:
    sh = SHAPES[shape_name]
    n = logical_param_counts(arch)["active"]
    cfg = get_config(arch)
    tokens = sh.global_batch * (sh.seq_len if sh.kind != "decode" else 1)
    mult = 6.0 if sh.kind == "train" else 2.0
    flops = mult * n * tokens
    if cfg.family == "audio" and sh.kind != "decode":
        # encoder pass (6 layers over encoder_seq_len frames)
        enc_n = logical_param_counts(arch)["total"] * 0.45
        flops += mult * enc_n * sh.global_batch * cfg.encoder_seq_len
    return flops


def analytic_memory_floor(arch: str, shape_name: str, devices: int) -> float:
    """Deploy-true HBM bytes/chip/step lower bound.

    The CPU-target HLO legalizes every bf16 dot by CONVERTING both operands
    to f32 (measured: 70% of `bytes accessed` on several cells is
    standalone converts) — TPU's MXU consumes bf16 directly, so the HLO
    memory term is a systematic upper bound.  This floor counts what a
    fused TPU lowering must move:

      params      1x read (serve) / 3x (train: fwd + bwd re-read + dW)
      activations C x B_loc*S*d*L*2B (C~4 serve, ~8 train with remat)
      KV cache    write once (prefill) / read once + slot write (decode)
      logits      ~3x B_loc*S*V_loc (train xent) / tiny at serve
      attention   visited-block kv re-reads (Pallas revisiting grid)
    """
    from ..config import ATTN_FULL, ATTN_LOCAL, ENC_ATTN
    cfg = get_config(arch)
    sh = SHAPES[shape_name]
    rcfg = resolve(cfg, tp=16)
    tp = 16
    dp = devices // tp
    b_loc = max(sh.global_batch // dp, 1)
    d, L = cfg.d_model, cfg.num_layers
    dh, hq, hkv = rcfg.head_dim, rcfg.padded_heads, rcfg.padded_kv_heads
    kv_chip = max(hkv // tp, 1) if hkv >= tp else hkv
    S = sh.seq_len
    params_bytes = 2.0 * rcfg.param_count() / tp
    if cfg.moe is not None:
        # experts sharded over data under EP; dense-TP keeps all per chip
        if cfg.moe.strategy == "ep_a2a":
            params_bytes = 2.0 * (rcfg.active_param_count() / tp
                                  + (rcfg.param_count()
                                     - rcfg.active_param_count()) / devices)
    kinds = cfg.layer_kinds()

    def attn_kv_io(seq_q: int) -> float:
        """Pallas revisiting-grid kv re-reads per chip (prefill/train)."""
        bq = bkv = 512
        total = 0.0
        for kind in kinds:
            if kind not in (ATTN_FULL, ATTN_LOCAL, ENC_ATTN):
                continue
            nq = max(seq_q // bq, 1)
            if kind == ATTN_LOCAL:
                per_q = min(cfg.sliding_window // bkv + 2, nq)
                pairs = nq * per_q
            else:
                pairs = nq * (nq + 1) // 2
            total += pairs * 2 * bkv * dh * 2.0 * b_loc * max(hq // tp, 1)
        return total

    if sh.kind == "train":
        act = 8.0 * L * b_loc * S * d * 2.0
        logits = 3.0 * b_loc * S * (rcfg.padded_vocab / tp) * 2.0
        if cfg.moe is not None:
            act *= (1 + cfg.moe.top_k * cfg.moe.capacity_factor)
        return 3.0 * params_bytes + act + logits + 3.5 * attn_kv_io(S)
    if sh.kind == "prefill":
        act = 4.0 * L * b_loc * S * d * 2.0
        kv_write = sum(
            2.0 * b_loc * (min(cfg.sliding_window, S)
                           if k == ATTN_LOCAL else S) * kv_chip * dh * 2.0
            for k in kinds if k in (ATTN_FULL, ATTN_LOCAL, ENC_ATTN))
        return params_bytes + act + kv_write + attn_kv_io(S)
    # decode: weights + full KV read per token
    kv_read = 0.0
    for k in kinds:
        if k == ATTN_LOCAL:
            s_here = min(cfg.sliding_window, S)
            kv_read += 2.0 * b_loc * s_here * kv_chip * dh * 2.0
        elif k in (ATTN_FULL, ENC_ATTN):
            s_here = S // dp if sh.global_batch < dp else S
            kv_read += 2.0 * b_loc * s_here * kv_chip * dh * 2.0
    return params_bytes + kv_read + 6.0 * L * b_loc * d * 2.0


@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    compute_s: float
    memory_s: float            # deploy-true floor (see analytic_memory_floor)
    memory_hlo_s: float        # CPU-target HLO upper bound
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops_global: float
    useful_ratio: float
    bound_step_s: float
    roofline_frac: float       # max-term / sum-of-terms lower bound quality
    note: str = ""

    def table_row(self) -> str:
        return (f"| {self.arch} | {self.shape} | {self.mesh} | "
                f"{self.compute_s:.2e} | {self.memory_s:.2e} | "
                f"{self.memory_hlo_s:.2e} | {self.collective_s:.2e} | "
                f"**{self.dominant}** | "
                f"{self.useful_ratio:.2f} | {self.roofline_frac:.2f} |")


SUGGESTIONS = {
    "compute": ("compute-bound: raise MFU via larger per-chip tiles / fewer "
                "pad heads / less remat recompute"),
    "memory": ("HBM-bound: shrink bytes moved — fuse softmax/xent, bf16 "
               "masters, windowed KV, or shard the dominant resident tensor"),
    "collective": ("ICI-bound: reshard to cut the dominant collective, "
                   "overlap it with compute, or compress the payload"),
}


def analyze(result: Dict) -> Optional[RooflineRow]:
    if not result.get("ok"):
        return None
    ex = result.get("extrapolated", result)
    chips = result["devices"]
    flops_pc = ex["flops"]                       # per-chip (SPMD program)
    bytes_pc = ex["bytes_accessed"]
    coll_pc = float(sum(ex.get("collective_bytes", {}).values()))
    compute_s = flops_pc / _V5E.flops_bf16
    memory_hlo_s = bytes_pc / _V5E.hbm_bw
    floor_bytes = analytic_memory_floor(result["arch"], result["shape"],
                                        chips)
    memory_s = min(max(floor_bytes / _V5E.hbm_bw, 0.0), memory_hlo_s)
    collective_s = coll_pc / _V5E.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(result["arch"], result["shape"])
    hlo_global = flops_pc * chips
    useful = mf / hlo_global if hlo_global else 0.0
    bound = max(terms.values())
    total = sum(terms.values())
    # roofline fraction: how close the binding term is to owning the step
    # (1.0 = perfectly overlapped single-bottleneck execution)
    frac = bound / total if total else 0.0
    return RooflineRow(
        arch=result["arch"], shape=result["shape"], mesh=result["mesh"],
        compute_s=compute_s, memory_s=memory_s, memory_hlo_s=memory_hlo_s,
        collective_s=collective_s,
        dominant=dominant, model_flops=mf, hlo_flops_global=hlo_global,
        useful_ratio=useful, bound_step_s=bound, roofline_frac=frac,
        note=SUGGESTIONS[dominant])


HEADER = """| arch | shape | mesh | compute (s) | memory floor (s) | memory HLO-UB (s) | collective (s) | bottleneck | useful FLOP ratio | overlap-quality |
|------|-------|------|-------------|------------------|-------------------|----------------|------------|-------------------|-----------------|"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    with open(args.results) as f:
        results = json.load(f)
    if isinstance(results, dict):
        results = [results]
    lines = [HEADER]
    details = []
    for r in results:
        row = analyze(r)
        if row is None:
            lines.append(f"| {r.get('arch')} | {r.get('shape')} | "
                         f"{r.get('mesh')} | FAILED | | | | | | |")
            continue
        lines.append(row.table_row())
        details.append(
            f"- **{row.arch} x {row.shape} ({row.mesh})** — dominant: "
            f"{row.dominant} ({row.bound_step_s:.2e}s); MODEL_FLOPS "
            f"{row.model_flops:.2e}, HLO {row.hlo_flops_global:.2e} "
            f"(useful ratio {row.useful_ratio:.2f}). {row.note}")
    text = "\n".join(lines) + "\n\n" + "\n".join(details) + "\n"
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
