"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Reads experiments/dryrun/*.json (written by repro.launch.dryrun) and derives,
per (arch × shape) on the single-pod mesh:

  compute term    = HLO_FLOPs_per_chip / peak (bf16 197 TF/s; int8 394 TOP/s)
  memory term     = HLO_bytes_per_chip / 819 GB/s
  collective term = wire_bytes_per_chip / (3 links x 50 GB/s)

plus MODEL_FLOPS = 6·N(_active)·D and the usefulness ratio
MODEL_FLOPS / HLO_FLOPs.  Notes: XLA cost_analysis reports per-device
program cost; totals come from the unroll/extrapolation pass
(``cost_totals``) when present.  Emits CSV + a markdown table to
experiments/roofline.md.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from benchmarks.common import emit
from repro.configs.base import INPUT_SHAPES, get_config
from repro.launch.compile_cache import enable_compile_cache

PEAK_BF16 = 197e12
PEAK_INT8 = 394e12
HBM_BW = 819e9
ICI_LINKS = 3           # per chip on a 2D torus slice (approx)
ICI_BW = 50e9

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")
OUT_MD = os.path.join(os.path.dirname(__file__), "..", "experiments",
                      "roofline.md")


def active_params(arch: str) -> float:
    """MODEL params N (active for MoE) from the config dims."""
    cfg = get_config(arch)
    d = cfg.d_model
    if cfg.family == "ssm":
        per_layer = d * (2 * cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
                         + cfg.ssm_heads) + cfg.d_inner * d
        return cfg.n_layers * per_layer + cfg.vocab * d
    attn = d * cfg.n_heads * cfg.d_head + 2 * d * cfg.n_kv_heads * cfg.d_head \
        + cfg.n_heads * cfg.d_head * d
    glu = 3 if cfg.mlp_kind == "swiglu" else 2
    if cfg.family in ("moe",):
        ffn = glu * d * cfg.d_ff * cfg.top_k
    else:
        ffn = glu * d * cfg.d_ff
    per_layer = attn + ffn
    if cfg.family == "hybrid":
        period = cfg.attn_period
        mamba_pl = d * (2 * cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
                        + cfg.ssm_heads) + cfg.d_inner * d
        moe_pl = glu * d * cfg.d_ff * cfg.top_k
        mlp_pl = glu * d * cfg.d_ff
        per_period = (period - 1) * mamba_pl + attn \
            + (period // 2) * moe_pl + (period - period // 2) * mlp_pl
        return (cfg.n_layers // period) * per_period + cfg.vocab * d
    return cfg.n_layers * per_layer + cfg.vocab * d


def model_flops(arch: str, shape: str) -> float:
    """6·N·D for train, 2·N·D for inference (per step/token batch)."""
    shp = INPUT_SHAPES[shape]
    n = active_params(arch)
    if shp["kind"] == "train":
        tokens = shp["global_batch"] * shp["seq_len"]
        return 6.0 * n * tokens
    if shp["kind"] == "prefill":
        tokens = shp["global_batch"] * shp["seq_len"]
        return 2.0 * n * tokens
    tokens = shp["global_batch"]  # one token per sequence per step
    return 2.0 * n * tokens


def load_results(mesh: str = "16x16") -> Dict[str, dict]:
    out = {}
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, f"*__{mesh}.json"))):
        with open(path) as f:
            r = json.load(f)
        out[f"{r['arch']}__{r['shape']}"] = r
    return out


def analytic_hbm_bytes(r: dict) -> float:
    """Per-chip lower-bound HBM traffic for one step.

    XLA's 'bytes accessed' counts every HLO operand (no fusion residency), a
    loose upper bound — on CPU it labels everything memory-bound.  This model
    counts mandatory traffic only:

      train  : params fwd read + bwd read + update write (3x, bf16) +
               opt moments read+write (4x f32 sizes) + per-layer remat
               checkpoints write+read (2x) + logits write (f32)
      prefill: params read + cache write + layer activations write+read
      decode : params read + cache read + cache write (one slot)
    """
    chips = r["n_chips"]
    cfg = get_config(r["arch"])
    p_local = r["param_bytes"] / chips
    kind = r["kind"]
    tokens = r["global_batch"] * r["seq_len"]
    act_ckpt = tokens * cfg.d_model * 2 * cfg.n_layers / chips  # bf16 inputs
    logits = tokens * cfg.vocab * 4 / chips
    if kind == "train":
        opt_local = r.get("opt_bytes", 0) / chips
        return 3 * p_local + 2 * opt_local + 2 * act_ckpt + logits
    if kind == "prefill":
        cache_local = r.get("cache_bytes", 0) / chips
        return p_local + cache_local + 2 * act_ckpt + logits
    # decode: one token per sequence
    cache_local = r.get("cache_bytes", 0) / chips
    return p_local + cache_local


def roofline_row(r: dict) -> Optional[dict]:
    chips = r["n_chips"]
    tot = r.get("cost_totals")
    if tot:
        flops_pc = tot["flops"]          # per-chip (cost_analysis convention)
        bytes_pc = tot["bytes"]
        wire_pc = tot["wire_bytes"]
        method = tot["method"]
    else:
        flops_pc, bytes_pc = r["hlo_flops"], r["hlo_bytes"]
        wire_pc = r["collectives"]["wire_bytes"]
        method = "scan_body_once(LOWER-BOUND)"
    peak = PEAK_INT8 if r.get("quant") else PEAK_BF16
    t_comp = flops_pc / peak
    t_mem_hlo = bytes_pc / HBM_BW                     # upper bound (unfused)
    t_mem = analytic_hbm_bytes(r) / HBM_BW            # lower bound (mandatory)
    t_coll = wire_pc / (ICI_LINKS * ICI_BW)
    dominant = max((t_comp, "compute"), (t_mem, "memory"),
                   (t_coll, "collective"))[1]
    mf = model_flops(r["arch"], r["shape"])
    useful = mf / (flops_pc * chips) if flops_pc else 0.0
    return {
        "arch": r["arch"], "shape": r["shape"], "method": method,
        "t_compute_s": t_comp, "t_memory_s": t_mem,
        "t_memory_hlo_s": t_mem_hlo, "t_collective_s": t_coll,
        "dominant": dominant, "model_flops": mf,
        "useful_ratio": useful,
    }


def main(quick: bool = False):
    rows_csv = []
    results = load_results()
    md = ["| arch | shape | compute s | memory s (min) | memory s (HLO ub) | "
          "collective s | dominant | MODEL_FLOPS/HLO | method |",
          "|---|---|---|---|---|---|---|---|---|"]
    for key, r in sorted(results.items()):
        rl = roofline_row(r)
        if rl is None:
            continue
        rows_csv.append({
            "name": f"roofline/{rl['arch']}/{rl['shape']}",
            "us_per_call": rl["t_compute_s"] * 1e6,
            "derived": (f"mem_us={rl['t_memory_s'] * 1e6:.1f};"
                        f"mem_hlo_us={rl['t_memory_hlo_s'] * 1e6:.1f};"
                        f"coll_us={rl['t_collective_s'] * 1e6:.1f};"
                        f"dominant={rl['dominant']};"
                        f"useful={rl['useful_ratio']:.3f}")})
        md.append(
            f"| {rl['arch']} | {rl['shape']} | {rl['t_compute_s']:.3e} | "
            f"{rl['t_memory_s']:.3e} | {rl['t_memory_hlo_s']:.3e} | "
            f"{rl['t_collective_s']:.3e} | "
            f"{rl['dominant']} | {rl['useful_ratio']:.3f} | {rl['method']} |")
    if len(md) > 2:
        os.makedirs(os.path.dirname(OUT_MD), exist_ok=True)
        with open(OUT_MD, "w") as f:
            f.write("\n".join(md) + "\n")
    if not rows_csv:
        rows_csv.append({"name": "roofline/no_dryrun_artifacts",
                         "us_per_call": 0.0,
                         "derived": "run repro.launch.dryrun first"})
    return emit(rows_csv)


if __name__ == "__main__":
    enable_compile_cache()
    main()
