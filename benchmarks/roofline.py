"""Roofline analysis (brief §Roofline): derive the three terms per
(arch x shape x mesh) from the dry-run artifacts in reports/dryrun*/.

  compute term    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
  memory term     = HBM_traffic_per_device / HBM_bw
  collective term = collective_bytes_per_device / ICI_link_bw

Hardware constants live in the ``HARDWARE`` table below, keyed by
backend name (default ``tpu_v5e`` -- 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI, brief-provided).  Every emitted report is tagged
with the constants actually used so numbers stay interpretable when
the table grows or an override is applied (``constants_for``).

Also reports MODEL_FLOPS (6*N*D dense / 6*N_active*D MoE; 2*N*D for
prefill; 2*N_active*B per decode step) and the useful-compute ratio
MODEL_FLOPS / HLO_FLOPs, which exposes remat/redundancy waste.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np


# ----------------------------------------------------------------------
# Hardware constants (labelled, overridable -- see constants_for)
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HardwareConstants:
    name: str
    peak_flops: float      # FLOP/s per chip (bf16)
    hbm_bw: float          # bytes/s per chip
    ici_bw: float          # bytes/s per link

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


HARDWARE: Dict[str, HardwareConstants] = {
    # brief-provided v5e numbers; the repo's primary target
    "tpu_v5e": HardwareConstants("tpu_v5e", 197e12, 819e9, 50e9),
    # public spec-sheet numbers, for comparison runs
    "tpu_v4": HardwareConstants("tpu_v4", 275e12, 1228e9, 50e9),
    # rough host-CPU envelope so dev-box reports are not nonsense
    "cpu": HardwareConstants("cpu", 0.5e12, 100e9, 10e9),
}
DEFAULT_BACKEND = "tpu_v5e"


def constants_for(backend: Optional[str] = None,
                  **overrides: float) -> HardwareConstants:
    """Resolve the constants table entry for ``backend`` (default
    ``tpu_v5e``; unknown names fall back to the default) and apply any
    keyword overrides, e.g. ``constants_for("tpu_v5e", ici_bw=45e9)``."""
    hw = HARDWARE.get(backend or DEFAULT_BACKEND, HARDWARE[DEFAULT_BACKEND])
    if overrides:
        hw = dataclasses.replace(hw, **overrides)
    return hw


# legacy module-level aliases (== HARDWARE[DEFAULT_BACKEND])
PEAK_FLOPS = HARDWARE[DEFAULT_BACKEND].peak_flops
HBM_BW = HARDWARE[DEFAULT_BACKEND].hbm_bw
ICI_BW = HARDWARE[DEFAULT_BACKEND].ici_bw


# ----------------------------------------------------------------------
# Analytic model FLOPs
# ----------------------------------------------------------------------

def _param_counts(cfg) -> Dict[str, float]:
    """Total and active (per-token) parameter counts, excluding the
    input embedding table (standard 6ND convention keeps the LM head)."""
    from repro.models import get_api, param_count
    from repro.models.common import ParamDef
    import jax
    defs = get_api(cfg).defs(cfg)
    total = param_count(defs)
    embed = 0
    if "embed" in defs:
        embed = int(np.prod(defs["embed"].shape))
    # MoE: inactive experts do not contribute to per-token FLOPs
    inactive = 0.0
    if cfg.num_experts > 0:
        E, K = cfg.num_experts, cfg.top_k
        F = cfg.effective_moe_ff()
        per_expert = 3 * cfg.d_model * F
        n_moe_layers = cfg.num_layers
        if cfg.family == "hybrid":
            n_moe_layers = (cfg.num_layers // cfg.attn_every) * \
                (cfg.attn_every // cfg.moe_every)
        inactive = n_moe_layers * (E - K) * per_expert
    n = total - embed
    return {"total": float(total), "dense_equiv": float(n),
            "active": float(n - inactive)}


def model_flops(cfg, kind: str, seq: int, batch: int) -> float:
    pc = _param_counts(cfg)
    n_active = pc["active"]
    tokens = batch * seq
    if kind == "train":
        return 6.0 * n_active * tokens
    if kind == "prefill":
        return 2.0 * n_active * tokens
    return 2.0 * n_active * batch          # decode: one token per sequence


# ----------------------------------------------------------------------
# Roofline rows from dry-run artifacts
# ----------------------------------------------------------------------

@dataclasses.dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_ratio: float
    mem_gb_per_device: float
    step_time_s: float
    roofline_fraction: float   # compute_s / max(term) -- MFU-style


def analyze_report(rep: dict, chips: int,
                   hw: Optional[HardwareConstants] = None
                   ) -> Optional[RooflineRow]:
    from repro.configs import get_arch
    if rep.get("skipped"):
        return None
    hw = hw or constants_for()
    hc = rep["hlo_accounting"]
    spec = get_arch(rep["arch"])
    sh = spec.shape(rep["shape"])
    compute_s = hc["flops_per_device"] / hw.peak_flops
    memory_s = hc["hbm_traffic_bytes_per_device"] / hw.hbm_bw
    coll_s = sum(hc["collective_bytes"].values()) / hw.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dom = max(terms, key=terms.get)
    mf = model_flops(spec.config, sh.kind, sh.seq_len, sh.global_batch)
    ratio = mf / max(hc["flops_per_device"] * chips, 1.0)
    mem = rep["memory"]
    mem_gb = (mem["argument_bytes_per_device"]
              + mem["temp_bytes_per_device"]) / 1e9
    step = max(terms.values())
    return RooflineRow(rep["arch"], rep["shape"], rep["mesh"], compute_s,
                       memory_s, coll_s, dom, ratio, mem_gb, step,
                       compute_s / step if step > 0 else 0.0)


def load_rows(report_dir: str | Path,
              hw: Optional[HardwareConstants] = None) -> List[RooflineRow]:
    rows = []
    for f in sorted(Path(report_dir).glob("*.json")):
        rep = json.loads(f.read_text())
        chips = 512 if rep.get("mesh") == "2x16x16" else 256
        r = analyze_report(rep, chips, hw=hw)
        if r:
            rows.append(r)
    return rows


def print_table(rows: List[RooflineRow], only_mesh: Optional[str] = "16x16"
                ) -> None:
    hdr = (f"{'arch':24s} {'shape':12s} {'mesh':8s} {'compute_s':>10s} "
           f"{'memory_s':>10s} {'collect_s':>10s} {'dominant':>10s} "
           f"{'MF/HLO':>7s} {'mem/dev':>8s} {'RF':>6s}")
    print(hdr)
    for r in rows:
        if only_mesh and r.mesh != only_mesh:
            continue
        print(f"{r.arch:24s} {r.shape:12s} {r.mesh:8s} {r.compute_s:10.4f} "
              f"{r.memory_s:10.4f} {r.collective_s:10.4f} {r.dominant:>10s} "
              f"{r.model_flops_ratio:7.3f} {r.mem_gb_per_device:7.1f}G "
              f"{r.roofline_fraction:6.3f}")


def bench_roofline(report_dir: str = "reports/dryrun_baseline",
                   backend: Optional[str] = None) -> None:
    hw = constants_for(backend)
    rows = load_rows(report_dir, hw=hw)
    if not rows:
        print(f"roofline,,status,no dry-run artifacts in {report_dir} "
              f"(run python -m repro.launch.dryrun first)")
        return
    print(f"roofline,constants,hw,{hw.name}")
    print(f"roofline,constants,peak_flops,{hw.peak_flops:.6g}")
    print(f"roofline,constants,hbm_bw,{hw.hbm_bw:.6g}")
    print(f"roofline,constants,ici_bw,{hw.ici_bw:.6g}")
    for r in rows:
        tag = f"{r.arch}/{r.shape}/{r.mesh}"
        print(f"roofline,{tag},compute_s,{r.compute_s:.6g}")
        print(f"roofline,{tag},memory_s,{r.memory_s:.6g}")
        print(f"roofline,{tag},collective_s,{r.collective_s:.6g}")
        print(f"roofline,{tag},dominant,{r.dominant}")
        print(f"roofline,{tag},roofline_fraction,{r.roofline_fraction:.4f}")
