"""Roofline analysis from the dry-run's compiled artifacts (§g).

Terms (seconds, per device, TPU v5e constants):
  compute    = dot FLOPs / 197e12            (bf16 peak per chip)
  memory     = dot stream bytes / 819e9      (HBM bandwidth)
  collective = collective bytes / (4 links * 50e9)   (ICI, ring model)

All inputs come from the trip-count-aware HLO walker
(repro/launch/hlo_analysis.py; XLA's own cost_analysis counts scan bodies
once). DTYPE CORRECTION: XLA:CPU float-normalizes bf16 to f32, so walker
byte counts for bf16 programs (all model cells) are 2x the TPU values —
corrected by 0.5 here (flops are dtype-independent). The audio-pipeline
cells mix f32 I/O with bf16 DFT streams; they are left uncorrected (upper
bound).

Roofline fraction ("roof%"):
  train/prefill: useful model FLOPs (6*N_active*D or 2*N_active*D) per
                 device vs peak, over the bounding term (perfect overlap).
  decode:        streaming efficiency — the bytes that MUST move per step
                 (weights + caches = argument bytes) over the bounding term.
  pipeline:      reported terms only (the §Perf log carries the iterations).
"""
from __future__ import annotations

import argparse
import glob
import json
import os

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_LINKS = 4
LINK_BW = 50e9
HBM_GB = 16.0
BF16_CORRECTION = 0.5


def fused_tail_record(R, S, window=256, hop=128, hpf=False, hpf_taps=129):
    """A `roofline_terms`-compatible record for the fused survivor tail's
    single kernel pass (kernels/fused_tail): DFT-dot + FIR + MMSE FLOPs
    against the kernel's true HBM traffic (gathered rows in, packed
    filtered spectrum out — the VMEM-resident intermediates move nothing).
    kind="pipeline" so the f32 byte counts skip the bf16 correction."""
    from repro.kernels.fused_tail.kernel import spectrum_lanes, tail_geometry
    _, S_pad, F, _ = tail_geometry(S, window, hop)
    bins = window // 2 + 1
    out = 2 * spectrum_lanes(bins)                # packed [re | im] lanes
    flops = 2 * R * F * window * out              # matmul DFT
    if hpf:
        flops += 2 * R * S * hpf_taps             # FIR tap chain
    flops += R * F * bins * 40                    # MMSE recurrence (approx)
    bytes_ = R * S * 4 + window * out * 4         # rows + basis in
    bytes_ += R * F * out * 4                     # packed spectrum out
    return {"kind": "pipeline", "flops_per_device": flops,
            "bytes_per_device": bytes_, "collective_bytes_per_device": 0,
            "n_devices": 1}


def load_records(pattern):
    recs = []
    for path in sorted(glob.glob(pattern)):
        with open(path) as f:
            recs.extend(json.load(f))
    return recs


def roofline_terms(rec):
    corr = 1.0 if rec.get("kind") == "pipeline" else BF16_CORRECTION
    comp = rec["flops_per_device"] / PEAK_FLOPS
    mem = corr * rec["bytes_per_device"] / HBM_BW
    coll = corr * rec["collective_bytes_per_device"] / (ICI_LINKS * LINK_BW)
    dom = max(("compute", comp), ("memory", mem), ("collective", coll),
              key=lambda kv: kv[1])
    t_bound = max(comp, mem, coll, 1e-12)
    out = {"compute_s": comp, "memory_s": mem, "collective_s": coll,
           "dominant": dom[0], "bound_s": t_bound}
    if rec.get("kind") == "pipeline" or not rec.get("model_flops"):
        out["useful_flops_ratio"] = None
        out["roofline_fraction"] = None
        return out
    model_flops_dev = rec["model_flops"] / rec["n_devices"]
    out["useful_flops_ratio"] = model_flops_dev / max(
        rec["flops_per_device"], 1.0)
    if rec["kind"] == "decode":
        # decode must stream weights+caches every step: efficiency = that
        # minimal traffic time over the bounding time
        need = rec["memory"]["argument_bytes"] / HBM_BW
        out["roofline_fraction"] = min(1.0, need / t_bound)
    else:
        out["roofline_fraction"] = (model_flops_dev / PEAK_FLOPS) / t_bound
    return out


def what_would_move_it(rec, terms):
    d = terms["dominant"]
    if d == "compute":
        if (terms["useful_flops_ratio"] or 1) < 0.5:
            return ("compute-bound with low useful-FLOPs ratio: cut remat "
                    "recompute / causal-attention waste")
        return "compute-bound near useful peak: good placement"
    if d == "memory":
        if rec["kind"] == "decode":
            return ("memory-bound on weight+KV streaming: quantize KV/"
                    "weights or raise batch to amortize weight reads")
        return "memory-bound: fuse elementwise chains, avoid f32 round-trips"
    return ("collective-bound: reshard (zero3/sp_ep profiles) or overlap "
            "(collective-matmul); move the axis with the largest transfer")


def fmt_table(recs, md=False):
    headers = ["arch", "shape", "mesh", "mode", "mb", "peakGB", "compute_s",
               "memory_s", "collective_s", "dominant", "useful%", "roof%"]
    rows = []
    for rec in recs:
        if rec.get("skipped"):
            rows.append([rec["arch"], rec["shape"], _mesh(rec.get("mesh")),
                         "-", "-", "-", "-", "-", "-", "SKIP(brief)", "-",
                         "-"])
            continue
        if rec.get("error"):
            rows.append([rec["arch"], rec["shape"], _mesh(rec.get("mesh")),
                         "-", "-", "-", "-", "-", "-", "ERROR", "-", "-"])
            continue
        t = roofline_terms(rec)
        uf = t["useful_flops_ratio"]
        rf = t["roofline_fraction"]
        rows.append([
            rec["arch"], rec["shape"], _mesh(rec["mesh"]),
            rec.get("mode", "-"), str(rec.get("microbatches") or "-"),
            f"{rec['memory']['peak_estimate_gb']:.1f}",
            f"{t['compute_s']:.2e}", f"{t['memory_s']:.2e}",
            f"{t['collective_s']:.2e}", t["dominant"],
            "-" if uf is None else f"{100 * uf:.0f}",
            "-" if rf is None else f"{100 * rf:.1f}",
        ])
    if md:
        lines = ["| " + " | ".join(headers) + " |",
                 "|" + "|".join("---" for _ in headers) + "|"]
        lines += ["| " + " | ".join(map(str, r)) + " |" for r in rows]
        return "\n".join(lines)
    from benchmarks.util import table
    return table(rows, headers, title="Roofline per (arch x shape x mesh)")


def _mesh(name):
    return {"single_pod_16x16": "1pod", "multi_pod_2x16x16": "2pod"}.get(
        name, name or "-")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pattern", default="results/dryrun_final*.json")
    ap.add_argument("--md", action="store_true")
    ap.add_argument("--mesh", default=None)
    args = ap.parse_args()
    recs = load_records(args.pattern)
    if args.mesh:
        recs = [r for r in recs if r.get("mesh") == args.mesh]
    if not recs:
        print(f"no dry-run records match {args.pattern} — run "
              "`python -m repro.launch.dryrun --all --mesh both --out "
              "results/dryrun_final` first")
        return
    out = fmt_table(recs, md=args.md)
    if args.md:
        print(out)


if __name__ == "__main__":
    main()
