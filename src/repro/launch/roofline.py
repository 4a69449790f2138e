"""Roofline report (§Roofline): three terms per (arch × shape × mesh) from
the dry-run records, dominant-bottleneck identification, and the
MODEL_FLOPS/HLO_FLOPS usefulness ratio.

    PYTHONPATH=src python -m repro.launch.roofline --results results/dryrun \
        [--markdown results/roofline.md]

Terms (seconds per step, PER DEVICE — the dry-run module is the per-device
program, so no further division):

    compute    = dot_flops / PEAK_FLOPS
    memory     = hbm_bytes / HBM_BW          (fused model; raw also shown)
    collective = collective_bytes / LINK_BW

Hardware: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI
(cross-pod 'pod'-axis traffic rides DCN ~25 GB/s; the multi-pod pass is a
shardability proof, the roofline table is single-pod per the assignment).
"""
from __future__ import annotations

import argparse
import glob
import json
import os

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

# Matrix-param counts per arch (total, active) for MODEL_FLOPS = 6·N·D
# (dense) / 6·N_active·D (MoE).  Computed from the configs at import time.


def _matrix_params(cfg):
    """(N_total, N_active) matmul params (embeddings excluded)."""
    d, dff, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    dh = cfg.d_head
    attn = d * (cfg.n_heads * dh) + 2 * d * (cfg.n_kv_heads * dh) \
        + (cfg.n_heads * dh) * d
    if cfg.family in ("dense", "vlm"):
        mlp = 3 * d * dff if cfg.act == "silu" else 2 * d * dff
        n = L * (attn + mlp)
        return n, n
    if cfg.family == "moe":
        mc = cfg.moe
        dff_e = mc.d_ff_expert or dff
        expert = 3 * d * dff_e
        shared = mc.n_shared_experts * expert
        routed_total = mc.n_experts * expert
        routed_active = mc.top_k * expert
        k = cfg.n_dense_layers                     # leading dense blocks
        dense = k * (attn + 3 * d * dff)
        n_tot = dense + (L - k) * (attn + shared + routed_total)
        n_act = dense + (L - k) * (attn + shared + routed_active)
        return n_tot, n_act
    if cfg.family == "hybrid":
        ssm = cfg.ssm
        d_in = ssm.expand * d
        heads = d_in // ssm.head_dim
        mamba = d * d_in * 2 + 2 * d * ssm.n_groups * ssm.d_state \
            + d * heads + d_in * d
        every = cfg.attn_every or (L + 1)
        n_apps = L // every
        shared_attn = 2 * d * (cfg.n_heads * dh) * 2 \
            + 2 * (2 * d) * (cfg.n_kv_heads * dh) + 3 * d * dff
        n = L * mamba + shared_attn  # ONE shared block
        n_act = L * mamba + n_apps * 0  # weights reused; compute ∝ apps
        compute_n = L * mamba + n_apps * shared_attn
        return n, compute_n
    if cfg.family == "ssm":
        d_in = 2 * d
        mlstm = 3 * d * d_in + d * d_in + 2 * d * cfg.n_heads + d_in * d
        slstm = d * 4 * d + 4 * (d // cfg.n_heads) ** 2 * cfg.n_heads + d * d
        every = cfg.slstm_every or (L + 1)
        n_s = L // every
        n = n_s * slstm + (L - n_s) * mlstm
        return n, n
    if cfg.family == "encdec":
        enc = cfg.enc_layers * (attn + 2 * d * dff)
        dec = L * (2 * attn + 2 * d * dff)
        return enc + dec, enc + dec
    raise ValueError(cfg.family)


def model_flops(cfg, shape, devices: int) -> float:
    """Analytic useful flops per device per step."""
    from repro.configs.base import SHAPES_BY_NAME
    n_tot, n_act = _matrix_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens / devices
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens / devices
    # decode: one token per sequence
    return 2.0 * n_act * shape.global_batch / devices


def load_records(results_dir: str):
    recs = []
    for f in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        r = json.load(open(f))
        if r.get("ok"):
            recs.append(r)
    return recs


def terms(rec: dict) -> dict:
    comp = rec["dot_flops"] / PEAK_FLOPS
    memf = rec["hbm_bytes"] / HBM_BW
    memr = rec.get("hbm_bytes_raw", rec["hbm_bytes"]) / HBM_BW
    coll = rec["collectives"]["total_bytes"] / LINK_BW
    dom = max(("compute", comp), ("memory", memf), ("collective", coll),
              key=lambda kv: kv[1])
    return dict(compute_s=comp, memory_s=memf, memory_raw_s=memr,
                collective_s=coll, dominant=dom[0], bound_s=dom[1])


def build_table(results_dir: str, multi_pod: bool = False):
    from repro import configs as C
    from repro.configs.base import SHAPES_BY_NAME
    rows = []
    for rec in load_records(results_dir):
        if rec["multi_pod"] != multi_pod or rec.get("variant"):
            continue
        cfg = C.get_config(rec["arch"])
        shape = SHAPES_BY_NAME[rec["shape"]]
        t = terms(rec)
        mf = model_flops(cfg, shape, rec["devices"])
        t["model_flops"] = mf
        t["useful_ratio"] = mf / max(rec["dot_flops"], 1.0)
        # roofline fraction: useful work at peak vs the bounding term
        t["roofline_frac"] = (mf / PEAK_FLOPS) / max(t["bound_s"], 1e-12)
        rows.append({**rec, **t})
    return rows


def to_markdown(rows) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | bound | "
           "MODEL/HLO flops | roofline frac |\n|---|---|---|---|---|---|---|---|")
    out = [hdr]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac'] * 100:.1f}% |")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default="results/dryrun")
    ap.add_argument("--markdown", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    rows = build_table(args.results, args.multi_pod)
    md = to_markdown(rows)
    print(md)
    if args.markdown:
        with open(args.markdown, "w") as f:
            f.write(md + "\n")
    # flag the three most interesting cells for the perf loop
    if rows:
        worst = min(rows, key=lambda r: r["roofline_frac"])
        collb = max(rows, key=lambda r: r["collective_s"])
        print(f"\nworst roofline: {worst['arch']}/{worst['shape']} "
              f"({worst['roofline_frac'] * 100:.1f}%)")
        print(f"most collective-bound: {collb['arch']}/{collb['shape']} "
              f"({collb['collective_s']:.4f}s)")


if __name__ == "__main__":
    main()
