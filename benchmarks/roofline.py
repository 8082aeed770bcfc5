"""Roofline analysis (deliverable g): per (arch × shape × mesh) derive the
three roofline terms from the compiled dry-run artifacts:

    compute    = HLO_dot_FLOPs/dev ÷ 197 TFLOP/s (bf16, TPU v5e)
    memory     = HLO_bytes/dev     ÷ 819 GB/s HBM
    collective = coll_bytes/dev    ÷ 50 GB/s/link ICI

plus MODEL_FLOPS/HLO_FLOPs (useful-compute fraction; catches remat and
dispatch waste) and the dominant bottleneck. Reads results/dryrun/*.json
(produced by repro.launch.dryrun); writes a markdown table + json.

The **dequant section** extends the model to the packed serving hot path:
for each decode matmul shape of the serve bench (the paper-100m full
config's five projections, per batch size), it renders the dequant terms
from the kernel's own tuning model (``kernels.dequant_matmul.tune``) —
packed code bytes, dequant VPU ops and time for the tile shape + strategy
``choose_tiles`` actually picks — next to the dense-weight stream those
bytes replace. Tile/strategy choices are thereby guided by the same
analytic terms this table makes inspectable, not guessed: if a choice
looks wrong here, ``tune.register`` overrides it per geometry.
"""
from __future__ import annotations

import glob
import json
import os

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # bytes/s / chip
ICI_BW = 50e9            # bytes/s/link

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN_OUT", "results/dryrun")


def load_cells(mesh_tag: str = "pod256"):
    cells = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, mesh_tag,
                                              "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def terms(cell: dict) -> dict:
    n_dev = cell["n_devices"]
    t_comp = cell["hlo_dot_flops_per_device"] / PEAK_FLOPS
    # memory term: dot-level traffic (TPU-realistic — matmul operands and
    # results stream HBM⇄VMEM; elementwise fuses); the fusion-level figure
    # from the CPU backend is kept as an upper bound.
    t_mem = cell.get("hlo_dot_bytes_per_device",
                     cell["hlo_bytes_per_device"]) / HBM_BW
    t_mem_upper = cell["hlo_bytes_per_device"] / HBM_BW
    t_coll = cell["collective_bytes_per_device"].get("total", 0.0) / ICI_BW
    dominant = max((("compute", t_comp), ("memory", t_mem),
                    ("collective", t_coll)), key=lambda kv: kv[1])[0]
    total_hlo_flops = cell["hlo_dot_flops_per_device"] * n_dev
    useful = (cell["model_flops_total"] / total_hlo_flops
              if total_hlo_flops else float("nan"))
    # roofline fraction: useful FLOPs vs what the dominant term's time
    # would allow at peak compute
    t_bound = max(t_comp, t_mem, t_coll)
    step_flops_at_peak = t_bound * PEAK_FLOPS * n_dev
    frac = (cell["model_flops_total"] / step_flops_at_peak
            if step_flops_at_peak else float("nan"))
    return dict(
        arch=cell["arch"], shape=cell["shape"],
        t_compute_s=t_comp, t_memory_s=t_mem, t_memory_upper_s=t_mem_upper,
        t_collective_s=t_coll,
        dominant=dominant, useful_flops_ratio=useful,
        roofline_fraction=frac,
        mem_per_dev_gib=(cell["memory"]["argument_bytes"]
                         + cell["memory"]["temp_bytes"]) / 2**30,
    )


def run(fast: bool = True, mesh_tag: str = "pod256"):
    rows = []
    for cell in load_cells(mesh_tag):
        if cell.get("status") == "ok":
            rows.append(terms(cell))
        elif cell.get("status") == "skipped":
            rows.append(dict(arch=cell["arch"], shape=cell["shape"],
                             dominant="skipped",
                             note=cell["reason"][:60]))
    os.makedirs("results/bench", exist_ok=True)
    with open(f"results/bench/roofline_{mesh_tag}.json", "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def markdown_table(rows) -> str:
    hdr = ("| arch | shape | compute s | memory s (upper) | collective s | "
           "dominant | useful/HLO | roofline frac | mem GiB/dev |\n"
           "|---|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        if r["dominant"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                         f"skipped | — | — | — |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3g} | "
            f"{r['t_memory_s']:.3g} ({r.get('t_memory_upper_s', 0):.3g}) | "
            f"{r['t_collective_s']:.3g} | "
            f"**{r['dominant']}** | {r['useful_flops_ratio']:.2f} | "
            f"{r['roofline_fraction']:.2f} | {r['mem_per_dev_gib']:.1f} |")
    return "\n".join(lines)


def check(rows):
    ok = [r for r in rows if r["dominant"] != "skipped"]
    fails = []
    if len(ok) < 30:
        fails.append(f"roofline: only {len(ok)} ok cells")
    return fails


# ------------------------------------------------------------------ dequant

def serve_shapes(batches=(1, 2, 4, 8)):
    """The serve bench's decode matmul shapes: (tag, M, K, N) for every
    projection of the paper-100m full config, per swept batch size (M =
    batch slots at decode — one valid token per slot)."""
    from repro import configs
    cfg = configs.get_config("paper-100m", "full")
    d, ff, v = cfg.d_model, cfg.d_ff, cfg.vocab
    kv = cfg.n_kv_heads * cfg.head_dim
    projs = [("wq", d, cfg.n_heads * cfg.head_dim), ("wk", d, kv),
             ("wv", d, kv), ("wo", cfg.n_heads * cfg.head_dim, d),
             ("w_gate", d, ff), ("w_up", d, ff), ("w_down", ff, d),
             ("unembed", d, v)]
    return [(f"{tag}/b{M}", M, K, N)
            for M in batches for tag, K, N in projs]


def dequant_rows(batches=(1, 2, 4, 8), bits=4, n_codes=16, block=64):
    """Dequant roofline per serve-bench shape: the tuning table's chosen
    tiles/strategy with its own cost terms, against the dense f32 stream.
    ``block=64`` matches the serve bench's ``babsmax64:n4`` format."""
    from repro.kernels.dequant_matmul import tune
    rows = []
    for tag, M, K, N in serve_shapes(batches):
        c = tune.choose_tiles(M, K, N, bits, n_codes=n_codes, block=block)
        est = tune.estimate(M, K, N, bits, c.tm, c.tk, c.tn, n_codes,
                            c.decode, block)
        dense_bytes = 4 * K * N          # the f32 master stream replaced
        rows.append(dict(
            shape=tag, M=M, K=K, N=N, bits=bits,
            tiles=f"{c.tm}x{c.tk}x{c.tn}",
            strategy="decode" if c.decode else "lut",
            code_bytes=est["code_bytes"],
            dequant_ops=est["dequant_ops"],
            dequant_time_s=est["dequant_time"],
            time_s=est["time"],
            dense_bytes=dense_bytes,
            stream_cut=round(dense_bytes / est["code_bytes"], 2),
        ))
    return rows


def dequant_markdown(rows) -> str:
    hdr = ("| shape | M×K×N | tiles | strategy | code bytes | dequant s | "
           "total s | stream cut |\n|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['shape']} | {r['M']}×{r['K']}×{r['N']} | {r['tiles']} | "
            f"{r['strategy']} | {r['code_bytes']} | "
            f"{r['dequant_time_s']:.3g} | {r['time_s']:.3g} | "
            f"{r['stream_cut']}× |")
    return "\n".join(lines)


def run_dequant():
    rows = dequant_rows()
    os.makedirs("results/bench", exist_ok=True)
    with open("results/bench/roofline_dequant.json", "w") as f:
        json.dump(rows, f, indent=1)
    return rows


# --------------------------------------------------------------- decode attn

def attn_rows(batches=(1, 2, 4, 8), kv_len=64):
    """Decode-attention HBM roofline per serve-bench shape: the cache
    bytes one decode step streams (every live KV row of every layer) under
    each storage format — dense f32/bf16 vs the block-scaled q8/q4 code +
    scale stream the flash-decode kernel reads instead. Attention FLOPs
    are format-independent (2·QK^T + 2·PV per head), so at decode's tiny
    arithmetic intensity the byte cut IS the predicted speedup; ``t_hbm_s``
    renders each stream at the HBM bandwidth for the roofline table."""
    from repro import configs
    from repro.serve.cache import kv_bits
    cfg = configs.get_config("paper-100m", "full")
    L, K, H, hd = cfg.n_layers, cfg.n_kv_heads, cfg.n_heads, cfg.hd
    rows = []
    for B in batches:
        rows_live = L * B * kv_len * K        # (token, head) KV rows read
        flops = 2 * L * B * H * kv_len * hd * 2   # QK^T + PV, per step
        for fmt in ("f32", "bf16", "q8", "q4"):
            if fmt in ("f32", "bf16"):
                row_bytes = hd * (4 if fmt == "f32" else 2)
            else:
                bits = kv_bits(fmt)
                row_bytes = hd * bits // 8 + 4    # codes + one f32 scale
            hbm = 2 * rows_live * row_bytes       # k and v streams
            rows.append(dict(
                shape=f"decode/b{B}", batch=B, kv_len=kv_len, fmt=fmt,
                kv_rows=rows_live, row_bytes=row_bytes, hbm_bytes=hbm,
                attn_flops=flops,
                t_hbm_s=hbm / HBM_BW,
                intensity_flops_per_byte=round(flops / hbm, 3)))
    # per batch, the cut each quantised stream delivers vs the f32 cache
    by = {(r["batch"], r["fmt"]): r for r in rows}
    for r in rows:
        base = by[(r["batch"], "f32")]["hbm_bytes"]
        r["stream_cut_vs_f32"] = round(base / r["hbm_bytes"], 2)
    return rows


def attn_markdown(rows) -> str:
    hdr = ("| shape | fmt | KV rows | bytes/row | HBM bytes | t_hbm | "
           "FLOPs/byte | cut vs f32 |\n|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['shape']} | {r['fmt']} | {r['kv_rows']} | "
            f"{r['row_bytes']} | {r['hbm_bytes']:,} | "
            f"{r['t_hbm_s']:.3g} | {r['intensity_flops_per_byte']} | "
            f"{r['stream_cut_vs_f32']}× |")
    return "\n".join(lines)


def run_attn():
    rows = attn_rows()
    os.makedirs("results/bench", exist_ok=True)
    with open("results/bench/roofline_attn.json", "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dequant", action="store_true",
                    help="print only the packed-serving dequant table")
    ap.add_argument("--attn", action="store_true",
                    help="print only the decode-attention HBM table "
                         "(quantised vs dense KV streams per serve shape; "
                         "written to results/bench/roofline_attn.json)")
    args = ap.parse_args()
    if args.attn:
        print(attn_markdown(run_attn()))
    else:
        if not args.dequant:
            print(markdown_table(run()))
        print(dequant_markdown(run_dequant()))
