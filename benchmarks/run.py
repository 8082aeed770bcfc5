"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV; each module also self-checks its
figure's paper claim and writes rows to results/bench/*.json.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig4,...]
"""
from __future__ import annotations

import argparse
import sys
import time

from . import (fig1_llm_tradeoff, fig4_error_size, fig5_bits_histogram,
               fig6_allocation, fig11_fisher_kl, fig12_fisher_structure,
               fig18_formats, fig19_fp_formats, fig21_block_size,
               fig22_alpha_rule, fig23_search, fig24_huffman,
               fig28_compression_scaling, fig29_rotations, fig34_signmax,
               table1_headline)

MODULES = {
    "fig4": fig4_error_size,
    "fig18": fig18_formats,
    "fig19": fig19_fp_formats,
    "fig21": fig21_block_size,
    "fig22": fig22_alpha_rule,
    "fig23": fig23_search,
    "fig24": fig24_huffman,
    "fig28": fig28_compression_scaling,
    "fig29": fig29_rotations,
    "fig34": fig34_signmax,
    "fig1": fig1_llm_tradeoff,
    "fig5": fig5_bits_histogram,
    "fig6": fig6_allocation,
    "fig11": fig11_fisher_kl,
    "fig12": fig12_fisher_structure,
    "table1": table1_headline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sample counts (slow on CPU)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    names = list(MODULES) if not args.only else args.only.split(",")

    print("name,us_per_call,derived")
    all_fails = []
    for name in names:
        mod = MODULES[name]
        t0 = time.perf_counter()
        try:
            rows = mod.run(fast=not args.full)
            dt_us = (time.perf_counter() - t0) * 1e6
            fails = mod.check(rows) if hasattr(mod, "check") else []
            derived = "PASS" if not fails else f"FAIL:{';'.join(fails)[:120]}"
            print(f"{name},{dt_us:.0f},{derived} (n_rows={len(rows)})")
            all_fails.extend(f"{name}: {f}" for f in fails)
        except Exception as e:  # pragma: no cover
            dt_us = (time.perf_counter() - t0) * 1e6
            print(f"{name},{dt_us:.0f},ERROR:{type(e).__name__}:{e}")
            all_fails.append(f"{name}: {type(e).__name__}: {e}")
    if all_fails:
        print("\nFAILURES:", file=sys.stderr)
        for f in all_fails:
            print("  " + f, file=sys.stderr)
        sys.exit(1)
    print("\nall benchmark claims PASS")


if __name__ == "__main__":
    main()
