"""Serving driver: load a (optionally quantised) checkpoint and serve
batched requests with the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch paper-100m \
        --variant small [--quantise babsmax128:int4] --requests 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.core import build_plan
from repro.launch.compile_cache import use_compile_cache
from repro.models.api import get_family
from repro.serve.engine import Request, ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-100m")
    ap.add_argument("--variant", default="small")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir (step_XXXX); random init if absent")
    ap.add_argument("--quantise", default=None,
                    help="serve with weights quantised to this format spec")
    ap.add_argument("--packed", action="store_true",
                    help="with --quantise: keep weights packed (codes — two "
                         "per byte for ≤16-point codebooks — + block scales) "
                         "and serve through dequant_matmul instead of "
                         "materialising dense fake-quant weights")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="batched chunked-prefill width (every family runs "
                         "the ragged path: per-slot positions + in-step "
                         "slot reset; rwkv6/zamba2 stream prompt chunks "
                         "through their block-parallel wkv/ssd forms)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--kv-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--relaxed-admission", action="store_true",
                    help="admit requests whose prompt + max_new exceeds "
                         "--kv-len and flag the truncated generations, "
                         "instead of rejecting them at submit (the budget "
                         "is the global-layer cache length; windowed ring "
                         "groups never overflow)")
    ap.add_argument("--uniform-cache", action="store_true",
                    help="disable the rolling-window ring allocation for "
                         "local-attention layer groups and serve from the "
                         "masked full-length baseline layout")
    ap.add_argument("--kv-format", default=None,
                    choices=["f32", "q8", "q4", "auto"],
                    help="KV-cache storage format: f32 (dense, the default "
                         "and bit-exact kill-switch), q8/q4 (block-scaled "
                         "codes + per-(token,head) f32 scales, dequantised "
                         "in VMEM by the fused flash-decode kernel), or "
                         "auto (per-group Fisher allocation under "
                         "--kv-budget-bytes)")
    ap.add_argument("--kv-budget-bytes", type=int, default=None,
                    help="with --kv-format auto: resident KV cache byte "
                         "budget the Fisher allocator demotes formats "
                         "(f32 -> q8 -> q4, least-sensitive group first) "
                         "to meet")
    ap.add_argument("--no-validate", action="store_true",
                    help="with --packed: skip the load-time integrity pass "
                         "over the packed checkpoint (trusted-checkpoint "
                         "escape hatch; by default corruption raises "
                         "IntegrityError naming the tensor)")
    ap.add_argument("--step-retries", type=int, default=1,
                    help="re-run a transiently failing device step up to "
                         "this many total attempts before degrading "
                         "(1 = no retry)")
    ap.add_argument("--no-dense-fallback", action="store_true",
                    help="let a persistent device-step failure propagate "
                         "instead of dequantising packed weights and "
                         "continuing in degraded mode")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="wall-clock watchdog for the whole run(): on "
                         "expiry, return resumable partial generations "
                         "instead of hanging on a stalled engine")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = configs.get_config(args.arch, args.variant)
    fam = get_family(cfg.family)
    if args.ckpt:
        from repro.train.checkpoint import restore_checkpoint
        state, _ = restore_checkpoint(args.ckpt)
        params = state["params"]
        params = jax.tree.map(jax.numpy.asarray, params)
    else:
        params = fam.init(jax.random.PRNGKey(0), cfg)

    if args.kv_format == "auto":
        cfg = cfg.replace(kv_format=_auto_kv_format(cfg, fam, params, args))
    elif args.kv_format and args.kv_format != "f32":
        cfg = cfg.replace(kv_format=args.kv_format)

    if args.quantise:
        plan = build_plan(params, args.quantise)
        bits = plan.bits_per_param(params)
        if args.packed:
            # fails fast (ValueError naming the family) when the family
            # declares an empty pack layout
            eng = ServeEngine.from_quantised(
                cfg, plan.quantise(params), plan, batch_slots=args.slots,
                kv_len=args.kv_len, prefill_chunk=args.prefill_chunk,
                strict_admission=not args.relaxed_admission,
                windowed_cache=not args.uniform_cache,
                validate=not args.no_validate,
                step_retries=args.step_retries,
                dense_fallback=not args.no_dense_fallback)
            wb = eng.weight_bytes()
            if wb["packed"] == 0:
                # the family has layouts but the format rejected every
                # tensor (QuantisationPlan.packable: block-scaled ≤256-code
                # codebooks, no sparse outliers, output tiling by the block)
                raise SystemExit(
                    f"[serve] --packed: no tensor of {cfg.family!r} packs "
                    f"under format {args.quantise!r} — use a block-scaled "
                    "codebook format, or drop --packed to serve dense")
            print(f"[serve] packed {args.quantise} ({bits:.2f} bits/param): "
                  f"{wb['packed']:,} packed ({wb['codes']:,} codes + "
                  f"{wb['scales']:,} scales + {wb['codebooks']:,} codebooks)"
                  f" + {wb['dense']:,} dense bytes resident")
        else:
            params = plan.fake_quant(params)
            print(f"[serve] weights quantised to {args.quantise} "
                  f"({bits:.2f} bits/param)")
            eng = None
    else:
        eng = None
    if eng is None:
        eng = ServeEngine(cfg, params, batch_slots=args.slots,
                          kv_len=args.kv_len,
                          prefill_chunk=args.prefill_chunk,
                          strict_admission=not args.relaxed_admission,
                          windowed_cache=not args.uniform_cache,
                          step_retries=args.step_retries,
                          dense_fallback=not args.no_dense_fallback)
    cb = eng.cache_bytes()
    if cb["kv"] < cb["uniform_kv"]:
        print(f"[serve] decode cache {cb['kv']:,} bytes "
              f"({cb['cache_ratio_vs_uniform']}x the uniform "
              f"{cb['uniform_kv']:,}: windowed layer groups serve from "
              "ring buffers)")
    else:
        print(f"[serve] decode cache {cb['total']:,} bytes resident")
    if eng.cfg.kv_format:
        print(f"[serve] quantised KV ({eng.cfg.kv_format}): "
              f"{cb['kv']:,} bytes ({cb['code_bytes']:,} codes + "
              f"{cb['scale_bytes']:,} scales), "
              f"{cb['cache_ratio_vs_dense']}x the dense "
              f"{cb['dense_kv']:,}")
        for i, g in enumerate(cb["cache_groups"]):
            print(f"[serve]   group {i} [{g['format']}] "
                  f"{g['n_layers']} layer(s) x {g['length']} slots: "
                  f"{g['bytes']:,} bytes ({g['ratio_vs_dense']}x dense)")
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=4).tolist()
        eng.submit(Request(prompt=prompt, max_new_tokens=args.max_new,
                           rid=rid))
    t0 = time.time()
    done = eng.run(deadline_s=args.deadline_s)
    dt = time.time() - t0
    n_tok = sum(len(g.tokens) for g in done)
    n_trunc = sum(g.truncated for g in done)
    n_failed = sum(g.failed for g in done)
    print(f"[serve] {len(done)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / max(dt, 1e-9):.1f} tok/s)"
          + (f", {n_trunc} truncated at the KV budget" if n_trunc else "")
          + (f", {n_failed} quarantined" if n_failed else ""))
    for g in done[:4]:
        print(f"  rid={g.rid} tokens={g.tokens}"
              + (f" FAILED: {g.fail_reason}" if g.failed else ""))
    _exit_if_degraded(eng)
    return done


def _exit_if_degraded(eng):
    """A run that fell back to dense weights did not serve what was asked
    for: exit non-zero, naming the error that caused the fallback."""
    if eng.degraded:
        raise SystemExit(f"[serve] engine degraded to dense weights: "
                         f"{eng.degrade_reason}")


def _auto_kv_format(cfg, fam, params, args) -> str:
    """--kv-format auto: estimate per-cache-group Fisher sensitivity on a
    short dense decode, then demote formats (f32 -> q8 -> q4, least
    sensitive first) until the serving-geometry cache fits
    --kv-budget-bytes. Returns the explicit comma-separated format list
    the config carries from here on."""
    from repro.core.allocation import allocate_kv_formats, kv_format_bytes
    from repro.core.fisher import estimate_kv_fisher
    if args.kv_budget_bytes is None:
        raise SystemExit("[serve] --kv-format auto needs --kv-budget-bytes")
    if fam.cache_spec is None:
        raise SystemExit(f"[serve] --kv-format auto: family {cfg.family!r} "
                         "declares no cache geometry")
    stats = estimate_kv_fisher(cfg, params, batch_size=2,
                               kv_len=min(args.kv_len, 32))
    # rescale calibration numels to the serving geometry (same groups,
    # serving batch/kv_len): budget what will actually be resident
    spec = fam.cache_spec(cfg, args.slots, args.kv_len,
                          slack=args.prefill_chunk,
                          windowed=not args.uniform_cache)
    for g in spec.groups:
        stats[f"g{g.index}"]["numel"] = (
            2 * len(g.layers) * args.slots * g.length * spec.kv_heads *
            spec.head_dim)
    alloc = allocate_kv_formats(stats, args.kv_budget_bytes, cfg.hd)
    fmts = [alloc[f"g{g.index}"] for g in spec.groups]
    total = sum(stats[f"g{g.index}"]["numel"] *
                kv_format_bytes(alloc[f"g{g.index}"], cfg.hd)
                for g in spec.groups)
    print(f"[serve] kv auto allocation under {args.kv_budget_bytes:,} B: "
          f"{','.join(fmts)} (~{total:,.0f} B resident KV)")
    return ",".join(fmts)


if __name__ == "__main__":
    main()
