"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch paper-100m \
        --variant small --steps 100 --batch 8 --seq 128 \
        [--qat babsmax128:int4] [--quantised-opt] [--ckpt-dir runs/x]

Runs on whatever devices exist (1 CPU here; the production mesh path is
exercised by dryrun.py). All the fault-tolerance machinery is live: resume
from latest checkpoint, atomic saves, deterministic data.
"""
from __future__ import annotations

import argparse
import json

import jax

from repro import configs
from repro.data.pipeline import make_batch_fn
from repro.launch.compile_cache import use_compile_cache
from repro.train import AdamConfig, TrainConfig, train
from repro.train.qat import qat_plan_for


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-100m")
    ap.add_argument("--variant", default="small",
                    choices=["full", "small", "smoke"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--qat", default=None,
                    help="format spec for QAT fake-quant (e.g. babsmax128:int4)")
    ap.add_argument("--quantised-opt", action="store_true")
    ap.add_argument("--grad-compression", default=None)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)
    use_compile_cache()

    try:
        cfg = configs.get_config(args.arch, args.variant)
    except AttributeError:
        cfg = configs.get_config(args.arch, "smoke")
        print(f"[train] no '{args.variant}' variant for {args.arch}; "
              f"using smoke")
    tc = TrainConfig(steps=args.steps, lr=args.lr, warmup=args.warmup,
                     log_every=args.log_every, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every, seed=args.seed,
                     grad_compression=args.grad_compression)
    ac = AdamConfig(quantised_state=args.quantised_opt)
    batch_fn = make_batch_fn(cfg, seq=args.seq, batch=args.batch,
                             seed=args.seed)
    qat_plan = None
    if args.qat:
        from repro.models.api import get_family
        params0 = get_family(cfg.family).init(
            jax.random.PRNGKey(args.seed), cfg)
        qat_plan = qat_plan_for(params0, args.qat)
        del params0

    def log(m):
        print(f"step {m['step']:5d}  loss {m['loss']:.4f}  "
              f"gnorm {m['grad_norm']:.3f}  lr {m['lr']:.2e}  "
              f"{m['s_per_step']:.2f}s/step")

    state, history = train(cfg, tc, ac, batch_fn, qat_plan=qat_plan,
                           on_step=log)
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(history, f, indent=1)
    return state, history


if __name__ == "__main__":
    main()
