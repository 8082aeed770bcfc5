"""Chip smoke test: serve paper-100m from packed 4-bit weights on one TPU.

    python3 chip_smoke.py [--seed N]

Drives the serving path once, through the entry points a user calls, at
the full width of ``paper-100m`` (12 layers, d 768, 12/4 heads, head dim
64, d_ff 2048, vocab 32768) with tied embeddings:

* weights are initialised from ``--seed``, quantised ``babsmax64:n4`` and
  served packed through ``ServeEngine.from_quantised`` with a ``q8`` KV
  cache — one engine runs all three Pallas kernels (``dequant_matmul`` for
  the projections, ``dequant_matmul_t`` for the tied unembed,
  ``decode_attention`` for the quantised cache) and ``block_quant`` for
  the cache writes;
* the compiled step programs must contain those kernels as TPU custom
  calls (nothing falls back to the jnp oracles);
* 8 requests (prompts of 64–512 tokens drawn from the seed, 32 new tokens
  each) are served on 8 slots with ``kv_len=2048``, with the dense
  fallback off: all must finish, none quarantined or truncated;
* the served prefill logits are compared with a plain f32 reference
  forward of the same dequantised weights under
  ``jax.default_matmul_precision("highest")``: mean top-128 KL must stay
  under ``KL_BOUND``.

Set-up and smoke timings are printed on earlier lines; they are not
benchmarks. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``. Without a TPU, or without the rest of
the repository beside it, the script exits non-zero and prints no result.
It runs in one process and starts none."""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

FORMAT = "babsmax64:n4"
KV_FORMAT = "q8"
SLOTS, KV_LEN, CHUNK = 8, 2048, 8     # CHUNK: the launcher's prefill chunk
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, (64, 512), 32
KERNELS = ("dequant_matmul", "dequant_matmul_t", "decode_attention")
# Served logits differ from the f32 reference only by the bf16 serving
# dtype and the q8 cache (the weights are the same dequantised values):
# 3.6e-7 on a v5e at seed 0. Random weights give flat logits, so even
# unrelated logits (each position against another, the "null" KL printed
# beside it) differ by only ~1e-3: the bound sits between the two, 27x
# above the measurement and 100x below the null.
KL_BOUND = 1e-5


def require_tpu():
    """The device list, after checking that JAX found a TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devices[0].platform!r}")
    return devices


def model_config(variant: str = "full"):
    from repro import configs
    return configs.get_config("paper-100m", variant).replace(
        tie_embeddings=True, kv_format=KV_FORMAT)


def make_requests(cfg, seed: int, n: int, prompt_len, max_new: int):
    import numpy as np
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_len[0], prompt_len[1] + 1, size=n)
    return [Request(prompt=rng.integers(0, cfg.vocab, int(k)).tolist(),
                    max_new_tokens=max_new, rid=i)
            for i, k in enumerate(lens)]


def _step_batches(B: int, chunk: int):
    """The step variants a wave compiles: prefill with the admission reset,
    prefill, decode."""
    import jax.numpy as jnp
    prefill = {"tokens": jnp.zeros((B, chunk), jnp.int32),
               "t_valid": jnp.full((B,), chunk, jnp.int32)}
    decode = {"tokens": jnp.zeros((B, 1), jnp.int32),
              "t_valid": jnp.ones((B,), jnp.int32)}
    return {"prefill+reset": {**prefill, "reset": jnp.ones((B,), bool)},
            "prefill": prefill, "decode": decode}


def run(cfg, seed: int = 0, *, slots: int = SLOTS, kv_len: int = KV_LEN,
        chunk: int = CHUNK, n_requests: int = N_REQUESTS,
        prompt_len=PROMPT_LEN, max_new: int = MAX_NEW) -> dict:
    """Build the packed engine, compile its step variants, serve the
    requests and compare the served prefill logits with the f32
    reference. Returns the measurements :func:`failures` judges."""
    import jax
    import numpy as np
    from repro.core import build_plan
    from repro.core.metrics import mean_topk_kl
    from repro.kernels.ops import tpu_kernel_calls
    from repro.models.api import get_family
    from repro.serve.engine import ServeEngine

    out = {"platform": jax.default_backend()}
    t0 = time.perf_counter()
    fam = get_family(cfg.family)
    params = fam.init(jax.random.PRNGKey(seed), cfg)
    plan = build_plan(params, FORMAT)
    qparams = jax.block_until_ready(jax.jit(plan.quantise)(params))
    del params
    eng = ServeEngine.from_quantised(
        cfg, qparams, plan, batch_slots=slots, kv_len=kv_len,
        prefill_chunk=chunk, dense_fallback=False)
    out["setup_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    out["kernels"] = {}
    for tag, batch in _step_batches(slots, chunk).items():
        compiled = eng._step.lower(eng.params, eng._state, batch).compile()
        out["kernels"][tag] = dict(tpu_kernel_calls(compiled.as_text()))
    out["compile_s"] = time.perf_counter() - t0

    # the served prefill logits: every request is seated in the first wave
    # (slot i = request i) and prompts are at least prompt_len[0] long, so
    # the first prompt_len[0] // chunk steps are full prefill chunks
    n_chunks = prompt_len[0] // chunk
    captured = []
    step = eng._step

    def capture(p, s, b):
        logits, state = step(p, s, b)
        if len(captured) < n_chunks:
            captured.append(logits)
        return logits, state

    eng._step = capture
    requests = make_requests(cfg, seed, n_requests, prompt_len, max_new)
    for r in requests:
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run(max_steps=10_000)
    out["serve_s"] = time.perf_counter() - t0
    out["steps"] = eng.steps_total
    out["requests"] = len(requests)
    out["done"] = sum(g.done for g in done)
    out["failed"] = [f"rid={g.rid}: {g.fail_reason}" for g in done
                     if g.failed]
    out["truncated"] = sum(g.truncated for g in done)
    out["tokens"] = sum(len(g.tokens) for g in done)
    out["degraded"] = eng.degraded

    L = n_chunks * chunk
    served = np.concatenate([np.asarray(x) for x in captured], axis=1)
    tokens = np.asarray([r.prompt[:L] for r in requests], np.int32)
    dense = plan.unpack(eng.params)
    ref_cfg = cfg.replace(dtype="float32", kv_format="", remat="none")
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, t: fam.apply(
            p, {"tokens": t}, ref_cfg))(dense, tokens))
    served = served[:len(requests)]
    out["positions"] = int(ref.shape[0] * ref.shape[1])
    out["kl"] = float(mean_topk_kl(ref, served))
    # the KL a wrong read would give: each position against a different one
    out["kl_null"] = float(mean_topk_kl(ref, np.roll(ref, 1, axis=1)))
    out["greedy_agreement"] = float(
        (ref.argmax(-1) == served.argmax(-1)).mean())
    return out


def failures(res: dict) -> list:
    """What the smoke contract forbids, as messages (empty = pass). Off
    the TPU (the CPU rehearsal) the oracles serve, so no kernel is
    expected in the compiled steps."""
    bad = []
    if res["done"] != res["requests"]:
        bad.append(f"{res['done']}/{res['requests']} requests finished")
    if res["failed"]:
        bad.append(f"quarantined: {res['failed']}")
    if res["truncated"]:
        bad.append(f"{res['truncated']} truncated")
    if res["degraded"]:
        bad.append("engine degraded to dense weights")
    if res["platform"] == "tpu":
        for tag, calls in res["kernels"].items():
            missing = [k for k in KERNELS if not calls.get(k)]
            if missing:
                bad.append(f"{tag} step lacks kernels {missing}: {calls}")
    if not res["kl"] <= KL_BOUND:
        bad.append(f"top-k KL {res['kl']:.3e} above the bound {KL_BOUND}")
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu()
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    dev = devices[0]
    print(f"[chip_smoke] device {dev.device_kind} x{len(devices)} "
          f"({dev.platform}); compile cache {cache}")
    res = run(model_config(), args.seed)
    print(f"[chip_smoke] set-up: init + quantise + pack {res['setup_s']:.3f}"
          f" s; compile of {len(res['kernels'])} step variants "
          f"{res['compile_s']:.3f} s")
    for tag, calls in res["kernels"].items():
        print(f"[chip_smoke] kernels in the {tag} step: "
              + ", ".join(f"{k}={v}" for k, v in sorted(calls.items())))
    print(f"[chip_smoke] smoke (not a benchmark): {res['done']}/"
          f"{res['requests']} requests, {res['tokens']} tokens, "
          f"{res['steps']} engine steps in {res['serve_s']:.3f} s")
    print(f"[chip_smoke] quality over {res['positions']} prefill positions:"
          f" mean top-128 KL {res['kl']:.3e} (bound {KL_BOUND:g}; null "
          f"{res['kl_null']:.3e}), greedy agreement "
          f"{res['greedy_agreement']:.4f}")
    bad = failures(res)
    if bad:
        for b in bad:
            print(f"[chip_smoke] FAIL: {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
