"""Chip benchmark: serve one cell of ``BENCHMARK.json`` on the accelerator
and print its metrics.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, the program's quantiser and packer, the
compile of every step variant the traffic uses) is ``setup_s``. Then the
cell's traffic runs through ``Scheduler.submit`` and
``ServeEngine.step_once`` for ``--seconds``. With ``--trace 1`` the last
seconds of the window are traced and the per-layer metrics are reported
instead of the end-to-end ones. After the window, a sample of the served
requests is replayed through a plain float32 reference and every served
token's gap below the reference's best logit is held to the
configuration's limit.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``, each compared number beside its limit;
the same numbers close standard error. Without an accelerator, with fewer
chips than the cell asks for, or without the program beside it, the run
exits non-zero and prints no result. It runs in one process and starts
none; JAX's compilation cache lives in ``.jax_cache/`` in the checkout."""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".jax_cache"


def require_devices(chips: int):
    """The device list, after checking that JAX found enough accelerators."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"chipbench: needs {chips} TPU chip(s), JAX found "
                         f"{len(devices)} {devices[0].platform!r} device(s)")
    return devices


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def open_cell(workload: str):
    """The cell and its devices, with the program importable, JAX's
    compilation cache and the TPU runtime's logs in the checkout, and the
    device check passed. Shared by every command of the benchmark."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    from chipbench import harness
    cell = harness.load_cell(bench, workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    logs = ROOT / "chipbench" / "out" / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))
    devices = require_devices(cell.chips)
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        raise SystemExit(f"chipbench: the program is not beside the "
                         f"benchmark ({e})")
    import jax
    use_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cell, devices


def main(argv=None) -> int:
    args = parse(argv)
    cell, devices = open_cell(args.workload)
    from chipbench import harness
    out = ROOT / "chipbench" / "out" / f"{args.workload}.{args.seed}"
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, devices, out)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
