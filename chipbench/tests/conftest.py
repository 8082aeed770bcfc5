"""Shared fixtures of the benchmark's CPU tests: cells of
``BENCHMARK.json`` shrunk to smoke size (the published widths are for the
chip; here only the path is exercised)."""
import copy
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SMOKE = {
    "transformer": dict(n_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
                        head_dim=32, d_ff=128, vocab=256),
    "rwkv6": dict(n_layers=2, d_model=128, n_heads=2, n_kv_heads=2,
                  d_ff=256, vocab=256),
}


# The widest-gap limits at smoke size, comparing every finished request,
# from CPU readings on seeds 1-6: the program's widest gap reads at most
# 0.0260 (transformer) and 0.0344 (rwkv6), the float8 control's at least
# 0.413 and 0.489.
SMOKE_LIMIT = {"transformer": 0.1, "rwkv6": 0.12}


def smoke_cell(name: str):
    """The named cell with its model at smoke size, 4 slots, kv_len 64,
    prefill chunk 4, short requests, and every finished request compared."""
    from chipbench import harness
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(bench, name)
    cell.config = copy.deepcopy(cell.config)
    cell.config["model"].update(SMOKE[cell.config["family"]])
    cell.config["serving"].update(kv_len=64, prefill_chunk=4)
    cell.config["correct"]["widest_gap_limit"] = SMOKE_LIMIT[
        cell.config["family"]]
    cell.mix = dict(cell.mix, slots=4, waiting=4, block=8, check_requests=64,
                    prompt=dict(cell.mix["prompt"], lo=8, hi=24),
                    output=dict(cell.mix["output"], lo=8, hi=24))
    return cell


@pytest.fixture
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def run_smoke(name: str, seed: int = 2 ** 31 + 3, seconds: float = 1.5,
              trace: bool = False, out=None):
    """One whole run of the smoke-size cell on the CPU, past the device
    check."""
    import time
    import jax
    from chipbench import harness
    return harness.run(smoke_cell(name), seed, seconds, trace,
                       time.monotonic(), jax.devices(), out)
