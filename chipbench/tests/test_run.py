"""A whole run rehearsed on the CPU at smoke size, steered past the device
check inside the test only: the last-line contract, and the refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run as bench_run
from chipbench.tests.conftest import ROOT, run_smoke

CELLS = ["internlm2-1.8b.steady", "rwkv6-1.6b.steady"]


@pytest.mark.parametrize("name", CELLS)
def test_whole_run_keeps_the_last_line_contract(name, bench):
    res = run_smoke(name)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 4
    cell = {w["name"]: w for w in bench["workloads"]}[name]
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or name in m["workloads"]}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    assert cell["chips"] == 1
    gap, limit = res["checks"]["widest_gap"]
    assert 0 <= gap <= limit
    assert res["checks"]["compared_tokens"][0] >= 8
    json.dumps(res)


def test_traced_run_reports_per_layer_metrics(tmp_path):
    res = run_smoke(CELLS[0], trace=True, out=tmp_path)
    # off the chip there is no device plane, no peak and no roofline: the
    # counter-read metric is there, the device ones are silent
    assert "prefill_step_share" in res["metrics"]
    assert "dequant_matmul_roofline" not in res["metrics"]
    assert "step_mfu" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_main_refuses_without_tpu(monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert "needs 1 TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_refuses_without_the_program_beside_it(tmp_path):
    """A checkout holding only BENCHMARK.json and chipbench/: past the device
    check, the run finds no program, exits non-zero and prints nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    code = ("import sys, chipbench.run as r; "
            "r.require_devices = lambda n: [object()]; "
            "sys.exit(r.main(['--workload', 'internlm2-1.8b.steady', "
            "'--seed', '1', '--seconds', '1']))")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "program is not beside the benchmark" in p.stderr
