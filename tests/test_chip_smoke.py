"""CPU rehearsal of ``chip_smoke.py``: its serving and comparison body at
paper-100m ``smoke()`` size on the jnp oracle path, its refusal to run
without a TPU, and the no-hidden-fallback contract — a kernel failure in a
packed engine built with ``dense_fallback=False`` propagates, and the serve
launcher exits non-zero naming the error when an engine degrades."""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

from repro.core import build_plan
from repro.kernels import ops
from repro.serve.engine import Request, ServeEngine

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load_chip_smoke()


def test_smoke_body_at_smoke_size(cs):
    res = cs.run(cs.model_config("smoke"), 0, slots=4, kv_len=64, chunk=4,
                 n_requests=4, prompt_len=(8, 24), max_new=4)
    assert res["platform"] == "cpu"
    assert cs.failures(res) == []
    assert res["done"] == res["requests"] == 4
    assert res["tokens"] == 16 and res["positions"] == 4 * 8
    # the bound separates a faithful path from unrelated logits
    assert res["kl"] <= cs.KL_BOUND < res["kl_null"]


def test_failures_flag_each_contract_breach(cs):
    ok = dict(platform="tpu", done=8, requests=8, failed=[], truncated=0,
              degraded=False, kl=0.0,
              kernels={"decode": {k: 1 for k in cs.KERNELS}})
    assert cs.failures(ok) == []
    for bad in (dict(done=7), dict(failed=["rid=3: non-finite"]),
                dict(truncated=1), dict(degraded=True),
                dict(kl=2 * cs.KL_BOUND), dict(kl=float("nan")),
                dict(kernels={"decode": {"dequant_matmul": 7}})):
        assert len(cs.failures({**ok, **bad})) == 1, bad


def test_main_refuses_without_tpu(cs, capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert e.value.code not in (0, None)
    assert "needs a TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""


def _packed_engine(dense_fallback):
    from repro import configs
    from repro.models.api import get_family
    cfg = configs.get_config("paper-100m", "smoke")
    params = get_family(cfg.family).init(jax.random.PRNGKey(0), cfg)
    plan = build_plan(params, "babsmax64:n4")
    return ServeEngine.from_quantised(
        cfg, plan.quantise(params), plan, batch_slots=2, kv_len=32,
        prefill_chunk=4, dense_fallback=dense_fallback)


def _kernel_refused(*a, **k):
    # what Mosaic raises for a lowering it does not support
    raise NotImplementedError("Only 2D gather is supported")


def test_kernel_failure_propagates_without_dense_fallback(monkeypatch):
    eng = _packed_engine(dense_fallback=False)
    monkeypatch.setattr(ops, "dequant_matmul", _kernel_refused)
    eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
    with pytest.raises(NotImplementedError, match="2D gather"):
        eng.run()
    assert not eng.degraded


def test_serve_launcher_exits_when_degraded(monkeypatch, capsys):
    from repro.launch import serve
    monkeypatch.setattr(serve, "use_compile_cache", lambda: "")
    monkeypatch.setattr(ops, "dequant_matmul", _kernel_refused)
    with pytest.warns(RuntimeWarning, match="degraded mode"):
        with pytest.raises(SystemExit) as e:
            serve.main(["--variant", "smoke", "--quantise", "babsmax64:n4",
                        "--packed", "--requests", "1", "--max-new", "2"])
    msg = str(e.value.code)
    assert "degraded to dense" in msg and "2D gather" in msg


def test_compile_cache_placement(monkeypatch):
    from repro.launch import compile_cache
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert seen == []              # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert seen == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_result_line_shape(cs, monkeypatch, capsys):
    """With a TPU the last stdout line is exactly the driver's JSON."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    res = dict(platform="tpu", done=1, requests=1, failed=[], truncated=0,
               degraded=False, kl=0.0, kl_null=1.0, greedy_agreement=1.0,
               positions=8,
               tokens=2, steps=3, setup_s=0.0, compile_s=0.0, serve_s=0.0,
               kernels={"decode": {k: 1 for k in cs.KERNELS}})
    monkeypatch.setattr(cs, "require_tpu", lambda: [Dev()])
    monkeypatch.setattr(cs, "run", lambda cfg, seed: res)
    monkeypatch.setattr(cs, "model_config", lambda: None)
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "use_compile_cache", lambda: "")
    assert cs.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
