"""Config-exactness guards (the assigned hyperparameters, verbatim), the
entropy-coded checkpoint round trip, and where the compile cache goes."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs

ROOT = Path(__file__).resolve().parents[1]

# the assigned table, verbatim — guards against config drift
ASSIGNED = {
    "llama4-scout-17b-a16e": dict(n_layers=48, d_model=5120, n_heads=40,
                                  n_kv_heads=8, d_ff=8192, vocab=202048,
                                  n_experts=16, experts_per_token=1),
    "qwen2-moe-a2.7b": dict(n_layers=24, d_model=2048, n_heads=16,
                            n_kv_heads=16, d_ff=1408, vocab=151936,
                            n_experts=60, experts_per_token=4),
    "llama3-405b": dict(n_layers=126, d_model=16384, n_heads=128,
                        n_kv_heads=8, d_ff=53248, vocab=128256),
    "internlm2-20b": dict(n_layers=48, d_model=6144, n_heads=48,
                          n_kv_heads=8, d_ff=16384, vocab=92544),
    "gemma3-1b": dict(n_layers=26, d_model=1152, n_heads=4, n_kv_heads=1,
                      d_ff=6912, vocab=262144),
    "deepseek-7b": dict(n_layers=30, d_model=4096, n_heads=32,
                        n_kv_heads=32, d_ff=11008, vocab=102400),
    "rwkv6-1.6b": dict(n_layers=24, d_model=2048, d_ff=7168, vocab=65536),
    "whisper-large-v3": dict(n_layers=32, d_model=1280, n_heads=20,
                             n_kv_heads=20, d_ff=5120, vocab=51866),
    "internvl2-26b": dict(n_layers=48, d_model=6144, n_heads=48,
                          n_kv_heads=8, d_ff=16384, vocab=92553),
    "zamba2-2.7b": dict(n_layers=54, d_model=2560, n_heads=32, d_ff=10240,
                        vocab=32000, ssm_state=64),
}


@pytest.mark.parametrize("arch_id", list(ASSIGNED))
def test_full_config_matches_assignment(arch_id):
    cfg = configs.get_config(arch_id, "full")
    for field, want in ASSIGNED[arch_id].items():
        assert getattr(cfg, field) == want, (arch_id, field)


def test_all_assigned_archs_registered():
    assert set(configs.ASSIGNED) == set(ASSIGNED)


@pytest.mark.parametrize("arch_id", list(ASSIGNED))
def test_smoke_config_same_family(arch_id):
    full = configs.get_config(arch_id, "full")
    smoke = configs.get_config(arch_id, "smoke")
    assert smoke.family == full.family
    assert bool(smoke.n_experts) == bool(full.n_experts)
    assert bool(smoke.local_global_pattern) == bool(full.local_global_pattern)


def test_shape_cells_account_for_40():
    runnable = skipped = 0
    for arch in configs.ASSIGNED:
        cfg = configs.get_config(arch, "full")
        for s in configs.SHAPES:
            ok, _ = configs.applicable(cfg, s)
            runnable += ok
            skipped += not ok
    assert runnable + skipped == 40
    assert skipped == 7  # pure-full-attention archs skip long_500k


class TestCompressedCheckpoint:
    def test_roundtrip_and_size(self, tmp_path):
        from repro.models.api import get_family
        from repro.train.compressed_ckpt import (load_compressed_params,
                                                 save_compressed_params)
        cfg = configs.get_config("paper-100m", "smoke")
        fam = get_family(cfg.family)
        params = fam.init(jax.random.PRNGKey(0), cfg)
        path = save_compressed_params(str(tmp_path / "c"), params,
                                      target_bits=4.0)
        loaded = load_compressed_params(path, params)
        import os
        # round-trip error bounded by the grid resolution per tensor
        for (p, a), b in zip(
                jax.tree_util.tree_flatten_with_path(params)[0],
                jax.tree.leaves(loaded)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert a.shape == b.shape
            if a.size >= 4096 and a.ndim >= 2:
                rms = np.sqrt((a ** 2).mean())
                assert np.abs(a - b).max() < rms  # grid-bounded
            else:
                np.testing.assert_array_equal(a, b)  # raw
        # size: well under bf16 and under packed int8
        nbytes = os.path.getsize(os.path.join(path, "arrays.npz"))
        n_params = sum(x.size for x in jax.tree.leaves(params))
        assert nbytes < n_params * 1.0  # < 8 bits/param incl. overheads

    def test_achieved_bits_near_target(self, tmp_path):
        import json, os
        from repro.models.api import get_family
        from repro.train.compressed_ckpt import save_compressed_params
        cfg = configs.get_config("paper-100m", "smoke")
        fam = get_family(cfg.family)
        params = fam.init(jax.random.PRNGKey(1), cfg)
        path = save_compressed_params(str(tmp_path / "c2"), params,
                                      target_bits=3.0)
        with open(os.path.join(path, "manifest.json")) as f:
            man = json.load(f)
        assert 2.5 < man["achieved_bits_per_param"] < 3.6


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
