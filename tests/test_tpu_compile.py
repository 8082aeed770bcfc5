"""Compile rehearsal for the TPU v5e: the serving path's Pallas kernels at
paper-100m's widths, and one whole decode step of the chip smoke's engine,
compiled for a described (not attached) ``v5e:2x2`` chip. Nothing runs;
the TPU compiler refuses here what it would refuse on the chip (illegal
block shapes, unsupported lowerings, VMEM or HBM overflows).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, so collection must
not touch it. Every test of this kind lives in this one file."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.block_quant.block_quant import block_quant
from repro.kernels.decode_attention.decode_attention import \
    decode_attention_quant
from repro.kernels.dequant_matmul.dequant_matmul import (dequant_matmul,
                                                         dequant_matmul_t)

BLOCK = 64                       # babsmax64, the smoke's weight format
# paper-100m projections (K, N): wq/wo, wk/wv, w_gate/w_up, w_down
PROJECTIONS = [(768, 768), (768, 256), (768, 2048), (2048, 768)]
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _calls(compiled):
    return ops.tpu_kernel_calls(compiled.as_text())


@pytest.mark.parametrize("K,N", PROJECTIONS)
@pytest.mark.parametrize("M", [8, 64, 256])
@pytest.mark.parametrize("variant", ["decode", "lut"])
@pytest.mark.parametrize("bits", [4, 8])
def test_dequant_matmul(one_chip, bits, variant, M, K, N):
    pack = 2 if bits == 4 else 1
    c = _compile(
        lambda x, w, s, cb: dequant_matmul(x, w, s, cb, block=BLOCK,
                                           bits=bits, variant=variant),
        one_chip, ((M, K), jnp.bfloat16), ((K // pack, N), jnp.uint8),
        ((K, N // BLOCK), jnp.bfloat16), ((2 ** bits,), jnp.float32))
    assert _calls(c) == {"dequant_matmul": 1}


@pytest.mark.parametrize("M", [8, 64])
@pytest.mark.parametrize("variant", ["decode", "lut"])
@pytest.mark.parametrize("bits", [4, 8])
def test_dequant_matmul_t_unembed(one_chip, bits, variant, M):
    V, D = 32768, 768
    pack = 2 if bits == 4 else 1
    c = _compile(
        lambda x, w, s, cb: dequant_matmul_t(x, w, s, cb, block=BLOCK,
                                             bits=bits, variant=variant),
        one_chip, ((M, D), jnp.bfloat16), ((V // pack, D), jnp.uint8),
        ((V, D // BLOCK), jnp.bfloat16), ((2 ** bits,), jnp.float32))
    assert _calls(c) == {"dequant_matmul_t": 1}


@pytest.mark.parametrize("T", [1, 8])
@pytest.mark.parametrize("bits", [8, 4])
def test_decode_attention(one_chip, bits, T):
    B, H, K, hd, S = 8, 12, 4, 64, 2048 + 8   # kv_len + prefill chunk
    hdc = hd // 2 if bits == 4 else hd
    c = _compile(
        lambda q, kc, ks, vc, vs, cb, qp: decode_attention_quant(
            q, kc, ks, vc, vs, cb, qp, 0, bits=bits),
        one_chip, ((B, T, H, hd), jnp.bfloat16),
        ((B, S, K, hdc), jnp.uint8), ((B, S, K, 1), jnp.float32),
        ((B, S, K, hdc), jnp.uint8), ((B, S, K, 1), jnp.float32),
        ((2 ** bits,), jnp.float32), ((B, T), jnp.int32))
    assert _calls(c) == {"decode_attention": 1}


def test_block_quant_kv_write(one_chip):
    rows, hd = 8 * 8 * 4, 64                  # B·T·K cache rows per write
    c = _compile(lambda x, cb: block_quant(x, cb, block=hd), one_chip,
                 ((rows, hd), jnp.float32), ((256,), jnp.float32))
    assert _calls(c) == {"block_quant": 1}


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_engine_decode_step(one_chip, monkeypatch):
    """The chip smoke's whole prefill step (packed weights, q8 cache, tied
    unembed) at full width: every kernel is a custom call, and the program
    fits one chip's HBM."""
    from repro.core import build_plan
    from repro.models.api import get_family
    from repro.serve.engine import alloc_decode_state

    cs = _chip_smoke()
    # the program's kernel choice asks jax.default_backend(), which is the
    # CPU here: steer it to the TPU path this compile is for
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = cs.model_config()
    fam = get_family(cfg.family)
    shapes = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg))
    plan = build_plan(shapes, cs.FORMAT)
    packed = jax.eval_shape(lambda p: plan.pack(p, fam.pack_layouts(cfg)),
                            shapes)
    state = jax.eval_shape(lambda: alloc_decode_state(
        fam, cfg, cs.SLOTS, cs.KV_LEN, slack=cs.CHUNK))
    batch = cs._step_batches(cs.SLOTS, cs.CHUNK)["prefill+reset"]
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    step = jax.jit(lambda p, s, b: fam.decode_step(p, s, b, cfg))
    compiled = step.lower(jax.tree.map(place, packed),
                          jax.tree.map(place, state),
                          jax.tree.map(place, batch)).compile()
    calls = _calls(compiled)
    assert all(calls.get(k) for k in ops.KERNEL_NAMES), calls
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
