"""jit'd public wrappers over the Pallas kernels. On TPU they run the
compiled kernels; off-TPU they take the jnp oracle (tests; interpret=True
exercises the kernel bodies on the CPU). Nothing on the chip reaches the
oracle branch — :func:`tpu_kernel_calls` counts the kernels in a compiled
step to prove it."""
from __future__ import annotations

import collections
import re

import jax
import jax.numpy as jnp

from .block_quant.block_quant import block_quant as _bq_pallas
from .block_quant.ref import block_quant_ref, block_dequant_ref
from .decode_attention.decode_attention import \
    decode_attention_quant as _daq_pallas
from .decode_attention.ref import (decode_attention_quant_ref,
                                   dequant_kv_ref)
from .dequant_matmul.dequant_matmul import dequant_matmul as _dqm_pallas
from .dequant_matmul.dequant_matmul import dequant_matmul_t as _dqmt_pallas
from .dequant_matmul.ref import (dequant_matmul_decode_ref, dequant_matmul_ref,
                                 dequant_matmul_t_decode_ref,
                                 dequant_matmul_t_ref)

# Every 2-D x on the CPU fallback takes the decode-shaped oracle: its M=1
# pad and cache-sized N-panels win or tie the plain einsum at every
# measured M — decode rows (M = batch slots) by up to 4×, prefill chunks
# (M = slots × chunk, 32–192) by 1.2–2.5× on narrow-K shapes. Only the
# batched MoE lead-dim path (3-D x) stays on the plain oracle.


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# the ``name=`` of each pallas_call; a compiled TPU program names the
# kernel's custom-call instruction after it
KERNEL_NAMES = ("dequant_matmul", "dequant_matmul_t", "decode_attention",
                "block_quant")
_CUSTOM_CALL = re.compile(
    r'^\s*(?:ROOT )?%([\w.-]+) = .*custom_call_target="tpu_custom_call"')


def tpu_kernel_calls(hlo_text: str) -> collections.Counter:
    """Count the ``tpu_custom_call`` instructions of a compiled TPU HLO
    module (``compiled.as_text()``) by kernel name (instruction name less
    its ``.N`` uniquifier)."""
    names = (_CUSTOM_CALL.match(line) for line in hlo_text.splitlines())
    return collections.Counter(re.sub(r"(\.\d+)+$", "", m.group(1))
                               for m in names if m)


def block_quant(x, codebook, block: int = 128, interpret: bool | None = None):
    """Quantise a 2-D weight into (codes, scales). Uses the Pallas kernel on
    TPU (or in interpret mode); jnp oracle otherwise."""
    if interpret is None:
        interpret = not on_tpu()
    if interpret and not on_tpu():
        # fall back to the oracle for speed unless explicitly interpreting
        return block_quant_ref(x, codebook, block)
    return _bq_pallas(x, codebook, block=block, interpret=interpret)


def block_quant_interpret(x, codebook, block: int = 128):
    """Force the Pallas kernel body in interpret mode (tests)."""
    return _bq_pallas(x, codebook, block=block, interpret=True)


def block_dequant(codes, scales, codebook, block: int = 128,
                  dtype=jnp.bfloat16):
    return block_dequant_ref(codes, scales, codebook, block, dtype)


def dequant_matmul(x, codes, scales, codebook, block: int = 128,
                   bits: int = 8, interpret: bool | None = None):
    """x @ dequant(codes, scales) — fused on TPU; oracle off-TPU.

    ``bits=4``: codes are nibble-packed ((*lead, K//2, N) bytes, the
    ``core.nibble`` layout) and unpacked in VMEM after the HBM read. An
    optional leading dim batches over stacked experts (MoE serving).

    The off-TPU fallback dispatches by shape: 2-D x takes the decode-shaped
    oracle (panelled; bit-identical to the plain einsum oracle in ``ref.py``
    for M ≥ 2), the batched MoE lead-dim form the plain oracle."""
    if interpret is None:
        interpret = not on_tpu()
    if interpret and not on_tpu():
        if x.ndim == 2:
            return dequant_matmul_decode_ref(x, codes, scales, codebook,
                                             block, bits=bits)
        return dequant_matmul_ref(x, codes, scales, codebook, block,
                                  bits=bits)
    return _dqm_pallas(x, codes, scales, codebook, block=block, bits=bits,
                       interpret=interpret)


def dequant_matmul_interpret(x, codes, scales, codebook, block: int = 128,
                             bits: int = 8, variant: str | None = None):
    return _dqm_pallas(x, codes, scales, codebook, block=block, bits=bits,
                       interpret=True, variant=variant)


def dequant_matmul_t(x, codes, scales, codebook, block: int = 128,
                     bits: int = 8, interpret: bool | None = None):
    """x @ dequant(codes, scales).T — contraction along the **blocked**
    axis (the tied-embeddings unembed: the packed embed table (V, D) serves
    the logits matmul without materialising its transpose). Fused on TPU;
    oracle off-TPU. ``bits=4``: codes nibble-packed along V. Off-TPU, 2-D
    calls take the decode-shaped oracle, bit-identical to the plain one
    for M ≥ 2."""
    if interpret is None:
        interpret = not on_tpu()
    if interpret and not on_tpu():
        if x.ndim == 2:
            return dequant_matmul_t_decode_ref(x, codes, scales, codebook,
                                               block, bits=bits)
        return dequant_matmul_t_ref(x, codes, scales, codebook, block,
                                    bits=bits)
    return _dqmt_pallas(x, codes, scales, codebook, block=block, bits=bits,
                        interpret=interpret)


def dequant_matmul_t_interpret(x, codes, scales, codebook, block: int = 128,
                               bits: int = 8, variant: str | None = None):
    return _dqmt_pallas(x, codes, scales, codebook, block=block, bits=bits,
                        interpret=True, variant=variant)


def decode_attention_quant(q, k_codes, k_scales, v_codes, v_scales,
                           codebook, q_positions, window=0, *,
                           ring: bool = False, bits: int = 8,
                           interpret: bool | None = None):
    """Masked decode attention straight from block-scaled KV codes — the
    quantised twin of ``models.layers.chunked_decode_attention``. Fused
    flash-decode Pallas kernel on TPU (codes dequantise in VMEM after the
    HBM read); compositional oracle (dequantise + the dense masked path)
    off-TPU. ``bits=4``: codes nibble-packed pairwise along the head
    dim."""
    if interpret is None:
        interpret = not on_tpu()
    if interpret and not on_tpu():
        return decode_attention_quant_ref(
            q, k_codes, k_scales, v_codes, v_scales, codebook, q_positions,
            window=window, ring=ring, bits=bits)
    return _daq_pallas(q, k_codes, k_scales, v_codes, v_scales, codebook,
                       q_positions, window, ring=ring, bits=bits,
                       interpret=interpret)


def decode_attention_quant_interpret(q, k_codes, k_scales, v_codes, v_scales,
                                     codebook, q_positions, window=0, *,
                                     ring: bool = False, bits: int = 8,
                                     schunk=None):
    """Force the Pallas kernel body in interpret mode (tests)."""
    return _daq_pallas(q, k_codes, k_scales, v_codes, v_scales, codebook,
                       q_positions, window, ring=ring, bits=bits,
                       interpret=True, schunk=schunk)


def dequant_kv(codes, scales, codebook, bits: int = 8, dtype=jnp.float32):
    """Dequantise block-scaled KV rows (codes (..., hdc) + per-row scales
    (..., 1) → values (..., hd)); see decode_attention.ref."""
    return dequant_kv_ref(codes, scales, codebook, bits, dtype)


def dequant_rows(codes, scales, codebook, block: int = 128, dtype=None,
                 nibble=None):
    """Dequantise gathered rows of a packed weight (the embedding-lookup
    path: gather uint8 code rows + their scales, then expand — the full
    vocab×d table is never materialised in the serving dtype).

    codes: (..., N) uint8; scales: (..., N // block); returns (..., N).

    ``nibble`` (optional, (...,) int ∈ {0, 1}): the gathered code rows are
    nibble-packed bytes; select each row's low/high nibble before the
    codebook lookup. ``dtype=None`` keeps the legacy float32 output; callers
    serving packed tensors pass the tensor/serving dtype so the activation
    stream is not silently upcast."""
    if nibble is not None:
        shift = (nibble.astype(jnp.uint8) * jnp.uint8(4))[..., None]
        codes = jnp.right_shift(codes, shift) & jnp.uint8(0xF)
    n = codes.shape[-1]
    vals = codebook[codes.astype(jnp.int32)]
    vals = vals.reshape(*codes.shape[:-1], n // block, block)
    out = vals * scales.astype(jnp.float32)[..., None]
    return out.reshape(codes.shape).astype(dtype or jnp.float32)
