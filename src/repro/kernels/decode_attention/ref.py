"""Pure-jnp oracle for the quantised flash-decode attention kernel.

The oracle is deliberately *compositional*: dequantise the block-scaled
K/V cache (the exact ``block_quant`` dequant math — codebook gather ×
per-(token, head) absmax scale, nibble unpack for 4-bit codes), then run
the very same masked chunked decode attention the dense serving path uses
(``models.layers.chunked_decode_attention``, imported lazily to keep the
kernels package free of an import-time dependency on models). That makes
the oracle's ring/window/causal mask semantics correct by construction —
any drift between the Pallas kernel and the dense path shows up as a
kernel bug, never as two subtly different oracles.

Layout (one self-attention cache group, one layer):

* ``q``            (B, T, H, hd) — T decode/prefill-chunk queries per slot
* ``k/v codes``    (B, S, K, hdc) uint8 — ``hdc = hd`` for 8-bit codes,
                   ``hd // 2`` for nibble-packed 4-bit (pairs along the
                   head dim: byte ``j`` holds elements ``2j`` (low nibble)
                   and ``2j + 1`` (high nibble) — a row is self-contained,
                   so ring writes never read-modify-write)
* ``k/v scales``   (B, S, K, 1) float32 — one absmax scale per
                   (token, head) row (scale block = head_dim)
* ``q_positions``  (B, T) int32 absolute positions (per-slot ragged)
"""
from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def unpack_nibbles_hd(codes: jnp.ndarray) -> jnp.ndarray:
    """(..., hd // 2) nibble-packed bytes → (..., hd) 4-bit codes.

    Byte ``j`` holds element ``2j`` in its low nibble and ``2j + 1`` in its
    high nibble (the pack order of ``models.layers.quantise_kv``)."""
    lo = codes & jnp.uint8(0xF)
    hi = (codes >> jnp.uint8(4)) & jnp.uint8(0xF)
    pair = jnp.stack([lo, hi], axis=-1)               # (..., hd/2, 2)
    return pair.reshape(*codes.shape[:-1], 2 * codes.shape[-1])


def dequant_kv_ref(codes, scales, codebook, bits: int, dtype=jnp.float32):
    """Dequantise block-scaled KV rows: codes (..., hdc) uint8 + scales
    (..., 1) f32 → (..., hd) values (codebook gather × row scale)."""
    if bits == 4:
        codes = unpack_nibbles_hd(codes)
    vals = codebook[codes.astype(jnp.int32)] * scales.astype(jnp.float32)
    return vals.astype(dtype)


def decode_attention_quant_ref(q, k_codes, k_scales, v_codes, v_scales,
                               codebook, q_positions, *, window=0,
                               ring: bool = False, bits: int = 8,
                               dequant_dtype=jnp.float32, scale=None):
    """Oracle: dequantise the whole cache, then run the dense serving
    path's masked chunked decode attention verbatim. Returns
    (B, T, H, hd) in ``q.dtype``."""
    from repro.models.layers import chunked_decode_attention
    k = dequant_kv_ref(k_codes, k_scales, codebook, bits, dequant_dtype)
    v = dequant_kv_ref(v_codes, v_scales, codebook, bits, dequant_dtype)
    return chunked_decode_attention(q, k, v, q_positions, window=window,
                                    ring=ring, scale=scale)
