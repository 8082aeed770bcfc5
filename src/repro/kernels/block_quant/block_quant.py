"""Pallas TPU kernel: fused block-absmax quantisation.

One pass over the weight: per (row, 128-lane block) absmax → bf16 round-away
scale → normalise → round-to-nearest codebook index. Feeds the quantised
checkpoint writer, the 8-bit optimizer and QAT; on TPU this is the kernel
the paper's direct-cast path runs at deployment time.

Tiling: grid over (row_tiles, col_tiles); each step loads a
(TILE_R, TILE_C) f32 tile HBM→VMEM (block=128 divides TILE_C, matching the
TPU lane width so scales align with vector lanes), writes uint8 codes and
f32 scales. Codebook (≤256 entries) lives in VMEM, broadcast per tile; the
index is computed as Σ_i [x > mid_i] (VPU compares; no gather needed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 128
TILE_R = 256
TILE_C = 512


def _round_away_bf16(s):
    s16 = s.astype(jnp.bfloat16)
    up = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(s16, jnp.uint16) + jnp.uint16(1),
        jnp.bfloat16)
    return jnp.where(s16.astype(jnp.float32) < s, up.astype(jnp.float32),
                     s16.astype(jnp.float32))


def _kernel(x_ref, mids_ref, codes_ref, scales_ref, *, block: int):
    x = x_ref[...].astype(jnp.float32)                    # (TR, TC)
    tr, tc = x.shape
    xb = x.reshape(tr, tc // block, block)
    s = jnp.max(jnp.abs(xb), axis=-1)                     # (TR, TC/blk)
    s = _round_away_bf16(s)
    safe = jnp.where(s == 0, 1.0, s)
    norm = (xb / safe[..., None]).reshape(tr, tc)
    mids = mids_ref[...]                                  # (n_codes-1,)
    code = jnp.zeros((tr, tc), jnp.int32)
    for i in range(mids.shape[0]):                        # unrolled VPU adds
        code += (norm > mids[i]).astype(jnp.int32)
    codes_ref[...] = code.astype(jnp.uint8)
    scales_ref[...] = s


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def block_quant(x: jnp.ndarray, codebook: jnp.ndarray, block: int = BLOCK,
                interpret: bool = False):
    """x (rows, cols) → (codes uint8 (rows, cols), scales f32 (rows, cols/block)).
    cols must divide by TILE_C (pad upstream)."""
    rows, cols = x.shape
    assert cols % block == 0
    tr, tc = min(TILE_R, rows), min(TILE_C, cols)
    assert rows % tr == 0 and cols % tc == 0 and tc % block == 0
    mids = ((codebook[1:] + codebook[:-1]) * 0.5).astype(jnp.float32)
    grid = (rows // tr, cols // tc)
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            pl.BlockSpec((mids.shape[0],), lambda i, j: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            pl.BlockSpec((tr, tc // block), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), jnp.uint8),
            jax.ShapeDtypeStruct((rows, cols // block), jnp.float32),
        ],
        interpret=interpret,
        name="block_quant",
    )(x, mids)
