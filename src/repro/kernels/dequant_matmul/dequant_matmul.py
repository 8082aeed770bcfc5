"""Pallas TPU kernel: fused dequantise(codes, scales) @ x — the serving
hot-spot.

Decode is HBM-bandwidth-bound: weights stream once per token. Packed 4/8-bit
codes cut the stream by 4–8× vs bf16 — this kernel realises the paper's
formats as a bandwidth win by dequantising in VMEM *after* the HBM read,
feeding the matmul without ever materialising the wide weight in HBM.

Dequantisation (shared by every strategy and both orientations, written in
the forms Mosaic lowers):

  * **codebook lookup** — a depth-first binary select tree over the code
    bits against codepoints read as scalars from SMEM (``n_codes - 1``
    VPU selects per element; no vector gather, no one-hot reshape). The
    tile is expanded in row chunks inside a ``fori_loop`` so the unrolled
    code stays small at 8-bit widths.
  * **block scales** — the scales BlockSpec takes *whole scale rows* of
    the tile's rows (``(tk, N // block)``: the last dim equals the array's,
    so the (8, 128) block rule holds for any block size), and the tile's
    columns are selected and expanded to lanes by one MXU matmul against a
    0/1 expansion matrix built from iota (exact: one non-zero term per
    output). bf16 scales (the packed serving dtype) take one bf16 pass;
    f32 scales take the fp32-precision contraction.

Two **strategies** differ only in how the dequantised tile feeds the MXU,
picked per matmul geometry by the tuning table (``tune.choose_tiles``):

  * **decode** — f32 tile and activations, fp32-precision contraction.
    At serving M (= batch slots) the MXU has slack and the kernel is bound
    by the weight stream and the VPU lookup, so the exact feed is free.
  * **lut** — the tile is rounded to bf16 and fed with bf16 activations in
    one MXU pass: right at prefill/training M, where the multi-pass f32
    contraction would make the MXU the bound.

Tile shapes ``(tm, tk, tn)`` come from ``tune.choose_tiles(M, K, N,
bits)`` — an analytic roofline over the legal tile space, cached per
geometry, pre-seedable from measured sweeps. M is padded up to ``tm``
with zero rows (sliced off the output), so no divisibility constraint
leaks to callers.

Code layouts:

  * ``bits=8`` — one uint8 per code, tile (TK, TN).
  * ``bits=4`` — nibble-packed (two codes per byte along K, the
    ``core.nibble`` per-K-tile half interleave): the HBM read is a
    (TK/2, TN) byte tile whose low nibbles are the tile's first TK/2 rows
    and high nibbles the second, so the unpack is a mask and a shift per
    row chunk. The K tile is layout-locked to the interleave tile.

An optional leading dim batches the matmul over stacked experts (MoE
serving) as an extra outer grid axis.

Tiling: grid (E, M/TM, N/TN, K/TK), k innermost for revolving f32
accumulation in VMEM.

``dequant_matmul_t`` is the **transposed** variant: y = x @ dequant(W).T
for codes stored (V, D) with scales blocked along D — the contraction runs
along the *blocked* axis. This is the tied-embeddings unembed: the packed
``embed`` table (codes (V, D), gather-ready for lookups) serves the logits
matmul directly, so ``unembed = embed.T`` never materialises. The same
dequant body runs on (V, D) tiles; the matmul contracts the last dim of
both operands instead of transposing the tile."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nibble import NIBBLE_K_TILE
from repro.kernels.dequant_matmul.tune import choose_tiles

BLOCK = 128
TILE_K = NIBBLE_K_TILE  # K tile == the nibble interleave tile (core.nibble)


def codepoints(c, cb_ref, n_codes: int):
    """Codebook lookup ``cb[c]`` for an int32 code array, as a depth-first
    binary select tree over the code bits (``n_codes - 1`` selects against
    codepoints read as scalars from the SMEM ``cb_ref``). Depth-first keeps
    only one partial result per tree level live. Returns f32, c's shape."""
    depth = max(1, (n_codes - 1).bit_length())
    bit = [((c >> b) & 1) == 1 for b in range(depth)]

    def tree(base: int, d: int):
        if d == 0:
            return cb_ref[min(base, n_codes - 1)]
        hi = base + (1 << (d - 1))
        lo = tree(base, d - 1)
        if hi >= n_codes:        # no code reaches this half
            return lo
        return jnp.where(bit[d - 1], tree(hi, d - 1), lo)

    return jnp.broadcast_to(tree(0, depth), c.shape).astype(jnp.float32)


def row_chunk(rows: int, cap: int = 32) -> int:
    """Largest power-of-two row chunk ≤ ``cap`` dividing ``rows`` (32 is
    the uint8 sublane tile)."""
    ch = cap
    while rows % ch:
        ch //= 2
    return ch


def _expansion(nb: int, col0, cols: int, block: int, dtype):
    """(nb, cols) 0/1 matrix E with E[i, c] = 1 iff column ``col0 + c``
    lies in scale block ``i``: ``scale_rows @ E`` selects the tile's
    blocks and repeats each across its ``block`` lanes."""
    i = jax.lax.broadcasted_iota(jnp.int32, (nb, cols), 0) * block
    c = jax.lax.broadcasted_iota(jnp.int32, (nb, cols), 1) + col0
    return ((c >= i) & (c < i + block)).astype(dtype)


def _scale_dot(s, e):
    prec = (None if s.dtype == jnp.bfloat16 else
            jax.lax.Precision.HIGHEST)
    return jax.lax.dot_general(s, e, (((1,), (0,)), ((), ())),
                               precision=prec,
                               preferred_element_type=jnp.float32)


def _dequant_into(codes_ref, scales_ref, cb_ref, w_ref, col0, *, block: int,
                  n_codes: int, bits: int):
    """Expand a packed code tile into ``w_ref`` (R, C) f32 = codepoint ×
    block scale. codes_ref: (R // pack, C) uint8; scales_ref: (R, nb) whole
    scale rows; ``col0`` is the tile's first column in the blocked axis."""
    rp, cols = codes_ref.shape
    sdt = jnp.bfloat16 if scales_ref.dtype == jnp.bfloat16 else jnp.float32
    e = _expansion(scales_ref.shape[1], col0, cols, block, sdt)
    ch = row_chunk(rp)

    def put(r0, c):
        s = scales_ref[pl.ds(r0, ch), :]
        w_ref[pl.ds(r0, ch), :] = (codepoints(c, cb_ref, n_codes)
                                   * _scale_dot(s, e))

    def body(i, carry):
        r0 = pl.multiple_of(i * ch, ch)
        c = codes_ref[pl.ds(r0, ch), :].astype(jnp.int32)
        if bits == 4:       # per-tile half interleave (core.nibble)
            put(r0, c & 0xF)
            put(r0 + rp, c >> 4)
        else:
            put(r0, c)
        return carry

    jax.lax.fori_loop(0, rp // ch, body, 0)


def _kernel(x_ref, codes_ref, scales_ref, cb_ref, o_ref, acc_ref, w_ref, *,
            block: int, n_codes: int, bits: int, decode: bool,
            transposed: bool):
    nd = 3 if transposed else 4          # grid (E?, M, out, contraction)
    j, k = pl.program_id(nd - 2), pl.program_id(nd - 1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # normal: the tile's lanes are output columns (N tile j); transposed:
    # they are the contraction (D tile k), blocked either way
    col0 = (k if transposed else j) * w_ref.shape[1]
    _dequant_into(codes_ref, scales_ref, cb_ref, w_ref, col0, block=block,
                  n_codes=n_codes, bits=bits)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))
    if decode:
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), w_ref[...], dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.bfloat16), w_ref[...].astype(jnp.bfloat16),
            dims, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(nd - 1) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _resolve(M, K, N, bits, n_codes, block, variant):
    """Tiles + strategy for one geometry: tuning table unless forced."""
    tm, tk, tn, decode = choose_tiles(M, K, N, bits, n_codes=n_codes,
                                      block=block)
    if variant is not None:
        decode = variant == "decode"
    return tm, tk, tn, decode


@functools.partial(jax.jit, static_argnames=("block", "bits", "interpret",
                                             "out_dtype", "variant"))
def dequant_matmul(x, codes, scales, codebook, block: int = BLOCK,
                   bits: int = 8, interpret: bool = False,
                   out_dtype=jnp.bfloat16, variant: str | None = None):
    """x (*lead, M, K) @ dequant(codes, scales) → (*lead, M, N).

    codes: (*lead, K, N) uint8, or (*lead, K // 2, N) nibble-packed bytes
    when ``bits == 4``. scales: (*lead, K, N // block). ``lead`` is at most
    one dim (stacked experts), batched as an outer grid axis.

    ``variant``: None (default) lets the tuning table pick the strategy per
    geometry; "lut" / "decode" force it (tests, sweeps). M is padded up to
    the M tile with zero rows — any row count serves."""
    lead = x.ndim == 3
    if not lead:
        x, codes, scales = x[None], codes[None], scales[None]
    E, M, K = x.shape
    pack = 2 if bits == 4 else 1
    assert codes.shape[0] == E and codes.shape[1] * pack == K
    N = codes.shape[2]
    assert N % block == 0 and scales.shape == (E, K, N // block)
    n_codes = codebook.shape[0]
    tm, tk, tn, decode = _resolve(M, K, N, bits, n_codes, block, variant)
    assert K % tk == 0 and N % tn == 0 and tn % block == 0
    assert tk % pack == 0
    pad_m = (-M) % tm
    if pad_m:
        x = jnp.pad(x, ((0, 0), (0, pad_m), (0, 0)))
    grid = (E, (M + pad_m) // tm, N // tn, K // tk)
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, n_codes=n_codes, bits=bits,
                          decode=decode, transposed=False),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tm, tk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((None, tk // pack, tn),
                         lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((None, tk, N // block),
                         lambda e, i, j, k: (e, k, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, tm, tn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, M + pad_m, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32),
                        pltpu.VMEM((tk, tn), jnp.float32)],
        interpret=interpret,
        name="dequant_matmul",
    )(x, codes, scales, codebook.astype(jnp.float32))
    if pad_m:
        out = out[:, :M]
    return out if lead else out[0]


@functools.partial(jax.jit, static_argnames=("block", "bits", "interpret",
                                             "out_dtype", "variant"))
def dequant_matmul_t(x, codes, scales, codebook, block: int = BLOCK,
                     bits: int = 8, interpret: bool = False,
                     out_dtype=jnp.bfloat16, variant: str | None = None):
    """x (M, D) @ dequant(codes, scales).T → (M, V): contraction along the
    **blocked** axis (tied-embeddings unembed).

    codes: (V, D) uint8, or (V // 2, D) nibble-packed bytes when
    ``bits == 4`` (the ``core.nibble`` interleave along V — the same layout
    ``embed_lookup`` gathers rows from). scales: (V, D // block), blocks
    along D. ``variant``/M padding as in :func:`dequant_matmul`."""
    M, D = x.shape
    pack = 2 if bits == 4 else 1
    V = codes.shape[0] * pack
    assert codes.shape[1] == D and scales.shape == (V, D // block)
    n_codes = codebook.shape[0]
    # the V axis plays the nibble-tiled row role, D the blocked lane one
    tm, tv, td, decode = _resolve(M, V, D, bits, n_codes, block, variant)
    assert V % tv == 0 and D % td == 0 and td % block == 0
    assert tv % pack == 0
    pad_m = (-M) % tm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    grid = ((M + pad_m) // tm, V // tv, D // td)
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, n_codes=n_codes, bits=bits,
                          decode=decode, transposed=True),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, td), lambda i, j, k: (i, k)),
            pl.BlockSpec((tv // pack, td), lambda i, j, k: (j, k)),
            pl.BlockSpec((tv, D // block), lambda i, j, k: (j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((tm, tv), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M + pad_m, V), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, tv), jnp.float32),
                        pltpu.VMEM((tv, td), jnp.float32)],
        interpret=interpret,
        name="dequant_matmul_t",
    )(x, codes, scales, codebook.astype(jnp.float32))
    return out[:M] if pad_m else out
