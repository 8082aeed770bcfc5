"""Pallas TPU kernel: flash-style decode attention over a quantised KV cache.

The decode hot path reads the whole KV cache every token — at serving
batch sizes it is HBM-bound, so the win is shrinking the stream: K/V live
in HBM as block-scaled uint8 codes (nibble-packed for 4-bit) plus one
float32 absmax scale per (token, head) row, and this kernel dequantises
them **in VMEM** after the HBM read — codes stream at 1/4–1/8 the dense
f32 bytes and no dense copy of the cache ever exists.

Shape/grid design (one cache group, one layer per call), in the 2-D forms
Mosaic lowers:

* grid ``(B, K / kb, cdiv(S, sc))`` — batch rows outer, blocks of ``kb``
  KV heads next, cache chunks inner (the minor grid dim is sequential on
  TPU, so VMEM scratch carries the online-softmax state ``(m, l, acc)``
  across a (row, head block)'s chunk sweep, exactly the
  ``flash_attention`` recurrence). A ragged last chunk is masked.
* the cache is viewed as ``(B, S, K·hdc)`` codes and ``(B, S, K)`` scales
  (free reshapes), so every tile is a lane-dense 2-D block: a
  ``(sc, kb·hdc)`` code tile (``kb·hdc`` a multiple of 128, or ``kb = K``)
  and the ``(sc, K)`` scales of every head. Every KV codebook is a
  uniform grid (``serve.cache.kv_codebook``), so each step decodes the
  code tile affinely, ``lo + code · step`` (the grid's two scalars in
  SMEM: one convert and a multiply-add per element, where a lookup would
  take ``n_codes - 1`` selects), and scales it per (token, head) with a
  0/1 expansion matmul that also picks the block's heads out of the
  scales.
* heads stay 2-D too: the wrapper lays each head block's queries out
  **head-block diagonal**, ``(T·kb·G, kb·hd)`` with query head ``h`` in the
  lanes of its KV head ``h // G``, so one matmul against the dequantised
  tile gives every head's scores, and ``P @ V`` gives each head its own KV
  head's values in its lane block (the wrapper keeps that block). The
  matmuls do ``kb`` times the work a head needs; :func:`choose_kv_block`
  keeps the whole of ``K`` in one block where it fits the VMEM budget
  (grouped-query caches, whose ``K`` is small) and otherwise takes the
  narrowest legal block (wide multi-head caches). 4-bit rows split into
  their low-nibble (even) and high-nibble (odd) elements, two parts with
  matching query parts — no lane interleave in the kernel.
* q positions and the window arrive by scalar prefetch (SMEM); masks are
  built **in-kernel** from reconstructed slot positions — the
  ring/window/causal semantics of ``models.layers.chunked_decode_attention``
  (slot ``s`` holds position ``last - ((last - s) mod S)`` for ring
  buffers; negative ⇒ never written), so wrap-around needs no extra inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dequant_matmul.dequant_matmul import row_chunk

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def uniform_grid(codebook):
    """``(lo, step)`` of a uniform codebook, ``codebook[c] = lo + c · step``
    (f32 scalars), read from its first and last entries."""
    cb = codebook.astype(jnp.float32)
    return cb[0], (cb[-1] - cb[0]) / (cb.shape[0] - 1)


def _dequant_parts(codes_ref, scales_ref, grid_ref, out_ref, valid_rows,
                   head0, *, bits: int, K: int, kb: int):
    """Expand a (sc, kb·hdc) code tile of KV heads ``head0 .. head0 + kb``
    into ``out_ref`` (P, sc, kb·w) f32 = (lo + code · step) × (token, head)
    scale, ``(lo, step)`` read from the SMEM ``grid_ref`` and the scales
    from the (sc, K) tile of every head; 4-bit rows split into low/high
    nibble parts. Rows at or past ``valid_rows`` (a ragged last chunk) are
    zero."""
    sc, kw = out_ref.shape[1], out_ref.shape[2]
    w = kw // kb
    i = (jax.lax.broadcasted_iota(jnp.int32, (K, kw), 0) - head0) * w
    c = jax.lax.broadcasted_iota(jnp.int32, (K, kw), 1)
    expand = ((c >= i) & (c < i + w)).astype(jnp.float32)
    ch = row_chunk(sc)

    def body(n, carry):
        r0 = pl.multiple_of(n * ch, ch)
        codes = codes_ref[pl.ds(r0, ch), :].astype(jnp.int32)
        s = _dot(scales_ref[pl.ds(r0, ch), :], expand, ((1,), (0,)))
        rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (ch, kw), 0)
        parts = [codes & 0xF, codes >> 4] if bits == 4 else [codes]
        for p, cp in enumerate(parts):
            vals = (cp.astype(jnp.float32) * grid_ref[1] + grid_ref[0]) * s
            out_ref[p, pl.ds(r0, ch), :] = jnp.where(rows < valid_rows,
                                                     vals, 0.0)
        return carry

    jax.lax.fori_loop(0, sc // ch, body, 0)


def _kernel(qp_ref, win_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
            grid_ref, o_ref, m_ref, l_ref, acc_ref, kd_ref, vd_ref, *,
            bits: int, sc: int, S: int, ring: bool, T: int, HB: int, K: int,
            kb: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    n_parts = q_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = S - j * sc
    kw = dict(bits=bits, K=K, kb=kb)
    head0 = pl.program_id(1) * kb
    _dequant_parts(kc_ref, ks_ref, grid_ref, kd_ref, valid, head0, **kw)
    _dequant_parts(vc_ref, vs_ref, grid_ref, vd_ref, valid, head0, **kw)
    s = _dot(q_ref[0], kd_ref[0], ((1,), (1,)))            # (T·HB, sc)
    for p in range(1, n_parts):
        s += _dot(q_ref[p], kd_ref[p], ((1,), (1,)))

    # per-row query position (row t·HB + h belongs to query token t)
    row = jax.lax.broadcasted_iota(jnp.int32, (T * HB, 1), 0)
    qpos = jnp.zeros((T * HB, 1), jnp.int32)
    for t in range(T):
        qpos = jnp.where((row >= t * HB) & (row < (t + 1) * HB),
                         qp_ref[b, t], qpos)
    window = win_ref[0]
    slots = j * sc + jax.lax.broadcasted_iota(jnp.int32, (1, sc), 1)
    if ring:
        # slot s holds last - ((last - s) mod S); with r = last mod S that
        # is (last - r) + s, less S for the slots past r
        last = qp_ref[b, T - 1]
        r = jax.lax.rem(last, S)
        kv = (last - r) + slots - jnp.where(slots > r, S, 0)
        mask = (kv <= qpos) & (qpos - kv < window) & (kv >= 0)
    else:
        kv = slots
        mask = (kv <= qpos) & ((window <= 0) | (qpos - kv < window))
    mask &= slots < S
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                     # (T·HB, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    for part in range(n_parts):
        acc_ref[part] = acc_ref[part] * corr + _dot(p, vd_ref[part],
                                                    ((1,), (0,)))

    @pl.when(j == nj - 1)
    def _done():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-30)
        for part in range(n_parts):
            o_ref[part] = acc_ref[part] * inv


def choose_schunk(S: int) -> int:
    """Cache-chunk tile: the whole cache when it is short, else 512 rows
    (a multiple of the uint8 sublane tile; a ragged last chunk is
    masked). Not yet measured on the chip."""
    return S if S <= 512 else 512


# scoped VMEM a call may plan for: the v5e's default limit is 16 MiB, and
# the rest is left to Mosaic's own temporaries
VMEM_BUDGET = 12 * 2 ** 20


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(kb: int, *, T: int, G: int, K: int, hd: int, bits: int,
               sc: int) -> int:
    """VMEM one grid step of a ``kb``-head block holds, with its tiles
    padded to the (8, 128) f32 and (32, 128) uint8 layouts: the query and
    output blocks and the K/V code and scale tiles, each double-buffered,
    and the scratch (softmax state, accumulator, dequantised K and V)."""
    P = 2 if bits == 4 else 1
    hdc = hd // 2 if bits == 4 else hd
    rows, lanes = _ceil(T * kb * G, 8), _ceil(kb * hd // P, 128)
    qo = P * rows * lanes * 4
    codes = _ceil(sc, 32) * _ceil(kb * hdc, 128)
    scales = _ceil(sc, 8) * _ceil(K, 128) * 4
    kv = P * _ceil(sc, 8) * lanes * 4
    return 2 * (2 * qo + 2 * codes + 2 * scales) + qo + 2 * kv \
        + 2 * rows * 128 * 4


def choose_kv_block(K: int, G: int, T: int, hd: int, bits: int,
                    sc: int) -> int:
    """KV heads per grid step: all ``K`` where one block fits
    :data:`VMEM_BUDGET`, else the narrowest block whose code tile is a
    whole number of 128-lane rows (fewest wasted head-block products),
    else ``K``."""
    hdc = hd // 2 if bits == 4 else hd
    size = dict(T=T, G=G, K=K, hd=hd, bits=bits, sc=sc)
    if vmem_bytes(K, **size) <= VMEM_BUDGET:
        return K
    legal = [kb for kb in range(1, K) if K % kb == 0
             and (kb * hdc) % 128 == 0]
    return legal[0] if legal else K


@functools.partial(jax.jit, static_argnames=("ring", "bits", "interpret",
                                             "schunk", "scale"))
def decode_attention_quant(q, k_codes, k_scales, v_codes, v_scales,
                           codebook, q_positions, window=0, *,
                           ring: bool = False, bits: int = 8,
                           interpret: bool = False, schunk=None, scale=None):
    """Masked decode attention straight from quantised cache rows.

    q (B, T, H, hd); codes (B, S, K, hdc) uint8 (hdc = hd, or hd//2 nibble-
    packed for bits=4); scales (B, S, K, 1) f32; ``codebook`` a uniform
    grid (:func:`uniform_grid` reads its ends); q_positions (B, T) int32;
    ``window`` may be a traced scalar (0 = global); ``scale`` is the score
    scale (default ``hd ** -0.5``); :func:`choose_kv_block` sets the KV
    heads of a grid step. Returns (B, T, H, hd) in q.dtype — the
    quantised twin of ``models.layers.chunked_decode_attention``."""
    B, T, H, hd = q.shape
    S, K = k_codes.shape[1], k_codes.shape[2]
    G = H // K
    hdc = hd // 2 if bits == 4 else hd
    assert k_codes.shape == (B, S, K, hdc), (k_codes.shape, (B, S, K, hdc))
    assert k_scales.shape == (B, S, K, 1), k_scales.shape
    sc = schunk or choose_schunk(S)
    kb = choose_kv_block(K, G, T, hd, bits, sc)
    nb, HB = K // kb, kb * G
    # query parts: even/odd head-dim elements for nibble-packed rows, laid
    # out head-block diagonal within each block of kb KV heads,
    # (B, P, K/kb, T·kb·G, kb·w), and pre-scaled
    qf = q.astype(jnp.float32) * (hd ** -0.5 if scale is None else scale)
    parts = [qf[..., 0::2], qf[..., 1::2]] if bits == 4 else [qf]
    P, w = len(parts), hd // len(parts)
    own = (jnp.arange(HB) // G)[:, None] == jnp.arange(kb)      # (HB, kb)
    qbd = jnp.stack([
        jnp.where(own[:, :, None],
                  p.reshape(B, T, nb, HB, 1, w), 0.0)
        .transpose(0, 2, 1, 3, 4, 5).reshape(B, nb, T * HB, kb * w)
        for p in parts], axis=1)
    win = jnp.reshape(jnp.asarray(window, jnp.int32), (1,))
    qp = q_positions.astype(jnp.int32)
    kernel = functools.partial(_kernel, bits=bits, sc=sc, S=S, ring=ring,
                               T=T, HB=HB, K=K, kb=kb)
    block = pl.BlockSpec((None, P, None, T * HB, kb * w),
                         lambda b, h, j, *_: (b, 0, h, 0, 0))
    codes = pl.BlockSpec((None, sc, kb * hdc), lambda b, h, j, *_: (b, j, h))
    scales = pl.BlockSpec((None, sc, K), lambda b, h, j, *_: (b, j, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nb, pl.cdiv(S, sc)),
            in_specs=[block, codes, scales, codes, scales,
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((T * HB, 1), jnp.float32),
                pltpu.VMEM((T * HB, 1), jnp.float32),
                pltpu.VMEM((P, T * HB, kb * w), jnp.float32),
                pltpu.VMEM((P, sc, kb * w), jnp.float32),
                pltpu.VMEM((P, sc, kb * w), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, P, nb, T * HB, kb * w),
                                       jnp.float32),
        interpret=interpret,
        name="decode_attention",
    )(qp, win, qbd, k_codes.reshape(B, S, K * hdc),
      k_scales.reshape(B, S, K), v_codes.reshape(B, S, K * hdc),
      v_scales.reshape(B, S, K), jnp.stack(uniform_grid(codebook)))
    # keep each head's own KV-head lane block, re-interleave nibble parts
    heads = jnp.arange(HB)
    out = out.reshape(B, P, nb, T, HB, kb, w)[:, :, :, :, heads, heads // G]
    out = out.transpose(0, 3, 2, 4, 5, 1)          # (B, T, nb, HB, w, P)
    return out.reshape(B, T, H, hd).astype(q.dtype)
