"""Pallas TPU kernel: flash-style decode attention over a quantised KV cache.

The decode hot path reads the whole KV cache every token — at serving
batch sizes it is HBM-bound, so the win is shrinking the stream: K/V live
in HBM as block-scaled uint8 codes (nibble-packed for 4-bit) plus one
float32 absmax scale per (token, head) row, and this kernel dequantises
them **in VMEM** after the HBM read — codes stream at 1/4–1/8 the dense
f32 bytes and no dense copy of the cache ever exists.

Shape/grid design (one cache group, one layer per call), in the 2-D forms
Mosaic lowers:

* grid ``(B, cdiv(S, sc))`` — batch rows outer, cache chunks inner (the
  minor grid dim is sequential on TPU, so VMEM scratch carries the
  online-softmax state ``(m, l, acc)`` across a row's chunk sweep, exactly
  the ``flash_attention`` recurrence). A ragged last chunk is masked.
* the cache is viewed as ``(B, S, K·hdc)`` codes and ``(B, S, K)`` scales
  (free reshapes), so every tile is a lane-dense 2-D block. Each step
  expands a ``(sc, K·hdc)`` code tile through the codebook select tree
  (``dequant_matmul.codepoints``, codepoints in SMEM) and scales it per
  (token, head) with a 0/1 expansion matmul.
* heads stay 2-D too: the wrapper lays the queries out **head-block
  diagonal**, ``(T·H, K·hd)`` with query head ``h`` in the lanes of its KV
  head ``h // G``, so one matmul against the whole dequantised tile gives
  every head's scores, and ``P @ V`` gives each head its own KV head's
  values in its lane block (the wrapper keeps that block). 4-bit rows split
  into their low-nibble (even) and high-nibble (odd) elements, two parts
  with matching query parts — no lane interleave in the kernel.
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

from repro.kernels.dequant_matmul.dequant_matmul import codepoints, row_chunk

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _dequant_parts(codes_ref, scales_ref, cb_ref, out_ref, valid_rows, *,
                   n_codes: int, bits: int, K: int):
    """Expand a (sc, K·hdc) code tile into ``out_ref`` (P, sc, K·w) f32 =
    codepoint × (token, head) scale; 4-bit rows split into low/high nibble
    parts. Rows at or past ``valid_rows`` (a ragged last chunk) are zero."""
    sc, kw = out_ref.shape[1], out_ref.shape[2]
    w = kw // K
    i = jax.lax.broadcasted_iota(jnp.int32, (K, kw), 0) * w
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
            vals = codepoints(cp, cb_ref, n_codes) * s
            out_ref[p, pl.ds(r0, ch), :] = jnp.where(rows < valid_rows,
                                                     vals, 0.0)
        return carry

    jax.lax.fori_loop(0, sc // ch, body, 0)


def _kernel(qp_ref, win_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, cb_ref,
            o_ref, m_ref, l_ref, acc_ref, kd_ref, vd_ref, *, bits: int,
            n_codes: int, sc: int, S: int, ring: bool, T: int, H: int,
            K: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    n_parts = q_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = S - j * sc
    kw = dict(n_codes=n_codes, bits=bits, K=K)
    _dequant_parts(kc_ref, ks_ref, cb_ref, kd_ref, valid, **kw)
    _dequant_parts(vc_ref, vs_ref, cb_ref, vd_ref, valid, **kw)
    s = _dot(q_ref[0], kd_ref[0], ((1,), (1,)))            # (T·H, sc)
    for p in range(1, n_parts):
        s += _dot(q_ref[p], kd_ref[p], ((1,), (1,)))

    # per-row query position (row t·H + h belongs to query token t)
    row = jax.lax.broadcasted_iota(jnp.int32, (T * H, 1), 0)
    qpos = jnp.zeros((T * H, 1), jnp.int32)
    for t in range(T):
        qpos = jnp.where((row >= t * H) & (row < (t + 1) * H),
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

    m_prev = m_ref[...]                                     # (T·H, 1)
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


@functools.partial(jax.jit, static_argnames=("ring", "bits", "interpret",
                                             "schunk"))
def decode_attention_quant(q, k_codes, k_scales, v_codes, v_scales,
                           codebook, q_positions, window=0, *,
                           ring: bool = False, bits: int = 8,
                           interpret: bool = False, schunk=None):
    """Masked decode attention straight from quantised cache rows.

    q (B, T, H, hd); codes (B, S, K, hdc) uint8 (hdc = hd, or hd//2 nibble-
    packed for bits=4); scales (B, S, K, 1) f32; q_positions (B, T) int32;
    ``window`` may be a traced scalar (0 = global). Returns (B, T, H, hd)
    in q.dtype — the quantised twin of
    ``models.layers.chunked_decode_attention``."""
    B, T, H, hd = q.shape
    S, K = k_codes.shape[1], k_codes.shape[2]
    G = H // K
    hdc = hd // 2 if bits == 4 else hd
    assert k_codes.shape == (B, S, K, hdc), (k_codes.shape, (B, S, K, hdc))
    assert k_scales.shape == (B, S, K, 1), k_scales.shape
    sc = schunk or choose_schunk(S)
    # query parts: even/odd head-dim elements for nibble-packed rows, laid
    # out head-block diagonal (B, P, T·H, K·w) and pre-scaled by 1/sqrt(hd)
    qf = q.astype(jnp.float32) * hd ** -0.5
    parts = [qf[..., 0::2], qf[..., 1::2]] if bits == 4 else [qf]
    w = hd // len(parts)
    own = (jnp.arange(H) // G)[:, None] == jnp.arange(K)        # (H, K)
    qbd = jnp.stack([jnp.where(own[:, :, None], p[:, :, :, None, :], 0.0)
                     for p in parts], axis=1).reshape(B, len(parts), T * H,
                                                      K * w)
    win = jnp.reshape(jnp.asarray(window, jnp.int32), (1,))
    qp = q_positions.astype(jnp.int32)
    kernel = functools.partial(_kernel, bits=bits, n_codes=codebook.shape[0],
                               sc=sc, S=S, ring=ring, T=T, H=H, K=K)
    P = len(parts)
    tile = lambda b, j, *_: (b, j, 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, pl.cdiv(S, sc)),
            in_specs=[
                pl.BlockSpec((None, P, T * H, K * w),
                             lambda b, j, *_: (b, 0, 0, 0)),
                pl.BlockSpec((None, sc, K * hdc), tile),
                pl.BlockSpec((None, sc, K), tile),
                pl.BlockSpec((None, sc, K * hdc), tile),
                pl.BlockSpec((None, sc, K), tile),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((None, P, T * H, K * w),
                                   lambda b, j, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((T * H, 1), jnp.float32),
                pltpu.VMEM((T * H, 1), jnp.float32),
                pltpu.VMEM((P, T * H, K * w), jnp.float32),
                pltpu.VMEM((P, sc, K * w), jnp.float32),
                pltpu.VMEM((P, sc, K * w), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, P, T * H, K * w), jnp.float32),
        interpret=interpret,
        name="decode_attention",
    )(qp, win, qbd, k_codes.reshape(B, S, K * hdc),
      k_scales.reshape(B, S, K), v_codes.reshape(B, S, K * hdc),
      v_scales.reshape(B, S, K), codebook.astype(jnp.float32))
    # keep each head's own KV-head lane block, re-interleave nibble parts
    heads = jnp.arange(H)
    out = out.reshape(B, P, T, H, K, w)[:, :, :, heads, heads // G]
    return jnp.moveaxis(out, 1, -1).reshape(B, T, H, hd).astype(q.dtype)
