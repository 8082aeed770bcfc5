"""Shared neural-net layers: RMSNorm, RoPE, flash-style chunked GQA
attention (global + sliding window), SwiGLU MLP and sort-based MoE dispatch.

All functions are pure JAX, pjit-friendly (no host callbacks), and written so
XLA SPMD can shard: heads/mlp/experts dims map to the "model" mesh axis,
batch to ("pod","data").
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tensor_format import PackedTensor
from repro.kernels import ops as kops

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Packed-weight dispatch (the paper's formats as THE projection API)
# ---------------------------------------------------------------------------
#
# `linear(x, w, spec)` is the single way any model family multiplies an
# activation by a parameter. The einsum spec both documents the dense
# semantics and drives the packed dispatch: from the weight's subscripts we
# derive which of its axes contract, and route PackedTensors through the
# fused dequant_matmul kernel — the normal variant when the contraction runs
# along the codes' row (K) axis, the transposed variant when it runs along
# the blocked output axis (tied embeddings: "btd,vd->btv" against the packed
# (V, D) embed table). Dense weights take the exact einsum the call site
# always used (bit-identical path).

@functools.lru_cache(maxsize=None)
def _spec_orientation(spec: str) -> str:
    """Classify the weight operand of ``spec``: do its contracting labels
    lead ("normal", the dequant_matmul codes layout lead+K+out) or trail
    ("transposed", out+K — contraction along the blocked axis)?"""
    ins, out = spec.replace(" ", "").split("->")
    xs, ws = ins.split(",")
    batch = "".join(c for c in ws if c in xs and c in out)
    contract = "".join(c for c in ws if c in xs and c not in out)
    wout = "".join(c for c in ws if c not in xs)
    if not contract:
        raise ValueError(f"no contraction in spec {spec!r}")
    if ws == batch + contract + wout:
        return "normal"
    if ws == batch + wout + contract:
        return "transposed"
    raise ValueError(f"cannot orient weight subscripts in spec {spec!r}")


def linear(x, w, spec: str):
    """``einsum(spec, x, w)`` where ``w`` may be a :class:`PackedTensor`.

    Dense weights take the exact einsum the call site always used
    (bit-identical bf16 path). Packed weights route through the fused
    ``dequant_matmul`` kernel: x is flattened to (B·T, K) and the weight
    stream stays packed codes (nibble-packed bytes for 4-bit formats) +
    block scales end to end. ``x`` must be (B, T, *k_dims) with the trailing
    dims contracting, which covers every projection in the decode path.

    A spec whose weight subscripts end with the contracting labels (e.g.
    ``"btd,vd->btv"``) contracts along the packed tensor's blocked output
    axis and dispatches the transposed kernel — the tied-embeddings unembed
    serves straight from the packed embed table, never materialising
    ``embed.T``."""
    if isinstance(w, PackedTensor):
        B, T = x.shape[0], x.shape[1]
        if _spec_orientation(spec) == "transposed":
            n = int(np.prod(w.out_shape))
            y = kops.dequant_matmul_t(x.reshape(B * T, n), w.codes, w.scales,
                                      w.codebook(), block=w.block, bits=w.bits)
            return y.reshape(B, T, w.k_dim)
        y = kops.dequant_matmul(x.reshape(B * T, w.k_dim), w.codes, w.scales,
                                w.codebook(), block=w.block, bits=w.bits)
        return y.reshape(B, T, *w.out_shape)
    return jnp.einsum(spec, x, w.astype(x.dtype))


def expert_matmul(x, w, spec: str):
    """Per-expert batched matmul: x (E, C, K) against a stacked expert
    weight w (E, K, N) (``spec`` e.g. "ecd,edf->ecf"). Packed expert stacks
    route through ``dequant_matmul``'s leading expert dim — the codes stream
    packed per expert instead of densifying the whole stack. The dispatch
    capacity C is whatever the router chose; the kernel pads rows to its M
    tile internally, so routing semantics stay bit-identical to the dense
    einsum path at any capacity."""
    if isinstance(w, PackedTensor):
        y = kops.dequant_matmul(x, w.codes, w.scales, w.codebook(),
                                block=w.block, bits=w.bits)
        return y.astype(x.dtype)
    return jnp.einsum(spec, x, w.astype(x.dtype))


def embed_lookup(w, tokens, dtype=None):
    """Embedding row gather; packed tables dequantise only the gathered rows
    (codes layout (V, D), scales (V, D//block) — D must tile by block).
    Nibble-packed tables (bits=4) gather the byte row holding each token's
    codes and select the right nibble per row (core.nibble row coords).

    ``dtype``: output dtype (the serving dtype); defaults to the packed
    tensor's own dtype / the dense table's dtype — no silent f32 upcast."""
    if isinstance(w, PackedTensor):
        out_dt = jnp.dtype(dtype if dtype is not None else w.dtype)
        nib = None
        c_rows = tokens
        if w.bits == 4:
            from repro.core.nibble import nibble_row_coords
            c_rows, nib = nibble_row_coords(tokens, w.k_dim)
        c = jnp.take(w.codes, c_rows, axis=0)     # (B, T, D) uint8
        s = jnp.take(w.scales, tokens, axis=0)    # (B, T, D // block)
        return kops.dequant_rows(c, s, w.codebook(), block=w.block,
                                 dtype=out_dt, nibble=nib)
    out = jnp.take(w, tokens, axis=0)
    return out if dtype is None else out.astype(dtype)

# Activation sharding constraint, set by the launcher (dryrun/train drivers).
# XLA SPMD propagates parameter shardings well, but scan-carried activations
# (and their saved-for-backward stacks) need explicit constraints or the
# partitioner may replicate them — 16× memory on the production mesh.
_ACT_BATCH_AXES = None   # e.g. ("pod", "data") or ("data",)
_ACT_SEQ_AXIS = None     # sequence parallelism: shard T between blocks
                         # (Megatron-SP — turns the residual-stream f32
                         # all-reduces into bf16 AG/RS pairs)


def set_activation_sharding(batch_axes, seq_axis=None):
    """batch_axes: tuple of mesh axis names for the batch dim, or None.
    seq_axis: optional mesh axis for sequence parallelism between blocks."""
    global _ACT_BATCH_AXES, _ACT_SEQ_AXIS
    _ACT_BATCH_AXES = tuple(batch_axes) if batch_axes else None
    _ACT_SEQ_AXIS = seq_axis


def constrain_act(x):
    """Constrain a (batch, seq, ...) activation between blocks."""
    if _ACT_BATCH_AXES is None and _ACT_SEQ_AXIS is None:
        return x
    from jax.sharding import PartitionSpec as P
    ax = None
    if _ACT_BATCH_AXES:
        ax = (_ACT_BATCH_AXES[0] if len(_ACT_BATCH_AXES) == 1
              else _ACT_BATCH_AXES)
    seq = _ACT_SEQ_AXIS if (x.ndim >= 3 and _ACT_SEQ_AXIS is not None
                            and x.shape[1] % 16 == 0) else None
    spec = P(ax, seq, *([None] * (x.ndim - 2))) if x.ndim >= 2 \
        else P(ax)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except ValueError:   # no mesh in context (plain CPU tests)
        return x


# Head-dim sharding for attention activations. jit arguments must shard
# evenly, so weights with head counts not divisible by the model axis (e.g.
# llama4's 40 heads on 16) replicate — but GSPMD allows *uneven padded*
# sharding through with_sharding_constraint, so we pin (B, T, H, hd)
# activations to the model axis here and the attention FLOPs spread across
# all chips regardless of divisibility.
_HEAD_AXIS = None


def set_head_axis(axis):
    global _HEAD_AXIS
    _HEAD_AXIS = axis


def constrain_heads(x):
    """x: (B, T, H, hd) — shard H on the model axis (uneven OK)."""
    if _HEAD_AXIS is None or x.shape[-2] <= 1:
        return x
    from jax.sharding import PartitionSpec as P
    bax = None
    if _ACT_BATCH_AXES:
        bax = (_ACT_BATCH_AXES[0] if len(_ACT_BATCH_AXES) == 1
               else _ACT_BATCH_AXES)
    spec = P(bax, *([None] * (x.ndim - 3)), _HEAD_AXIS, None)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except ValueError:
        return x


def rms_norm(x, gain, eps: float = 1e-5, plus_one: bool = False):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    g = gain.astype(jnp.float32)
    if plus_one:
        g = g + 1.0
    return (y * g).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embedding. x: (..., T, n, hd); positions: (..., T)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Quantised KV cache (block-scaled codes + per-row scales)
# ---------------------------------------------------------------------------
#
# A quantised cache group stores K/V as uint8 codebook codes (nibble-packed
# pairwise along the head dim for 4-bit) plus one float32 absmax scale per
# (token, head) row — the paper's block-scaled format with the scale block
# set to head_dim. `QuantisedKV` is a plain pytree, so the pair rides layer
# scans, `lax.switch` branches and the engine's state dict exactly like a
# dense cache array; the cache-side functions below dispatch on it, keeping
# one code path per model family with the dense path untouched.

class QuantisedKV(NamedTuple):
    """One cache stack's quantised storage: codes (..., S, K, hdc) uint8 +
    scales (..., S, K, 1) float32 (hdc = hd, or hd // 2 nibble-packed)."""
    codes: jnp.ndarray
    scales: jnp.ndarray


def codebook_bits(codebook) -> int:
    """Code width implied by a KV codebook (16 codes → 4-bit nibble-packed,
    256 → 8-bit). Static: codebook shapes are trace-time constants."""
    n = codebook.shape[0]
    if n == 16:
        return 4
    if n == 256:
        return 8
    raise ValueError(f"KV codebook must have 16 or 256 codes, got {n}")


def quantise_kv(new, codebook, bits: int):
    """Quantise fresh K or V rows (B, T, K, hd) through the block_quant
    machinery (absmax per (token, head) row → bf16 round-away scale →
    round-to-nearest codebook index). Returns (codes (B, T, K, hdc) uint8,
    scales (B, T, K, 1) f32); 4-bit codes nibble-pack pairwise along hd
    (byte j = element 2j low | element 2j+1 high), so each row is
    self-contained and ring writes never read-modify-write."""
    B, T, K, hd = new.shape
    rows = B * T * K
    x = new.astype(jnp.float32).reshape(rows, hd)
    pad = (-rows) % 256 if rows > 256 else 0   # block_quant row-tile pad
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    codes, scales = kops.block_quant(x, codebook, block=hd)
    codes = codes[:rows].reshape(B, T, K, hd)
    scales = scales[:rows].reshape(B, T, K, 1)
    if bits == 4:
        codes = codes[..., 0::2] | (codes[..., 1::2] << jnp.uint8(4))
    return codes, scales


def dequant_kv(cache: QuantisedKV, codebook, dtype=jnp.float32):
    """Densify a quantised cache stack (tests / oracle paths only — the
    serving read path streams codes through the fused kernel instead)."""
    return kops.dequant_kv(cache.codes, cache.scales, codebook,
                           bits=codebook_bits(codebook), dtype=dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

class AttnParams(NamedTuple):
    wq: jnp.ndarray   # (D, H, hd)
    wk: jnp.ndarray   # (D, K, hd)
    wv: jnp.ndarray   # (D, K, hd)
    wo: jnp.ndarray   # (H, hd, D)
    q_norm: Optional[jnp.ndarray] = None  # (hd,)
    k_norm: Optional[jnp.ndarray] = None


def qkv_project(x, p: AttnParams, positions, cfg, rope_on: bool = True):
    q = linear(x, p.wq, "btd,dnh->btnh")
    k = linear(x, p.wk, "btd,dnh->btnh")
    v = linear(x, p.wv, "btd,dnh->btnh")
    if cfg.qk_norm and p.q_norm is not None:
        q = rms_norm(q, p.q_norm, cfg.norm_eps)
        k = rms_norm(k, p.k_norm, cfg.norm_eps)
    if rope_on:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return constrain_heads(q), constrain_heads(k), constrain_heads(v)


def flash_attention(q, k, v, q_positions, k_positions, *, causal: bool = True,
                    window: jnp.ndarray | int = 0, chunk: int = 1024,
                    k_valid_len=None, scale=None):
    """Chunked online-softmax attention (memory O(Tq·chunk), never
    materialises the full score matrix — required for the 32k cells).

    q: (B, Tq, H, hd) with H = K·G;  k, v: (B, Tk, K, hd)
    window: 0 = global; >0 = sliding window (only keys within `window`).
            May be a traced scalar (per-layer pattern scanning).
    k_valid_len: optional (B,) or scalar count of valid keys (padding mask).
    scale: the score scale, default hd ** -0.5.
    """
    B, Tq, H, hd = q.shape
    Tk, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, Tq, K, G, hd)
    scale = hd ** -0.5 if scale is None else scale

    chunk = min(chunk, Tk)
    pad = (-Tk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=2**30)
    n_chunks = (Tk + pad) // chunk
    ks = k.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, n_chunks, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    kpos = k_positions.reshape(n_chunks, chunk)

    def body(carry, inputs):
        m, l, acc = carry
        kc, vc, kp = inputs
        s = jnp.einsum("btkgh,bskh->btkgs", qg, kc.astype(qg.dtype)) * scale
        s = s.astype(jnp.float32)
        mask = jnp.ones((Tq, chunk), bool)
        if causal:
            mask &= q_positions[:, None] >= kp[None, :]
        mask &= jnp.where(window > 0,
                          q_positions[:, None] - kp[None, :] < window, True)
        if k_valid_len is not None:
            mask &= (kp < k_valid_len)[None, :]
        mask &= (kp < 2**30)[None, :]  # padding
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "btkgs,bskh->btkgh", p.astype(vc.dtype), vc).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Tq, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Tq, K, G), jnp.float32)
    a0 = jnp.zeros((B, Tq, K, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kpos))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Tq, H, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, q_position, *, window=0,
                     kv_positions=None, ring=False, codebook=None):
    """Single-token attention against a KV cache (no chunking needed: the
    score tensor is (B, H, S) which is small for decode).

    q: (B, 1, H, hd); caches: (B, S, K, hd); q_position: scalar current pos.
    ``ring=True``: the cache is a ring buffer written at ``pos % S`` — slot
    positions are reconstructed from ``q_position`` (the highest written
    position) instead of being the slot index; negative reconstructions
    (never-written slots) are masked.

    :class:`QuantisedKV` caches (with their ``codebook``) route through the
    fused quantised flash-decode kernel — codes stream from HBM and
    dequantise in VMEM, never materialising a dense cache.
    """
    if isinstance(k_cache, QuantisedKV):
        assert kv_positions is None, \
            "quantised caches reconstruct slot positions in-kernel"
        qpos = jnp.broadcast_to(jnp.asarray(q_position, jnp.int32),
                                (q.shape[0],))[:, None]
        return kops.decode_attention_quant(
            q, k_cache.codes, k_cache.scales, v_cache.codes, v_cache.scales,
            codebook, qpos, window, ring=ring, bits=codebook_bits(codebook))
    B, _, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache.astype(qg.dtype))
    s = s.astype(jnp.float32) * hd ** -0.5
    if ring:
        from repro.serve.cache import ring_positions
        kv_positions = ring_positions(jnp.asarray(q_position, jnp.int32), S)
        mask = (kv_positions <= q_position) & (kv_positions >= 0)
    else:
        if kv_positions is None:
            kv_positions = jnp.arange(S)
        mask = kv_positions <= q_position
    mask &= jnp.where(window > 0, q_position - kv_positions < window, True)
    s = jnp.where(mask[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def chunked_decode_attention(q, k_cache, v_cache, q_positions, *, window=0,
                             ring=False, codebook=None, scale=None):
    """Multi-token decode attention with **per-slot** positions: a chunk of
    T query tokens per batch row against that row's KV cache. Used for both
    single-token decode (T=1) and batched chunked prefill — slots need not
    be in lockstep.

    q: (B, T, H, hd); caches: (B, S, K, hd) — or :class:`QuantisedKV`
    (block-scaled codes + scales, with their ``codebook``), which routes
    through the fused quantised flash-decode kernel with identical
    ring/window/causal mask semantics; q_positions: (B, T) absolute
    positions of the query tokens (the new tokens' k/v must already be
    written into the cache at those positions).

    ``ring=True`` (windowed layers): the cache is a ring buffer written at
    ``pos % S``. Each row's slot positions are reconstructed from its
    highest written position (``q_positions[:, -1]`` — chunk writes always
    cover the query positions), making the causal/window masks wrap-correct
    with no stored per-slot positions: a slot overwritten by a later wrap
    reconstructs to its new position (masked causally until that position
    is queried, by which point the content is real — write-before-read),
    and never-written slots reconstruct negative. Requires
    ``S ≥ window + T - 1`` so ragged-chunk padding writes only clobber
    keys already outside every reachable window (see serve.cache).
    ``scale`` is the score scale, default ``hd ** -0.5``."""
    if isinstance(k_cache, QuantisedKV):
        return kops.decode_attention_quant(
            q, k_cache.codes, k_cache.scales, v_cache.codes, v_cache.scales,
            codebook, q_positions, window, ring=ring,
            bits=codebook_bits(codebook), scale=scale)
    B, T, H, hd = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    qg = q.reshape(B, T, K, G, hd)
    s = jnp.einsum("btkgh,bskh->btkgs", qg, k_cache.astype(qg.dtype))
    s = s.astype(jnp.float32) * (hd ** -0.5 if scale is None else scale)
    if ring:
        from repro.serve.cache import ring_positions
        kv = ring_positions(q_positions[:, -1], S)                # (B, S)
        mask = kv[:, None, :] <= q_positions[:, :, None]          # causal
        mask &= q_positions[:, :, None] - kv[:, None, :] < window
        mask &= kv[:, None, :] >= 0                               # unwritten
    else:
        kv = jnp.arange(S)
        mask = kv[None, None, :] <= q_positions[:, :, None]       # causal
        mask &= jnp.where(window > 0,
                          q_positions[:, :, None] - kv[None, None, :] < window,
                          True)
    s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("btkgs,bskh->btkgh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, T, H, hd).astype(q.dtype)


def update_kv_cache(cache, new, pos, *, ring=False, codebook=None):
    """Write T new entries per batch row at that row's own position.
    cache: (B, S, K, hd); new: (B, T, K, hd); pos: (B,) int32.
    ``ring=True`` writes at ``(pos + t) % S`` (rolling-window buffers;
    the scatter indices are distinct because T ≤ S always holds — ring
    length ≥ window + chunk - 1).

    A :class:`QuantisedKV` cache quantises the fresh rows at write time
    (``codebook`` required) and scatters codes + scales with the same
    index math — writes stay inside the jitted step and each (token, head)
    row is self-contained, so ragged/ring overwrites behave exactly like
    the dense path."""
    if isinstance(cache, QuantisedKV):
        codes, scales = quantise_kv(new, codebook, codebook_bits(codebook))
        return QuantisedKV(
            _kv_scatter(cache.codes, codes, pos, ring),
            _kv_scatter(cache.scales, scales, pos, ring))
    return _kv_scatter(cache, new, pos, ring)


def _kv_scatter(cache, new, pos, ring):
    if not ring:
        return jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(
                c, n, p, axis=0))(cache, new.astype(cache.dtype), pos)
    from repro.serve.cache import ring_slots
    S, T = cache.shape[1], new.shape[1]
    idx = ring_slots(pos[:, None] + jnp.arange(T, dtype=pos.dtype), S)
    return jax.vmap(lambda c, n, i: c.at[i].set(n))(
        cache, new.astype(cache.dtype), idx)


def attn_block(x, p: AttnParams, positions, cfg, window=0):
    """Full training/prefill attention block (pre-norm residual handled by
    the caller)."""
    q, k, v = qkv_project(x, p, positions, cfg)
    o = flash_attention(q, k, v, positions, positions, causal=True,
                        window=window, chunk=cfg.attn_chunk)
    o = constrain_heads(o)
    return linear(o, p.wo, "btnh,nhd->btd")


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

class MlpParams(NamedTuple):
    w_gate: jnp.ndarray  # (D, F)
    w_up: jnp.ndarray    # (D, F)
    w_down: jnp.ndarray  # (F, D)


def swiglu(x, p: MlpParams):
    g = linear(x, p.w_gate, "btd,df->btf")
    u = linear(x, p.w_up, "btd,df->btf")
    h = jax.nn.silu(g) * u
    return linear(h, p.w_down, "btf,fd->btd")


def gelu_gated_mlp(x, w_gate_up, w_down, adapter=None):
    """``down(gelu(g) * u)`` (exact, erf gelu) with ``[g, u] = x @
    w_gate_up`` (one (D, 2F) weight, gate half first) plus, when ``adapter
    = (A, B)`` is given, the low-rank ``(x @ A) @ B`` added to both halves
    before the gate (the per-application LoRA of Zamba2's shared MLP)."""
    gu = linear(x, w_gate_up, "btd,df->btf")
    if adapter is not None:
        a, b = adapter
        gu = gu + linear(linear(x, a, "btd,dr->btr"), b, "btr,rf->btf")
    g, u = jnp.split(gu, 2, axis=-1)
    return linear(jax.nn.gelu(g, approximate=False) * u, w_down,
                  "btf,fd->btd")


def gelu_mlp(x, w_in, w_out):
    h = jax.nn.gelu(linear(x, w_in, "btd,df->btf"))
    return linear(h, w_out, "btf,fd->btd")


class MoeParams(NamedTuple):
    w_router: jnp.ndarray   # (D, E)
    w_gate: jnp.ndarray     # (E, D, F)
    w_up: jnp.ndarray       # (E, D, F)
    w_down: jnp.ndarray     # (E, F, D)
    shared: Optional[MlpParams] = None


# Expert-parallel execution context, set by the launcher (like activation
# sharding). When set, moe_block runs under shard_map: experts are owned by
# model-axis shards, activations (replicated across the model axis, sharded
# by batch on the data axes) are routed locally, and expert outputs combine
# with one psum over the model axis — the same collective cost as a dense
# tensor-parallel MLP, versus the global-sort dispatch XLA cannot partition.
_EP_MESH = None  # (mesh, batch_axes tuple, model_axis)


def set_ep_mesh(mesh, batch_axes, model_axis="model"):
    global _EP_MESH
    _EP_MESH = (mesh, tuple(batch_axes) if batch_axes else (),
                model_axis) if mesh is not None else None


_EP_PACKED_FALLBACK_LOGGED = False


def moe_block(x, p: MoeParams, cfg):
    # Packed expert stacks serve through the local sort-dispatch path (the
    # EP shard_map path pads/casts expert weights, which would densify the
    # codes; packed EP is a recorded follow-up). Packability is decided per
    # tensor (output dim must tile by the scale block), so gate/up/down may
    # mix packed and dense — any packed stack forces the local path.
    packed = any(isinstance(w, PackedTensor)
                 for w in (p.w_gate, p.w_up, p.w_down))
    if _EP_MESH is not None and not packed:
        return moe_block_ep(x, p, cfg)
    if _EP_MESH is not None and packed:
        global _EP_PACKED_FALLBACK_LOGGED
        if not _EP_PACKED_FALLBACK_LOGGED:
            _EP_PACKED_FALLBACK_LOGGED = True
            print("[moe] packed expert stacks: EP shard_map path falls back "
                  "to local sort-dispatch (packed expert-parallel dispatch "
                  "is a recorded follow-up)")
    return _moe_block_local(x, p, cfg)


def _moe_block_local(x, p: MoeParams, cfg):
    """Top-k routed experts with sort-based capacity dispatch (TPU-native:
    gather/scatter + dense per-expert einsums; expert axis shards to the
    'model' mesh axis for EP). Dropped tokens (over capacity) fall through
    to the residual (plus shared experts if configured)."""
    B, T, D = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    N = B * T
    xt = x.reshape(N, D)
    logits = linear(xt[None], p.w_router, "btd,de->bte")[0]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, choice = jax.lax.top_k(probs, k)          # (N, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)          # renormalise
    expert_flat = choice.reshape(-1)                     # (N·k,)
    cap = int(np.ceil(cfg.capacity_factor * k * N / E))
    cap = max(cap, 4)

    # rank of each dispatch within its expert (stable sort by expert id)
    order = jnp.argsort(expert_flat, stable=True)
    sorted_e = expert_flat[order]
    # start offset of each expert group in the sorted order
    starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    rank_sorted = jnp.arange(N * k) - starts[sorted_e]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    keep = rank < cap
    safe_rank = jnp.where(keep, rank, cap - 1)

    # dispatch: (E, cap, D)
    tok_idx = jnp.arange(N * k) // k
    contrib = jnp.where(keep[:, None], xt[tok_idx], 0.0)
    buf = jnp.zeros((E, cap, D), x.dtype).at[expert_flat, safe_rank].add(contrib)

    # per-expert SwiGLU (expert stacks may be PackedTensors: the codes
    # stream per expert through dequant_matmul's leading dim)
    g = expert_matmul(buf, p.w_gate, "ecd,edf->ecf")
    u = expert_matmul(buf, p.w_up, "ecd,edf->ecf")
    h = jax.nn.silu(g) * u
    y = expert_matmul(h, p.w_down, "ecf,efd->ecd")

    # combine: gather back and weight by the (renormalised) gate
    y_tok = y[expert_flat, safe_rank]                    # (N·k, D)
    w = jnp.where(keep, gate_vals.reshape(-1), 0.0).astype(x.dtype)
    out = jnp.zeros((N, D), x.dtype).at[tok_idx].add(y_tok * w[:, None])

    aux = router_load_balancing_loss(probs, choice, E)
    out = out.reshape(B, T, D)
    if p.shared is not None:
        out = out + swiglu(x, p.shared)
    return out, aux


def moe_block_ep(x, p: MoeParams, cfg):
    """shard_map expert parallelism. Expert weights are padded to a multiple
    of the model-axis size (dummy experts get -inf router logits) and owned
    by model shards; every shard routes its (replicated-over-model) local
    tokens to its own experts; outputs psum over the model axis."""
    from jax.sharding import PartitionSpec as P

    mesh, batch_axes, model_ax = _EP_MESH
    M = mesh.shape[model_ax]
    E, k = cfg.n_experts, cfg.experts_per_token
    E_pad = ((E + M - 1) // M) * M
    # cast to compute dtype BEFORE shard_map: the E/D resharding then moves
    # bf16, not f32 master weights (2x less reshard traffic)
    cast = lambda w: w.astype(x.dtype)
    if E_pad != E:
        padw = lambda w: jnp.pad(cast(w),
                                 ((0, E_pad - E),) + ((0, 0),) * (w.ndim - 1))
        w_gate, w_up, w_down = padw(p.w_gate), padw(p.w_up), padw(p.w_down)
        w_router = jnp.pad(cast(p.w_router), ((0, 0), (0, E_pad - E)))
    else:
        w_gate, w_up, w_down, w_router = (cast(p.w_gate), cast(p.w_up),
                                          cast(p.w_down), cast(p.w_router))

    B, T, D = x.shape
    bax = batch_axes[0] if len(batch_axes) == 1 else (batch_axes or None)
    x_spec = P(bax, None, None) if batch_axes else P(None, None, None)

    def local(xl, wr, wg, wu, wd):
        """xl: (B_loc, T, D); wg/wu/wd: (E_loc, D, F); wr: (D, E_pad)."""
        Bl, Tl, Dl = xl.shape
        N = Bl * Tl
        E_loc = wg.shape[0]
        xt = xl.reshape(N, Dl)
        logits = linear(xt[None], wr, "btd,de->bte")[0]
        logits = logits.astype(jnp.float32)
        if E_pad != E:  # mask dummy experts
            mask = (jnp.arange(E_pad) < E)
            logits = jnp.where(mask[None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, choice = jax.lax.top_k(probs, k)              # (N, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        # my expert slice: [m*E_loc, (m+1)*E_loc)
        m_idx = jax.lax.axis_index(model_ax)
        e_lo = m_idx * E_loc
        flat_choice = choice.reshape(-1)                         # (N*k,)
        local_e = flat_choice - e_lo
        mine = (local_e >= 0) & (local_e < E_loc)
        local_e = jnp.clip(local_e, 0, E_loc - 1)
        cap = max(int(np.ceil(cfg.capacity_factor * k * N / E)), 4)
        # rank within local expert via stable sort
        order = jnp.argsort(jnp.where(mine, local_e, E_loc), stable=True)
        sorted_e = jnp.where(mine, local_e, E_loc)[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(E_loc), side="left")
        rank_sorted = jnp.arange(N * k) - starts[jnp.clip(sorted_e, 0, E_loc - 1)]
        rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
        keep = mine & (rank < cap)
        safe_rank = jnp.where(keep, rank, cap - 1)
        tok_idx = jnp.arange(N * k) // k
        contrib = jnp.where(keep[:, None], xt[tok_idx], 0.0)
        buf = jnp.zeros((E_loc, cap, Dl), xl.dtype).at[
            local_e, safe_rank].add(contrib)
        dt = xl.dtype
        g = expert_matmul(buf, wg, "ecd,edf->ecf")
        u = expert_matmul(buf, wu, "ecd,edf->ecf")
        h = jax.nn.silu(g) * u
        y = expert_matmul(h, wd, "ecf,efd->ecd")
        y_tok = y[local_e, safe_rank]
        w = jnp.where(keep, gate_vals.reshape(-1), 0.0).astype(dt)
        out = jnp.zeros((N, Dl), dt).at[tok_idx].add(y_tok * w[:, None])
        out = jax.lax.psum(out, model_ax)                        # combine
        aux = router_load_balancing_loss(probs[:, :E], choice, E)
        aux = jax.lax.pmean(aux, model_ax)
        for ax in batch_axes:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(Bl, Tl, Dl), aux

    smap = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(model_ax, None, None),
                  P(model_ax, None, None), P(model_ax, None, None)),
        out_specs=(x_spec, P()), check_vma=False)
    out, aux = smap(x, w_router, w_gate, w_up, w_down)
    if p.shared is not None:
        out = out + swiglu(x, p.shared)
    return out, aux


def router_load_balancing_loss(probs, choice, E):
    """Switch-style auxiliary loss: E * Σ_e f_e · P_e."""
    onehot = jax.nn.one_hot(choice[:, 0], E, dtype=jnp.float32)
    f = onehot.mean(0)
    pbar = probs.mean(0)
    return E * jnp.sum(f * pbar)


def causal_conv1d(x, w, state=None, n_valid=None, bias=None):
    """Depthwise causal conv over time. x: (B, T, C); w: (Kw, C); ``bias``
    (C,) is added to the output.
    With ``state`` ((B, Kw-1, C)) performs streaming decode; returns
    (y, new_state). ``n_valid`` ((B,) int32) marks how many leading tokens
    of each row are real (ragged chunks): the new state is then the Kw-1
    inputs preceding each row's valid prefix end, so padding tokens never
    enter the streaming state (a row with n_valid=0 keeps its state)."""
    Kw = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (Kw - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(Kw))
    if bias is not None:
        y = y + bias
    if Kw <= 1:
        new_state = None
    elif n_valid is None:
        new_state = xp[:, -(Kw - 1):, :]
    else:
        # row b's state = xp[b, n_valid[b] : n_valid[b] + Kw-1]
        new_state = jax.vmap(
            lambda xr, p: jax.lax.dynamic_slice_in_dim(xr, p, Kw - 1,
                                                       axis=0))(xp, n_valid)
    return y.astype(x.dtype), new_state
