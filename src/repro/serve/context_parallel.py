"""Context-parallel flash-decode: attention of one query token against a
sequence-sharded KV cache, combined with the log-sum-exp trick.

For long_500k (batch=1) the KV cache is the entire working set; sharding it
over the data axis turns one 500k-token read into 16 parallel 32k reads.
Each shard computes a *partial* softmax (local max m, local normaliser l,
local weighted values acc); the exact combine is

    m* = max_i m_i ;  l* = Σ_i l_i·e^{m_i−m*} ;  out = Σ_i acc_i·e^{m_i−m*} / l*

— one small all-gather/psum of (m, l, acc) per layer instead of XLA's
default resharding of the whole cache. ``combine_partials`` is the pure
math (unit-tested against single-shard attention); ``cp_decode_attention``
wires it through shard_map.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG = -1e30


def partial_attention(q, k, v, kv_positions, q_position, window=0):
    """One shard's partial attention. q: (B, 1, H, hd); k/v: (B, S_loc, K, hd).
    Returns (m, l, acc): (B, K, G), (B, K, G), (B, K, G, hd)."""
    B, _, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k.astype(qg.dtype))
    s = s.astype(jnp.float32) * hd ** -0.5
    mask = kv_positions <= q_position
    mask &= jnp.where(window > 0, q_position - kv_positions < window, True)
    s = jnp.where(mask[None, None, None, :], s, NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgs,bskh->bkgh", p.astype(v.dtype), v)
    return m, l, acc.astype(jnp.float32)


def combine_partials(m, l, acc):
    """Combine partials along a leading shard axis. m/l: (S, B, K, G);
    acc: (S, B, K, G, hd) → (B, K, G, hd)."""
    m_star = jnp.max(m, axis=0)
    corr = jnp.exp(m - m_star[None])
    l_star = jnp.sum(l * corr, axis=0)
    out = jnp.sum(acc * corr[..., None], axis=0)
    return out / jnp.maximum(l_star[..., None], 1e-30)


def cp_decode_attention(q, k_cache, v_cache, q_position, mesh, seq_axis,
                        window=0):
    """Decode attention with KV sequence sharded over ``seq_axis``.
    q: (B, 1, H, hd) replicated along seq_axis; caches (B, S, K, hd) sharded
    on S. Exact (== unsharded attention) via log-sum-exp combine."""
    from jax.sharding import PartitionSpec as P

    S = k_cache.shape[1]
    n = mesh.shape[seq_axis]
    S_loc = S // n

    def local(q, kl, vl):
        idx = jax.lax.axis_index(seq_axis)
        kv_pos = idx * S_loc + jnp.arange(S_loc)
        m, l, acc = partial_attention(q, kl, vl, kv_pos, q_position, window)
        # gather partials along the seq axis and combine everywhere
        ms = jax.lax.all_gather(m, seq_axis)       # (n, B, K, G)
        ls = jax.lax.all_gather(l, seq_axis)
        accs = jax.lax.all_gather(acc, seq_axis)   # (n, B, K, G, hd)
        return combine_partials(ms, ls, accs)

    B, _, H, hd = q.shape
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, None, None), P(None, seq_axis, None, None),
                  P(None, seq_axis, None, None)),
        out_specs=P(None, None, None, None), check_vma=False,
    )(q, k_cache, v_cache)
    return out.reshape(B, 1, H, hd).astype(q.dtype)
