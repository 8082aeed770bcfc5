"""``decode_attention``: T query tokens per slot against that slot's
block-quantised K/V cache, one call per layer.

Only the rows the slots have written are needed (``rows``, summed over
slots), so the bytes count those rows' codes and scales and not the
allocated cache: a kernel that reads only the live rows can reach 100% of
this roofline, and one that sweeps the whole allocation reads less.
Operations are the two products, q.k and p.v, of every head, for each
valid query over the rows it sees (``qrows``, summed over slots)."""
from __future__ import annotations

QO_BYTES = 2         # bf16 queries in and outputs out
SCALE_BYTES = 4      # one float32 scale per (row, KV head)


def cost(rows: int, qrows: int, slots: int, T: int, *, n_heads: int,
         n_kv_heads: int, head_dim: int, code_bits: int = 8) -> tuple:
    """(operations, bytes) of one call."""
    flops = 2 * 2 * n_heads * head_dim * qrows
    kv_row = n_kv_heads * (head_dim * code_bits // 8 + SCALE_BYTES)
    nbytes = 2 * rows * kv_row + 2 * slots * T * n_heads * head_dim * QO_BYTES
    return flops, nbytes
