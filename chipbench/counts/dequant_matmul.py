"""``dequant_matmul`` / ``dequant_matmul_t``: x (M, K) times a weight held
as block-scaled codes, K × N of them.

The bytes are what the call has to move at the least: the packed codes
(``bits`` per weight), one bf16 scale per block of ``block`` weights, the
activations in and the products out (bf16), and the codebook. Padding of M
to the kernel's tile is not counted: it is work the call does not need."""
from __future__ import annotations

ACT_BYTES = 2        # bf16 activations in and out
SCALE_BYTES = 2      # one bf16 scale per block


def cost(M: int, K: int, N: int, *, bits: int = 4, block: int = 64,
         n_codes: int = 16) -> tuple:
    """(operations, bytes) of one call."""
    flops = 2 * M * K * N
    nbytes = (K * N * bits // 8 + K * (N // block) * SCALE_BYTES
              + M * K * ACT_BYTES + M * N * ACT_BYTES + 4 * n_codes)
    return flops, nbytes
