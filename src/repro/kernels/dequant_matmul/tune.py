"""Tile + strategy selection for the fused dequant matmuls.

``choose_tiles(M, K, N, bits)`` answers two questions the kernel used to
hard-code: *which strategy* (the exact f32 ``decode`` feed or the one-pass
bf16 ``lut`` feed — see ``dequant_matmul``) and *which tile shape*
``(tm, tk, tn)``.

Both answers come from a small analytic roofline rather than guesswork:
per legal tile candidate we estimate the HBM stream (packed code bytes, the
whole scale rows each output-column sweep re-reads, and the activation
re-reads), the VPU work of the codebook select tree, and the MXU work of the
scale expansion plus the main matmul (multi-pass for the f32 feed), and take
the cheapest. The model is deliberately coarse — its job is to rank tile
shapes, not predict microseconds, and none of its terms has been measured
on the chip.

Resolved choices land in ``_TABLE``, an in-process tuning cache keyed by
``(M, K, N, bits, n_codes, block)``: each distinct matmul geometry pays the
candidate sweep once per process, and entries can be pre-seeded (or
overridden, e.g. from a measured autotune sweep) via :func:`register`.

Hard layout constraints the candidate sweep respects:

* ``bits=4``: the K tile is **locked** to the ``core.nibble`` interleave
  tile — the in-VMEM unpack (mask/shift per row chunk) is only valid on a
  whole interleave tile, so ``tk`` is not free.
* ``tn`` must be a multiple of the scale block and either a multiple of
  128 lanes or the whole N (the TPU (8, 128) block rule); ``tm``/``tk``/
  ``tn`` must divide the (M-padded) operand shapes; every operand tile must
  fit VMEM.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

from repro.core.nibble import nibble_k_tile

BLOCK = 128

# coarse TPU v5e model (only *ratios* drive choices): 197 TFLOP/s bf16 MXU,
# 819 GB/s HBM (Google Cloud "TPU v5e" documentation); the VPU as 4 ALU
# slots × 1024 lanes at the MXU's 1.5 GHz clock
PEAK_FLOPS = 197e12
VPU_OPS = 4 * 1024 * 1.5e9
HBM_BW = 819e9
F32_PASSES = 6               # fp32-precision contraction as bf16 MXU passes
VMEM_BUDGET = 8 * 2 ** 20    # per-call operand budget (half the scoped VMEM)


def lookup_ops(n_codes: int, bits: int) -> float:
    """VPU ops per weight element: the select tree (one select per
    codepoint, one shift/and/compare per code bit), nibble unpack, int
    conversion and the scale multiply."""
    depth = max(1, (n_codes - 1).bit_length())
    return (n_codes - 1) + 3 * depth + (2 if bits == 4 else 0) + 2


class TileChoice(NamedTuple):
    tm: int
    tk: int
    tn: int
    decode: bool  # True: exact f32 feed; False: one-pass bf16 ("lut") feed


_TABLE: Dict[Tuple[int, int, int, int, int, int], TileChoice] = {}


def register(M: int, K: int, N: int, bits: int, choice: TileChoice,
             n_codes: int = 16, block: int = BLOCK) -> None:
    """Pre-seed (or override) the tuning table for one matmul geometry."""
    _TABLE[(M, K, N, bits, n_codes, block)] = choice


def _pad_up(x: int, m: int) -> int:
    return x + (-x) % m


def estimate(M: int, K: int, N: int, bits: int, tm: int, tk: int, tn: int,
             n_codes: int, decode: bool, block: int = BLOCK) -> dict:
    """Roofline terms for one (tiles, strategy) candidate.

    Returns a dict of byte/flop terms plus ``time`` (seconds, coarse);
    :func:`choose_tiles` ranks by ``time``."""
    Mp = _pad_up(M, tm)
    m_sweeps = Mp // tm           # times the full weight stream is read
    n_sweeps = N // tn            # times x and the scale rows are re-read
    code_bytes = K * N * bits // 8 * m_sweeps
    scale_bytes = K * (N // block) * 2 * n_sweeps * m_sweeps
    x_bytes = Mp * K * 2 * n_sweeps
    out_bytes = Mp * N * 2
    hbm = code_bytes + scale_bytes + x_bytes + out_bytes
    dequant_ops = K * N * lookup_ops(n_codes, bits) * m_sweeps
    # scale expansion: whole scale rows (N // block wide) @ a 0/1 matrix
    expand_flops = 2 * K * N * (N // block) * m_sweeps
    matmul_flops = 2 * Mp * K * N
    # the f32 feed costs F32_PASSES MXU passes: the term that hands large-M
    # (prefill) shapes to the one-pass bf16 feed
    passes = F32_PASSES if decode else 1
    mxu_time = (expand_flops + matmul_flops * passes) / PEAK_FLOPS
    dequant_time = dequant_ops / VPU_OPS
    time = max(hbm / HBM_BW, dequant_time, mxu_time)
    return {"hbm_bytes": hbm, "code_bytes": code_bytes,
            "scale_bytes": scale_bytes, "dequant_ops": dequant_ops,
            "matmul_flops": matmul_flops, "dequant_time": dequant_time,
            "mxu_time": mxu_time, "time": time}


def _vmem_ok(tm: int, tk: int, tn: int, N: int, bits: int,
             block: int) -> bool:
    codes = tk * bits // 8 * tn
    scales = tk * 128 * 2            # whole scale rows, lane-padded
    expand = (N // block) * tn * 4
    x = tm * tk * 4
    w = tk * tn * 4                  # dequantised tile scratch
    acc = tm * tn * 4
    return 2 * (codes + scales + x) + expand + w + acc <= VMEM_BUDGET


def choose_tiles(M: int, K: int, N: int, bits: int, n_codes: int = 16,
                 block: int = BLOCK) -> TileChoice:
    """Pick (tm, tk, tn, decode) for one matmul geometry, cached.

    M is the *logical* row count — callers pad M up to ``tm`` (the kernel
    wrappers do this; no tile needs to divide the raw M)."""
    key = (M, K, N, bits, n_codes, block)
    hit = _TABLE.get(key)
    if hit is not None:
        return hit
    if bits == 4:
        tks = [nibble_k_tile(K)]  # layout-locked to the nibble interleave
    else:
        tks = [t for t in (512, 256, 128) if K % t == 0] or [K]
    tms = sorted({min(t, _pad_up(M, 8)) for t in (8, 16, 32, 64, 128)})
    tns = {t for t in (512, 256, 128) if N % t == 0 and t % block == 0}
    tns.add(N)                    # whole rows: no scale re-reads
    best, best_t = None, None
    for tm in tms:
        for tk in tks:
            for tn in sorted(tns):
                if not _vmem_ok(tm, tk, tn, N, bits, block):
                    continue
                # decode first: an exact tie keeps the exact f32 feed; at
                # equal time (the VPU bound) the smaller HBM stream wins
                for decode in (True, False):
                    e = estimate(M, K, N, bits, tm, tk, tn, n_codes, decode,
                                 block)
                    rank = (e["time"], e["hbm_bytes"])
                    if best is None or rank < best:
                        best = rank
                        best_t = TileChoice(tm, tk, tn, decode)
    assert best_t is not None, (M, K, N, bits)
    _TABLE[key] = best_t
    return best_t
