"""repro.kernels — Pallas TPU kernels for the paper's compute hot-spots.

  block_quant       fused block-absmax quantise (codes + scales in one pass)
  dequant_matmul    fused dequantise @ x — the memory-bound serving matmul
  decode_attention  fused quantised-KV flash-decode attention: block-scaled
                    q8/q4 cache codes dequantise in VMEM after the HBM read,
                    inside an online-softmax sweep with the serving path's
                    ring/window/causal mask semantics

Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd wrapper
with CPU fallback), ref.py (pure-jnp oracle). The kernel bodies are checked
against the oracles in interpret mode on the CPU, compiled for a described
TPU v5e by tests/test_tpu_compile.py, and run on the chip by chipbench/.
"""
from . import ops  # noqa: F401
