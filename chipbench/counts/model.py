"""Operations of a whole model step, and the least time of its kernel
calls, from the reference's shapes and one step's record (``T`` tokens per
slot, ``valid`` tokens processed, ``rows`` cache rows held and ``qrows``
query-row pairs, summed over slots)."""
from __future__ import annotations

from chipbench.counts import decode_attention, dequant_matmul


def matmul_flops_per_token(ref, model: dict) -> int:
    return sum(2 * n * K * N for n, K, N in ref.matmuls(model))


def step_flops(ref, model: dict, step: dict) -> float:
    """Operations the step's valid tokens need: every weight product, and
    attention over the rows each query sees."""
    flops = step["valid"] * matmul_flops_per_token(ref, model)
    att = ref.attention(model)
    if att is not None:
        flops += (4 * att["n_layers"] * att["n_heads"] * att["head_dim"]
                  * step["qrows"])
    return flops


def _least(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def matmul_least_s(ref, config: dict, step: dict, peaks: dict) -> float:
    """Least time of the step's packed-weight products, one call per weight
    matrix and layer, at M = slots x T rows."""
    w = config["weights"]
    M = step["slots"] * step["T"]
    total = 0.0
    for n, K, N in ref.matmuls(config["model"]):
        f, b = dequant_matmul.cost(M, K, N, block=w["block"],
                                   n_codes=len(w["codepoints"]))
        total += n * _least(f, b, peaks)
    return total


KV_BITS = {"q8": 8, "q4": 4}


def attention_least_s(ref, config: dict, step: dict, peaks: dict) -> float:
    """Least time of the step's quantised-cache attention, one call per
    layer over the rows the slots hold."""
    att = ref.attention(config["model"])
    f, b = decode_attention.cost(
        step["rows"], step["qrows"], step["slots"], step["T"],
        n_heads=att["n_heads"], n_kv_heads=att["n_kv_heads"],
        head_dim=att["head_dim"],
        code_bits=KV_BITS[config["model"]["kv_format"]])
    return att["n_layers"] * _least(f, b, peaks)
