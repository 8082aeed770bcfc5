"""repro.configs — assigned architectures (``--arch <id>``) + shapes.

Each module exposes ``full()`` (the exact published config) and ``smoke()``
(a reduced same-family config for CPU tests)."""
from __future__ import annotations

from . import (deepseek_7b, gemma3_1b, internlm2_20b, internvl2_26b,
               llama3_405b, llama4_scout_17b_a16e, paper_100m, qwen2_moe_a2_7b,
               rwkv6_1_6b, whisper_large_v3, zamba2_2_7b, zamba2_7b)
from . import shapes
from .shapes import SHAPES, Shape, applicable, input_specs, smoke_shape

_MODULES = [
    llama4_scout_17b_a16e, qwen2_moe_a2_7b, llama3_405b, internlm2_20b,
    gemma3_1b, deepseek_7b, rwkv6_1_6b, whisper_large_v3, internvl2_26b,
    zamba2_2_7b, paper_100m, zamba2_7b,
]

ARCHS = {m.ARCH_ID: m for m in _MODULES}
# the architectures the reproduction was assigned; paper-100m is the
# paper's own model and zamba2-7b a benchmark configuration
ASSIGNED = [m.ARCH_ID for m in _MODULES if m not in (paper_100m, zamba2_7b)]


def get_config(arch_id: str, variant: str = "full"):
    mod = ARCHS[arch_id]
    return getattr(mod, variant)()


__all__ = ["ARCHS", "ASSIGNED", "SHAPES", "Shape", "applicable",
           "get_config", "input_specs", "smoke_shape", "shapes"]
