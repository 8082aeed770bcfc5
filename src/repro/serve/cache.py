"""Decode-cache subsystem: per-layer-group KV specs with ring buffers.

The flat ``(L, B, kv_len, K, hd)`` KV allocation wastes memory on
local-attention layers: a layer with sliding window ``W`` only ever attends
the last ``W`` keys, yet the uniform cache gives it the full ``kv_len``
rows and masks the rest. With weights served packed (~0.133× the f32
master), the KV cache dominates resident memory at serving batch sizes —
so local layers here allocate a **ring buffer** of ``W + slack`` slots and
write at ``pos % length``, while global layers keep the full length.

``CacheGroup`` describes one window-homogeneous group of layers (same
window ⇒ same allocated length ⇒ one stacked cache array); ``CacheSpec``
is a model's full self-attention cache geometry and turns into state specs
(``k{g}``/``v{g}`` per group, the grouped decode-state protocol of
``repro.models.api``) and into byte accounting (``cache_bytes``, with the
uniform allocation as the baseline so the rolling-window saving is a
measured number).

Ring-buffer correctness (the helpers below are the single source of the
index math — ``models.layers`` reconstructs positions the same way):

* slot for absolute position ``p`` is ``p % length`` (:func:`ring_slots`);
* given the highest position written so far ``last``, slot ``s`` holds
  position ``last - ((last - s) % length)`` — the most recent position
  ≤ ``last`` congruent to ``s``; a negative value means the slot was never
  written (:func:`ring_positions`). Attention masks are built from these
  reconstructed positions, so wrap-around needs no extra bookkeeping.
* chunked prefill may write up to ``chunk`` tokens past a row's valid
  prefix (ragged padding), and those writes overwrite the oldest ring
  slots. ``length ≥ window + chunk - 1`` guarantees everything clobbered
  is already outside every reachable query's window — the engine passes
  ``slack = prefill_chunk``, satisfying it with a slot to spare.

The same geometry with ``windowed=False`` allocates every group at the
full length: the masked-full-cache baseline the ring path must match
bit-for-bit on greedy tokens (and the pre-ring layout, kept as a
kill-switch via ``ServeEngine(windowed_cache=False)``).

Quantised cache formats (PR 10)
-------------------------------
Each group additionally carries a storage ``fmt``:

* ``"f32"`` — dense rows at the spec dtype (the bit-exact baseline);
* ``"q8"`` / ``"q4"`` — block-scaled codebook storage via the
  ``kernels/block_quant`` machinery: one absmax scale per **(token, head)**
  row (scale block = ``head_dim``), uint8 codes into a uniform symmetric
  codebook (256 / 16 points). ``q4`` nibble-packs code pairs along the
  head dim (``hd // 2`` bytes per row), so a row is self-contained and
  ring writes never read-modify-write.

A quantised group's state entries are ``k{g}``/``v{g}`` (uint8 codes) plus
``k{g}s``/``v{g}s`` (float32 scales, trailing dim 1); ``state_keys``
enumerates all of them, so the shared-prefix fork (``PrefixPool``) and the
reset wipe copy/zero quantised rows with no special cases (a zero scale
dequantises to exactly 0.0, matching a wiped dense row).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# KV storage formats
# ---------------------------------------------------------------------------

KV_FORMATS = ("f32", "q8", "q4")
_KV_BITS = {"f32": 0, "q8": 8, "q4": 4}


def kv_bits(fmt: str) -> int:
    """Code width of a KV format (0 = dense)."""
    return _KV_BITS[fmt]


def kv_codebook(fmt: str):
    """The uniform symmetric codebook a quantised KV format dequantises
    through: ``linspace(-1, 1, 2**bits)`` (float32). The block-absmax
    scale normalises each (token, head) row into [-1, 1], so the uniform
    grid is the paper's block-scaled integer format at that width."""
    bits = kv_bits(fmt)
    if not bits:
        raise ValueError(f"dense format {fmt!r} has no codebook")
    return jnp.linspace(-1.0, 1.0, 2 ** bits, dtype=jnp.float32)


def parse_kv_formats(formats, n_groups: int, head_dim: int
                     ) -> Tuple[str, ...]:
    """Normalise a KV-format request to one format per cache group.

    ``formats`` may be None/"" (all dense), a single format token
    (broadcast), a comma-separated string, or a sequence — per group, in
    group-index order. ``"auto"`` must be resolved to explicit formats
    (Fisher allocation, see ``core.allocation.allocate_kv_formats``)
    before reaching the cache geometry."""
    if formats is None or formats == "":
        return ("f32",) * n_groups
    if isinstance(formats, str):
        toks = [t.strip() for t in formats.split(",") if t.strip()]
    else:
        toks = [str(t) for t in formats]
    if len(toks) == 1:
        toks = toks * n_groups
    if len(toks) != n_groups:
        raise ValueError(
            f"kv_format {formats!r}: got {len(toks)} formats for "
            f"{n_groups} cache groups")
    for t in toks:
        if t not in KV_FORMATS:
            raise ValueError(f"unknown kv format {t!r} (expected one of "
                             f"{KV_FORMATS}, or 'auto' resolved upstream)")
        if t == "q4" and head_dim % 2:
            raise ValueError(
                f"q4 nibble-packs code pairs along head_dim, which must be "
                f"even (got {head_dim})")
    return tuple(toks)


def layer_groups(windows) -> Tuple[Tuple[int, Tuple[int, ...]], ...]:
    """Group a per-layer window pattern into window-homogeneous cache
    groups. ``windows``: (L,) ints, 0 = global attention. Returns
    ``((window, layer_indices), ...)`` ordered by first appearance, so
    group ``g`` owns state keys ``k{g}``/``v{g}`` deterministically."""
    order: List[int] = []
    members: Dict[int, List[int]] = {}
    for i, w in enumerate(int(w) for w in np.asarray(windows).reshape(-1)):
        if w not in members:
            members[w] = []
            order.append(w)
        members[w].append(i)
    return tuple((w, tuple(members[w])) for w in order)


@dataclass(frozen=True)
class CacheGroup:
    """One window-homogeneous layer group's KV cache geometry."""
    index: int                # group id == suffix of the state keys
    window: int               # sliding-window size; 0 = global attention
    layers: Tuple[int, ...]   # absolute layer indices in stack order
    length: int               # allocated kv slots per layer
    fmt: str = "f32"          # storage format: f32 | q8 | q4

    @property
    def ring(self) -> bool:
        """Windowed groups write at ``pos % length`` (ring buffer)."""
        return self.window > 0

    @property
    def quantised(self) -> bool:
        return self.fmt != "f32"

    @property
    def k_key(self) -> str:
        return f"k{self.index}"

    @property
    def v_key(self) -> str:
        return f"v{self.index}"

    @property
    def k_scale_key(self) -> str:
        return f"k{self.index}s"

    @property
    def v_scale_key(self) -> str:
        return f"v{self.index}s"

    @property
    def group_state_keys(self) -> Tuple[str, ...]:
        """The decode-state keys this group owns: codes (or dense rows)
        always; per-row scales when quantised."""
        if self.quantised:
            return (self.k_key, self.k_scale_key,
                    self.v_key, self.v_scale_key)
        return (self.k_key, self.v_key)


@dataclass(frozen=True)
class CacheSpec:
    """A model's full self-attention decode-cache geometry.

    ``full_length`` is what a uniform (pre-ring) allocation would give
    every layer (``kv_len + slack``) — the baseline of the byte
    accounting. ``layer_axis``/``head_axis`` name the logical mesh axes of
    the stacked lead dim and the head dim (families differ: transformer
    stacks ``layers`` × ``kv_heads``, whisper ``layers`` × ``heads``;
    zamba2 has one single-layer cache group per hybrid application
    point)."""
    groups: Tuple[CacheGroup, ...]
    batch: int
    kv_heads: int
    head_dim: int
    dtype: str
    full_length: int
    layer_axis: str = "layers"
    head_axis: str = "kv_heads"

    def state_specs(self) -> dict:
        """Grouped decode-state entries (``pos`` and any non-KV state stay
        with the family): per group, ``k{g}``/``v{g}`` — dense rows at the
        spec dtype, or uint8 codes for quantised formats (``hd // 2`` wide
        for nibble-packed q4) — plus float32 ``k{g}s``/``v{g}s`` absmax
        scales (one per (token, head) row) when quantised."""
        from repro.models.api import ParamSpec
        specs = {}
        for g in self.groups:
            lead = (len(g.layers), self.batch, g.length, self.kv_heads)
            axes = (self.layer_axis, "batch", "seq_kv", self.head_axis, None)
            if g.quantised:
                hdc = self.head_dim // 2 if g.fmt == "q4" else self.head_dim
                code = ParamSpec(lead + (hdc,), axes, "uint8")
                scale = ParamSpec(lead + (1,), axes, "float32")
                specs[g.k_key] = code
                specs[g.k_scale_key] = scale
                specs[g.v_key] = code
                specs[g.v_scale_key] = scale
            else:
                spec = ParamSpec(lead + (self.head_dim,), axes, self.dtype)
                specs[g.k_key] = spec
                specs[g.v_key] = spec
        return specs

    @property
    def n_layers(self) -> int:
        return sum(len(g.layers) for g in self.groups)

    @property
    def formats(self) -> Tuple[str, ...]:
        return tuple(g.fmt for g in self.groups)

    @property
    def quantised(self) -> bool:
        return any(g.quantised for g in self.groups)

    @property
    def state_keys(self) -> Tuple[str, ...]:
        """Every decode-state key this geometry owns (codes + scales for
        quantised groups) — the rows a shared-prefix fork must copy (ring
        and global groups alike; see serve.scheduler.PrefixPool)."""
        return tuple(k for g in self.groups for k in g.group_state_keys)

    def group_row_bytes(self, fmt: str) -> int:
        """Bytes one (token, head) K+V row pair costs under ``fmt``,
        including per-row scales for quantised formats."""
        if fmt == "f32":
            return 2 * self.head_dim * jnp.dtype(self.dtype).itemsize
        hdc = self.head_dim // 2 if fmt == "q4" else self.head_dim
        return 2 * (hdc + 4)  # uint8 codes + one float32 scale, k and v

    def cache_bytes(self) -> dict:
        """Byte accounting: per-group breakdown (format, code/scale byte
        split, dense-equivalent bytes), grouped total (``kv``) plus its
        code/scale split, the same grouped geometry at the dense dtype
        (``dense_kv`` — what quantisation is saving against), and the
        uniform full-length dense baseline (``uniform_kv``) the rolling
        window is saving against."""
        item = jnp.dtype(self.dtype).itemsize
        dense_row = 2 * self.batch * self.kv_heads * self.head_dim * item
        per = []
        kv = codes = scales = dense = 0
        for g in self.groups:
            slots = len(g.layers) * g.length * self.batch * self.kv_heads
            d = dense_row * len(g.layers) * g.length
            if g.quantised:
                hdc = self.head_dim // 2 if g.fmt == "q4" else self.head_dim
                cb = 2 * slots * hdc   # uint8 codes, k + v
                sb = 2 * slots * 4     # one float32 scale per row, k + v
            else:
                cb, sb = d, 0
            b = cb + sb
            per.append({"window": g.window, "n_layers": len(g.layers),
                        "length": g.length, "format": g.fmt, "bytes": b,
                        "code_bytes": cb, "scale_bytes": sb,
                        "dense_bytes": d,
                        "ratio_vs_dense": round(b / d, 4) if d else 1.0})
            kv += b
            codes += cb
            scales += sb
            dense += d
        uniform = dense_row * self.n_layers * self.full_length
        return {"kv": kv, "code_bytes": codes, "scale_bytes": scales,
                "dense_kv": dense,
                "cache_ratio_vs_dense": round(kv / dense, 4) if dense
                else 1.0,
                "uniform_kv": uniform,
                "cache_ratio_vs_uniform": round(kv / uniform, 4) if uniform
                else 1.0,
                "cache_groups": per}


def build_cache_spec(windows, batch: int, kv_len: int, *, slack: int = 0,
                     kv_heads: int, head_dim: int, dtype: str,
                     windowed: bool = True, layer_axis: str = "layers",
                     head_axis: str = "kv_heads",
                     formats=None) -> CacheSpec:
    """Build a model's grouped cache geometry from its per-layer window
    pattern. Global groups (and every group when ``windowed=False`` — the
    masked-full-cache baseline) allocate ``kv_len + slack``; windowed
    groups allocate ``min(window, kv_len) + slack`` ring slots. ``slack``
    is the engine's chunk-write spill region (``prefill_chunk``): global
    caches never see a write past it, and it keeps ring clobbering outside
    every window (``length ≥ window + chunk - 1``). ``formats`` selects
    per-group storage (see :func:`parse_kv_formats`; default all
    dense)."""
    full = kv_len + slack
    grouped = layer_groups(windows)
    fmts = parse_kv_formats(formats, len(grouped), head_dim)
    groups = []
    for i, (w, layers) in enumerate(grouped):
        length = min(w, kv_len) + slack if (windowed and w > 0) else full
        groups.append(CacheGroup(index=i, window=w, layers=layers,
                                 length=length, fmt=fmts[i]))
    return CacheSpec(tuple(groups), batch, kv_heads, head_dim, dtype, full,
                     layer_axis, head_axis)


# ---------------------------------------------------------------------------
# Ring index math (shared with models.layers — keep in sync by using these)
# ---------------------------------------------------------------------------

def ring_slots(positions, length: int):
    """Ring slot for each absolute position. Linear caches are the
    degenerate case where positions never reach ``length``."""
    return positions % length

def ring_positions(last, length: int):
    """Reconstruct the absolute position each ring slot currently holds.

    ``last``: (...,) the highest position written so far per row. Returns
    (..., length): slot ``s`` holds the most recent position ≤ ``last``
    congruent to ``s`` mod ``length``; negative ⇒ never written. Content-
    agnostic — masks built from these positions (causal, window, ≥ 0) are
    wrap-correct with no per-slot bookkeeping."""
    last = jnp.asarray(last)
    s = jnp.arange(length, dtype=last.dtype)
    return last[..., None] - ((last[..., None] - s) % length)
