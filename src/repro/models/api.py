"""Model API: configs, parameter specs with logical sharding axes, and the
Model protocol every architecture implements.

Parameters are plain pytrees (no flax). Each leaf is described by a
``ParamSpec(shape, dtype, axes)`` where ``axes`` names a logical mesh axis
per dimension; ``repro.launch.mesh`` maps logical → physical axes with
divisibility-aware fallback. ``param_specs`` never allocates — it is the
basis of the multi-pod dry-run (ShapeDtypeStruct stand-ins).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Logical axis names used across the zoo:
#   batch, seq, seq_kv      activations / caches
#   vocab, fsdp, heads, kv_heads, head_dim, mlp, experts, layers, groups
#   conv, state             ssm internals


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def sds(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(self.dtype))

    @property
    def numel(self) -> int:
        return int(np.prod(self.shape))


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "transformer"   # transformer | rwkv6 | zamba2 | whisper | internvl
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 0             # 0 -> d_model // n_heads
    d_ff: int = 1024
    vocab: int = 1024
    # --- MoE ---
    n_experts: int = 0            # 0 -> dense
    experts_per_token: int = 1
    n_shared_experts: int = 0
    d_expert: int = 0             # 0 -> d_ff
    capacity_factor: float = 1.25
    # --- attention pattern ---
    window: int = 0               # sliding-window size for local layers
    local_global_pattern: Tuple[int, ...] = ()  # e.g. (5, 1): 5 local : 1 global
    qk_norm: bool = False
    # --- ssm / hybrid ---
    ssm_state: int = 0
    d_inner: int = 0              # 0 -> 2 * d_model
    conv_kernel: int = 4
    ssm_groups: int = 1           # B/C groups shared by the SSM heads
    # zamba2: the layers that run a shared transformer block before their
    # Mamba layer (the published ``hybrid_layer_ids``), the shared blocks
    # they cycle through, and the rank of each application's MLP adapter
    hybrid_layers: Tuple[int, ...] = ()
    n_shared_blocks: int = 1
    adapter_rank: int = 0         # 0 -> no adapter
    # --- enc-dec (whisper) ---
    n_enc_layers: int = 0
    enc_seq: int = 1500
    # --- vlm (internvl) ---
    n_vis_tokens: int = 0
    # --- misc ---
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"       # compute dtype
    param_dtype: str = "float32"  # master dtype
    kv_dtype: str = ""            # KV-cache storage dtype ("" = dtype);
                                  # "float8_e4m3fn" halves decode cache
    kv_format: str = ""           # quantised KV-cache storage per cache
                                  # group ("" = dense/bit-exact; "q8"/"q4"
                                  # broadcast; comma list per group index;
                                  # "auto" is resolved by the launcher via
                                  # Fisher allocation before cfg is built)
    attn_chunk: int = 1024        # flash-attention KV chunk
    linear_chunk: int = 32        # WKV/SSD block-parallel chunk (0 = scan)
    remat: str = "full"           # none | full | dots
    # moe dispatch implementation: "sort" (capacity, EP-friendly) | "dense"
    moe_impl: str = "sort"

    def __post_init__(self):
        # JSON configurations give lists; the config stays hashable
        object.__setattr__(self, "hybrid_layers", tuple(self.hybrid_layers))

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def dff_expert(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def dinner(self) -> int:
        return self.d_inner or 2 * self.d_model

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def window_pattern(self) -> np.ndarray:
        """Per-layer sliding-window sizes; 0 = global attention."""
        if not self.local_global_pattern:
            return np.zeros(self.n_layers, np.int32)
        nl, ng = self.local_global_pattern
        unit = [self.window] * nl + [0] * ng
        reps = (self.n_layers + len(unit) - 1) // len(unit)
        return np.asarray((unit * reps)[: self.n_layers], np.int32)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_FAMILIES: Dict[str, "ModelFamily"] = {}


def empty_pack_layouts(cfg) -> Dict[str, tuple]:
    """The explicit "this family cannot serve packed" declaration.

    Every family must declare its packed-serving surface; a family with no
    packable tensor registers this (rather than omitting the field) so
    serving dense is a visible decision, not a silent fallback —
    ``ServeEngine.from_quantised(packed=True)`` fails fast on it."""
    return {}


@dataclass
class ModelFamily:
    """One architecture family's full contract with the system.

    Weight application inside ``apply``/``decode_step`` must go through the
    unified projection API (``models.layers.linear`` /
    ``layers.embed_lookup`` / ``layers.expert_matmul``) — never a raw
    ``jnp.einsum`` against a parameter — so any tensor the family declares
    in ``pack_layouts`` serves straight from packed quantised codes with no
    per-family special cases.

    ``pack_layouts(cfg) -> {tensor-path: (n_lead, n_contract)}`` declares,
    per parameter, how its axes map onto the ``dequant_matmul`` codes
    layout: ``n_lead`` leading stack dims (scanned layers / expert stacks),
    then ``n_contract`` contraction dims, the rest output dims (blocked by
    the scale block). An embedding table declares ``(0, 1)``: its rows both
    gather (``embed_lookup``) and, when embeddings are tied, serve the
    unembed matmul through the transposed kernel variant — the contraction
    then runs along the blocked axis and no dense transpose is ever
    materialised. The field is **required**: a family that truly cannot
    pack registers :func:`empty_pack_layouts`, and the engine fails fast
    instead of silently serving dense. ``QuantisationPlan.packable``
    separately gates each tensor per format (block-scaled ≤256-code
    codebooks, no sparse outliers, output tiling by the scale block)."""

    name: str
    param_specs: Callable           # (cfg) -> tree[ParamSpec]
    init: Callable                  # (rng, cfg) -> params
    apply: Callable                 # (params, batch, cfg) -> logits
    # decoding (None for encoder-only):
    # decode_state_specs(cfg, batch, kv_len, slack=0, windowed=True)
    # -> tree[ParamSpec]. kv_len is the position budget (the global-layer
    # cache length); slack is the engine's chunk-write spill region
    # (prefill_chunk). Attention-bearing families return GROUPED KV
    # entries: one ``k{g}``/``v{g}`` stack per window-homogeneous layer
    # group (serve.cache.CacheSpec), where global groups allocate
    # kv_len + slack and windowed groups allocate a min(window, kv_len)
    # + slack ring buffer. windowed=False is the masked-full-cache
    # baseline: same grouped keys, every group at the full length.
    decode_state_specs: Callable = None
    decode_step: Callable = None    # (params, state, batch, cfg) -> (logits, state)
    prefill: Callable = None        # (params, batch, cfg) -> (logits, state)
    # --- serving capabilities -------------------------------------------------
    # supports_ragged: the ragged serving protocol, REQUIRED for ServeEngine
    # (the legacy lockstep loop is gone — every family decodes through the
    # one continuous-batching path). decode_step takes (B, T) token chunks
    # with per-slot positions (state["pos"]: (B,) int32) plus two optional
    # batch entries:
    #   * "t_valid" (B,) int32 — how many leading tokens of each row are
    #     real; the row's state (KV position, recurrent/conv/ssm state,
    #     token-shift buffers) advances by exactly that count and padding
    #     is masked out of every state update;
    #   * "reset" (B,) bool — zero that slot's per-request state (the
    #     grouped KV stacks k{g}/v{g}, recurrent state) and position
    #     inside the jitted step before any token is processed. The engine
    #     raises it on the first step after a slot is reused, so no
    #     request ever observes its predecessor's state and no host
    #     round-trip is needed.
    # T=1 is plain decode; T>1 is batched chunked prefill (recurrent
    # families route it through their block-parallel wkv/ssd forms).
    supports_ragged: bool = False
    # cross_prefill: optional — (params, frames (1, enc_seq, D) | None, cfg)
    # -> dict of per-slot decode-state entries (batch dim 1, e.g. whisper's
    # cross-attention xk/xv). The engine computes it per ADMITTED slot and
    # scatters the result into that slot's state rows; None frames must
    # return zeroed entries (text-only request / stale-slot wipe). These
    # entries are owned by admission, not by the in-step "reset" mask.
    cross_prefill: Callable = None
    # cache_spec: optional — (cfg, batch, kv_len, slack=0, windowed=True)
    # -> serve.cache.CacheSpec, the self-attention cache geometry behind
    # the grouped ``k{g}``/``v{g}`` decode-state entries. The engine uses
    # it for byte accounting (``ServeEngine.cache_bytes``): per-group
    # windowed-vs-global breakdown against the uniform full-length
    # baseline. None for families with no attention KV (rwkv6's recurrent
    # state is O(1) in sequence length).
    cache_spec: Callable = None
    # pack_layouts: required — see the class docstring. Declared last for
    # dataclass field ordering; validated at registration.
    pack_layouts: Callable = None

    def __post_init__(self):
        if self.pack_layouts is None:
            raise ValueError(
                f"ModelFamily {self.name!r}: pack_layouts is required — "
                "declare the packed-serving matmul layouts, or register "
                "models.api.empty_pack_layouts for a family with none")


def ragged_prologue(state, batch, reset_axes):
    """The shared prologue of the ragged serving protocol (one source of
    truth for all four decode_steps — see the ``supports_ragged`` notes on
    :class:`ModelFamily`): read the per-slot positions, default the advance
    counts from ``t_valid``, and honour the per-slot ``reset`` mask by
    zeroing the named per-request state entries (and pos) inside the jitted
    step. ``reset_axes`` maps each resettable state key to the index of its
    batch dim (families stack state differently: transformer/whisper KV is
    (L, B, S, ...), zamba2's per-layer conv/ssm lists (B, ...)); an entry
    may be a list of arrays, each wiped at that axis.

    Returns ``(pos, adv, valid, entries)``: ``entries`` holds the
    possibly-wiped arrays for exactly the ``reset_axes`` keys; ``valid`` is
    the (B, T) ragged-chunk mask (True where a row's token is real), or
    None for a plain T=1 call with no ``t_valid`` — the single-token fast
    path needs no masking."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    pos = state["pos"]                                     # (B,)
    t_valid = batch.get("t_valid")
    adv = jnp.full((B,), T, jnp.int32) if t_valid is None else t_valid
    entries = {k: state[k] for k in reset_axes}
    reset = batch.get("reset")
    if reset is not None:
        rm = reset.astype(bool)
        def wipe(a, ax):
            shape = [1] * a.ndim
            shape[ax] = a.shape[ax]
            return jnp.where(rm.reshape(shape), 0, a)

        for key, ax in reset_axes.items():
            entries[key] = jax.tree.map(lambda a: wipe(a, ax), entries[key])
        pos = jnp.where(rm, 0, pos)
    valid = (jnp.arange(T, dtype=jnp.int32)[None, :] < adv[:, None]
             if (T > 1 or t_valid is not None) else None)
    return pos, adv, valid, entries


def ring_prologue(state, batch, n_groups: int, extra_reset=None,
                  formats=None):
    """The grouped-cache variant of :func:`ragged_prologue` — the shared
    prologue of the ring decode-cache protocol. The reset set is derived
    from the cache groups: every group's stacked ``k{g}``/``v{g}`` cache
    wipes at batch axis 1 (the grouped layout is always (Lg, B, S, ...)),
    plus the per-row ``k{g}s``/``v{g}s`` scale stacks for quantised
    groups (``formats``: one KV format per group, default all dense — a
    zeroed scale dequantises every code in the row to exactly 0.0), plus
    any family extras (``extra_reset``, e.g. zamba2's conv/ssm at axis 0
    or rwkv6-style recurrent entries).

    Wiping a ring group on reset is defence in depth rather than a
    correctness requirement: the wrap-correct masks are built from
    reconstructed positions (``serve.cache.ring_positions``), so a reused
    slot's stale keys are already invisible — but zeroed rows make state
    leaks impossible even if a mask regresses. Returns the same
    ``(pos, adv, valid, entries)`` as :func:`ragged_prologue`, with
    ``entries`` holding the possibly-wiped cache stacks under their
    ``k{g}``/``v{g}`` (+ scale) keys."""
    axes = {}
    for g in range(n_groups):
        axes[f"k{g}"] = 1
        axes[f"v{g}"] = 1
        if formats is not None and formats[g] != "f32":
            axes[f"k{g}s"] = 1
            axes[f"v{g}s"] = 1
    if extra_reset:
        axes.update(extra_reset)
    return ragged_prologue(state, batch, axes)


def register_family(fam: ModelFamily):
    _FAMILIES[fam.name] = fam
    return fam


def get_family(name: str) -> ModelFamily:
    if name not in _FAMILIES:
        # import side-effect registration
        from . import transformer, rwkv6, zamba2, whisper, internvl  # noqa
    return _FAMILIES[name]


# ---------------------------------------------------------------------------
# Spec utilities
# ---------------------------------------------------------------------------

def specs_to_sds(specs):
    return jax.tree.map(lambda s: s.sds(), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def init_from_specs(rng, specs, scale_rule=None):
    """Materialise parameters: truncated-normal fan-in init for >=2-D, zeros
    for biases, ones for norm gains (axes == ('*norm*',))."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    rngs = jax.random.split(rng, len(flat))
    leaves = []
    for (path, spec), r in zip(flat, rngs):
        name = jax.tree_util.keystr(path)
        if "norm" in name or name.endswith("gain']"):
            leaves.append(jnp.ones(spec.shape, spec.dtype))
        elif "bias" in name or spec.numel == 0:
            leaves.append(jnp.zeros(spec.shape, spec.dtype))
        elif len(spec.shape) >= 2:
            if "embed" in name:
                std = 0.02
            else:  # fan_in = numel / fan_out(last dim)
                fan_in = spec.numel // max(spec.shape[-1], 1)
                std = 1.0 / np.sqrt(max(fan_in, 1))
            if scale_rule:
                std = scale_rule(name, spec, std)
            x = jax.random.truncated_normal(r, -3, 3, spec.shape) * std
            leaves.append(x.astype(spec.dtype))
        else:
            leaves.append(jnp.zeros(spec.shape, spec.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def count_params(specs) -> int:
    return sum(s.numel for s in jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
        if isinstance(s, ParamSpec))
