"""Unified decoder-only transformer LM: dense / MoE / GQA / local:global
attention patterns. Covers llama3/llama4-scout/qwen2-moe/internlm2/gemma3/
deepseek (and the InternVL2 / paper-100M backbones).

Structure: scan-over-layers with stacked parameters — HLO size is O(1) in
depth, which keeps the 126-layer Llama-405B dry-run compile tractable and is
standard production-JAX practice. Per-layer attention window sizes ride along
as a scanned (L,) array so heterogeneous local/global stacks share one scan.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .api import (ModelConfig, ModelFamily, ParamSpec, ring_prologue,
                  register_family)
from .layers import (AttnParams, MlpParams, MoeParams, QuantisedKV,
                     attn_block, chunked_decode_attention, embed_lookup,
                     flash_attention, linear, moe_block, qkv_project,
                     rms_norm, swiglu, update_kv_cache)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def layer_param_specs(cfg: ModelConfig, n_layers: int) -> dict:
    """Specs for the stacked (scanned) decoder layers."""
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    L = n_layers
    pd = cfg.param_dtype
    p = {
        "attn_norm": ParamSpec((L, D), ("layers", None), pd),
        "wq": ParamSpec((L, D, H, hd), ("layers", "fsdp", "heads", None), pd),
        "wk": ParamSpec((L, D, K, hd), ("layers", "fsdp", "kv_heads", None), pd),
        "wv": ParamSpec((L, D, K, hd), ("layers", "fsdp", "kv_heads", None), pd),
        "wo": ParamSpec((L, H, hd, D), ("layers", "heads", None, "fsdp"), pd),
        "mlp_norm": ParamSpec((L, D), ("layers", None), pd),
    }
    if cfg.qk_norm:
        p["q_norm"] = ParamSpec((L, hd), ("layers", None), pd)
        p["k_norm"] = ParamSpec((L, hd), ("layers", None), pd)
    if cfg.n_experts:
        E, F = cfg.n_experts, cfg.dff_expert
        p.update({
            "w_router": ParamSpec((L, D, E), ("layers", "fsdp", None), pd),
            "we_gate": ParamSpec((L, E, D, F), ("layers", "experts", "fsdp", None), pd),
            "we_up": ParamSpec((L, E, D, F), ("layers", "experts", "fsdp", None), pd),
            "we_down": ParamSpec((L, E, F, D), ("layers", "experts", None, "fsdp"), pd),
        })
        if cfg.n_shared_experts:
            Fs = cfg.dff_expert * cfg.n_shared_experts
            p.update({
                "ws_gate": ParamSpec((L, D, Fs), ("layers", "fsdp", "mlp"), pd),
                "ws_up": ParamSpec((L, D, Fs), ("layers", "fsdp", "mlp"), pd),
                "ws_down": ParamSpec((L, Fs, D), ("layers", "mlp", "fsdp"), pd),
            })
    else:
        F = cfg.d_ff
        p.update({
            "w_gate": ParamSpec((L, D, F), ("layers", "fsdp", "mlp"), pd),
            "w_up": ParamSpec((L, D, F), ("layers", "fsdp", "mlp"), pd),
            "w_down": ParamSpec((L, F, D), ("layers", "mlp", "fsdp"), pd),
        })
    return p


def param_specs(cfg: ModelConfig) -> dict:
    D = cfg.d_model
    pd = cfg.param_dtype
    specs = {
        "embed": ParamSpec((cfg.vocab, D), ("vocab", "fsdp"), pd),
        "layers": layer_param_specs(cfg, cfg.n_layers),
        "final_norm": ParamSpec((D,), (None,), pd),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((D, cfg.vocab), ("fsdp", "vocab"), pd)
    return specs


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _layer_attn_params(lp) -> AttnParams:
    return AttnParams(lp["wq"], lp["wk"], lp["wv"], lp["wo"],
                      lp.get("q_norm"), lp.get("k_norm"))


def _layer_body(cfg: ModelConfig, x, lp, window, positions):
    """One decoder layer. x: (B, T, D)."""
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    x = x + attn_block(h, _layer_attn_params(lp), positions, cfg, window)
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.n_experts:
        moe = MoeParams(
            lp["w_router"], lp["we_gate"], lp["we_up"], lp["we_down"],
            shared=(MlpParams(lp["ws_gate"], lp["ws_up"], lp["ws_down"])
                    if cfg.n_shared_experts else None))
        y, aux = moe_block(h, moe, cfg)
    else:
        y, aux = swiglu(h, MlpParams(lp["w_gate"], lp["w_up"], lp["w_down"])), 0.0
    return x + y, aux


def _scan_layers(cfg: ModelConfig, x, layers, positions):
    windows = jnp.asarray(cfg.window_pattern())

    def body(carry, inputs):
        lp, window = inputs
        from .layers import constrain_act
        y, aux = _layer_body(cfg, constrain_act(carry[0]), lp, window,
                             positions)
        return (constrain_act(y), carry[1] + aux), None

    body_fn = body
    if cfg.remat == "full":
        body_fn = jax.checkpoint(body)
    elif cfg.remat == "dots":
        body_fn = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                               (layers, windows))
    return x, aux


def apply(params, batch, cfg: ModelConfig):
    """Teacher-forcing forward. batch: {"tokens": (B, T) int32, ...}.
    Returns logits (B, T, V)."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    dt = jnp.dtype(cfg.dtype)
    x = embed_lookup(params["embed"], tokens, dtype=dt)
    if "vis_embed" in batch:  # VLM: prepend projected patch embeddings
        x = jnp.concatenate([batch["vis_embed"].astype(dt), x], axis=1)
        T = x.shape[1]
    positions = jnp.arange(T)
    x, aux = _scan_layers(cfg, x, params["layers"], positions)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(x, params, cfg)
    return logits.astype(jnp.float32)


def _unembed(x, params, cfg: ModelConfig):
    """Logits projection through the unified `linear`. Tied embeddings
    contract the (V, D) embed table along its blocked axis (the transposed
    spec) — packed tables serve via dequant_matmul_t, and the dense path's
    einsum never materialises ``embed.T`` either."""
    if cfg.tie_embeddings:
        return linear(x, params["embed"], "btd,vd->btv")
    return linear(x, params["unembed"], "btd,dv->btv")


# ---------------------------------------------------------------------------
# Decode path (serving)
# ---------------------------------------------------------------------------

def cache_spec(cfg: ModelConfig, batch_size: int, kv_len: int,
               slack: int = 0, windowed: bool = True):
    """Self-attention cache geometry (``serve.cache.CacheSpec``): layers
    grouped by their window, global groups at ``kv_len + slack``, windowed
    groups as ``min(window, kv_len) + slack`` ring buffers. ``windowed=
    False`` keeps the grouping but allocates every group at the full
    length — the masked-full-cache baseline / ring kill-switch. Per-group
    storage formats come from ``cfg.kv_format`` ("" = dense; q8/q4 store
    block-scaled codes + per-row scales)."""
    from repro.serve.cache import build_cache_spec
    return build_cache_spec(
        cfg.window_pattern(), batch_size, kv_len, slack=slack,
        kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        dtype=cfg.kv_dtype or cfg.dtype, windowed=windowed,
        formats=cfg.kv_format)


def decode_state_specs(cfg: ModelConfig, batch_size: int, kv_len: int,
                       slack: int = 0, windowed: bool = True) -> dict:
    """Grouped KV cache specs: one ``k{g}``/``v{g}`` stack per window-
    homogeneous layer group (see :func:`cache_spec`). A pure-global stack
    is the single group ``k0``/``v0`` at full length — byte-for-byte the
    old uniform allocation; local (windowed) groups allocate only
    ``window + slack`` ring slots instead of masking a full-length cache
    (~6× resident-cache saving on gemma3's 5:1 pattern at serving
    lengths). ``pos`` is **per-slot** ((B,) int32) so serving slots with
    different prompt lengths need not run in lockstep."""
    spec = cache_spec(cfg, batch_size, kv_len, slack, windowed)
    return {
        **spec.state_specs(),
        "pos": ParamSpec((batch_size,), ("batch",), "int32"),
    }


def decode_step(params, state, batch, cfg: ModelConfig):
    """Chunked decode step with per-slot positions and grouped caches.

    batch: {"tokens": (B, T), "t_valid": optional (B,) int32, "reset":
    optional (B,) mask}. T=1 is plain decode; T>1 is (batched) chunked
    prefill. Each row writes its T new k/v at its own ``state["pos"][b]``
    and advances by ``t_valid[b]`` (default T). Rows whose chunk is partly
    padding (ragged prompts, or decode rows riding in a prefill-sized call)
    advance by their valid count; the k/v written beyond it land at
    positions ≥ the row's new pos (mod the ring length for windowed
    groups), which are never visible to attention (write-before-read in
    linear caches; reconstruction-masked and outside every reachable
    window in ring caches), so padding is harmless. A set ``reset`` bit
    zeroes that slot's KV rows — in every cache group — and position
    inside the step (slot reuse — see ``ring_prologue`` in ``models.api``).
    Returns (logits (B, T, V), state); row b's next-token logits live at
    index t_valid[b]-1.

    A homogeneous all-global stack (the common case) scans the single
    group's cache alongside the layer params exactly as the uniform cache
    always did. Heterogeneous local:global stacks (gemma3) carry one cache
    stack per group through the scan and each layer switches into its
    group's stack at its group-local slot: local layers write at
    ``pos % ring_len`` and mask via wrap-correct reconstructed positions
    (``layers.chunked_decode_attention(ring=True)``), global layers keep
    the linear full-length path. Weights may be PackedTensors (serving
    from packed quantised weights) — dense weights take the identical
    einsum path as before."""
    from repro.serve.cache import kv_codebook, layer_groups, parse_kv_formats
    tokens = batch["tokens"]
    B, T = tokens.shape
    dt = jnp.dtype(cfg.dtype)
    groups = layer_groups(cfg.window_pattern())
    fmts = parse_kv_formats(cfg.kv_format, len(groups), cfg.hd)
    pos, adv, _, st = ring_prologue(state, batch, len(groups), formats=fmts)
    # named scopes (embed, attention, mlp, unembed) put the layer kind into
    # each operation's op_name in the compiled step's metadata
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, dtype=dt)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]  # (B, T)

    # quantised cache groups carry (codes, scales) as a QuantisedKV pytree;
    # dense groups stay plain arrays — layers.update_kv_cache /
    # chunked_decode_attention dispatch on the type, so layer_decode below
    # is one code path (and bit-identical to the pre-quantisation step when
    # every group is dense)
    def group_cache(g):
        if fmts[g] == "f32":
            return st[f"k{g}"], st[f"v{g}"]
        return (QuantisedKV(st[f"k{g}"], st[f"k{g}s"]),
                QuantisedKV(st[f"v{g}"], st[f"v{g}s"]))

    def cache_entries(g, kc, vc):
        if fmts[g] == "f32":
            return {f"k{g}": kc, f"v{g}": vc}
        return {f"k{g}": kc.codes, f"k{g}s": kc.scales,
                f"v{g}": vc.codes, f"v{g}s": vc.scales}

    def layer_decode(x, lp, k_cache, v_cache, window, ring, codebook=None):
        with jax.named_scope("attention"):
            h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            q, k_new, v_new = qkv_project(h, _layer_attn_params(lp),
                                          positions, cfg)
            k_cache = update_kv_cache(k_cache, k_new, pos, ring=ring,
                                      codebook=codebook)
            v_cache = update_kv_cache(v_cache, v_new, pos, ring=ring,
                                      codebook=codebook)
            o = chunked_decode_attention(q, k_cache, v_cache, positions,
                                         window=window, ring=ring,
                                         codebook=codebook)
            x = x + linear(o, lp["wo"], "btnh,nhd->btd")
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            if cfg.n_experts:
                moe = MoeParams(
                    lp["w_router"], lp["we_gate"], lp["we_up"], lp["we_down"],
                    shared=(MlpParams(lp["ws_gate"], lp["ws_up"],
                                      lp["ws_down"])
                            if cfg.n_shared_experts else None))
                y, _ = moe_block(h, moe, cfg)
            else:
                y = swiglu(h, MlpParams(lp["w_gate"], lp["w_up"],
                                        lp["w_down"]))
        return x + y, k_cache, v_cache

    codebooks = [None if f == "f32" else kv_codebook(f) for f in fmts]

    if len(groups) == 1 and groups[0][0] == 0:
        # homogeneous all-global stack: the cache rides the scan xs (a
        # QuantisedKV's codes/scales leaves slice per layer like any array)
        windows = jnp.asarray(cfg.window_pattern())
        kc0, vc0 = group_cache(0)

        def body(x, inputs):
            lp, kc, vc, window = inputs
            x, kc, vc = layer_decode(x, lp, kc, vc, window, ring=False,
                                     codebook=codebooks[0])
            return x, (kc, vc)

        x, (k_new, v_new) = jax.lax.scan(
            body, x, (params["layers"], kc0, vc0, windows))
        new_caches = cache_entries(0, k_new, v_new)
    else:
        # heterogeneous stack: group caches ride the scan carry; layer l
        # switches into its group's stack at its group-local slot
        gid = np.zeros(cfg.n_layers, np.int32)
        gslot = np.zeros(cfg.n_layers, np.int32)
        for g, (_, layers) in enumerate(groups):
            for j, l in enumerate(layers):
                gid[l], gslot[l] = g, j
        caches = tuple(group_cache(g) for g in range(len(groups)))

        def make_branch(g):
            window = groups[g][0]

            def branch(op):
                x, caches, lp, slot = op
                take = lambda a: jax.lax.dynamic_index_in_dim(
                    a, slot, 0, keepdims=False)
                kc = jax.tree.map(take, caches[g][0])
                vc = jax.tree.map(take, caches[g][1])
                x, kc, vc = layer_decode(x, lp, kc, vc, window,
                                         ring=window > 0,
                                         codebook=codebooks[g])
                put = lambda full, part: jax.lax.dynamic_update_index_in_dim(
                    full, part, slot, 0)
                kg = jax.tree.map(put, caches[g][0], kc)
                vg = jax.tree.map(put, caches[g][1], vc)
                return x, tuple((kg, vg) if i == g else c
                                for i, c in enumerate(caches))
            return branch

        branches = [make_branch(g) for g in range(len(groups))]

        def body(carry, inputs):
            x, caches = carry
            lp, g_id, slot = inputs
            x, caches = jax.lax.switch(g_id, branches, (x, caches, lp, slot))
            return (x, caches), None

        (x, caches), _ = jax.lax.scan(
            body, (x, caches),
            (params["layers"], jnp.asarray(gid), jnp.asarray(gslot)))
        new_caches = {}
        for g, (kg, vg) in enumerate(caches):
            new_caches.update(cache_entries(g, kg, vg))

    new_state = {**new_caches, "pos": pos + adv}
    with jax.named_scope("unembed"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _unembed(x, params, cfg).astype(jnp.float32)
    return logits, new_state


def prefill(params, batch, cfg: ModelConfig):
    """Process a full prompt, returning logits (the KV cache for generation
    is produced by re-running qkv per layer in `serve.engine`; the prefill
    dry-run cell measures this forward)."""
    return apply(params, batch, cfg)


def init(rng, cfg: ModelConfig):
    from .api import init_from_specs
    return init_from_specs(rng, param_specs(cfg))


def pack_layouts(cfg: ModelConfig) -> dict:
    """Matmul layouts for serving from packed quantised weights: tensor path
    → (n_lead, n_contract). Lead dims are scanned (layers) or stacked
    (experts); contraction dims come next; the rest are output dims (blocked
    by the scale block size). MoE expert stacks carry (layers, experts) lead
    dims and stream per expert through ``dequant_matmul``'s batched lead
    axis inside ``moe_block``.

    The embedding table always packs, tied or not: rows gather-dequantise
    through ``embed_lookup``, and with ``tie_embeddings`` the same packed
    (V, D) table serves the logits matmul through the transposed
    ``dequant_matmul_t`` (contraction along the blocked axis — no dense
    unembed is ever materialised). Only the MoE router stays dense (a tiny
    (D, E) matmul feeding top-k dispatch)."""
    lay = {
        "['layers']['wq']": (1, 1),
        "['layers']['wk']": (1, 1),
        "['layers']['wv']": (1, 1),
        "['layers']['wo']": (1, 2),
    }
    if not cfg.n_experts:
        # dense MLP only exists without experts (param_specs emits either
        # the w_* MLP or the we_*/ws_* expert stacks, never both — the
        # contract verifier checks every layout path resolves)
        lay.update({
            "['layers']['w_gate']": (1, 1),
            "['layers']['w_up']": (1, 1),
            "['layers']['w_down']": (1, 1),
        })
    if cfg.n_experts:
        lay.update({
            "['layers']['we_gate']": (2, 1),
            "['layers']['we_up']": (2, 1),
            "['layers']['we_down']": (2, 1),
        })
        if cfg.n_shared_experts:
            lay.update({
                "['layers']['ws_gate']": (1, 1),
                "['layers']['ws_up']": (1, 1),
                "['layers']['ws_down']": (1, 1),
            })
    # embed rows gather-dequantise (layers.embed_lookup); tied configs also
    # consume the same packed table transposed for logits
    lay["['embed']"] = (0, 1)
    if not cfg.tie_embeddings:
        lay["['unembed']"] = (0, 1)
    return lay


register_family(ModelFamily(
    name="transformer",
    param_specs=param_specs,
    init=init,
    apply=apply,
    decode_state_specs=decode_state_specs,
    decode_step=decode_step,
    prefill=prefill,
    supports_ragged=True,
    cache_spec=cache_spec,
    pack_layouts=pack_layouts,
))
