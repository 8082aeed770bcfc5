"""Zamba2 (arXiv:2411.15242; HF ``Zamba2``): a Mamba-2 backbone with
**shared** transformer blocks applied at the listed hybrid layers.

Every layer ℓ is a pre-norm Mamba-2 layer on the residual stream ``h``::

    h <- h + Mamba2_ℓ(RMSNorm_ℓ(x_in)),   x_in = h + linear_p(t)  (hybrid)
                                          x_in = h                (otherwise)

At the p-th hybrid layer (``cfg.hybrid_layers``) the shared block
``b = p mod n_shared_blocks`` reads the residual stream concatenated with
the token embeddings, ``u = concat(h, emb)`` (2·D wide), and gives

    a = o_proj_b(Attn_b(RMSNorm_b(u)))      # MHA, RoPE, scale (hd/2)^-0.5
    t = down_b(gelu(g) * v), [g, v] = gate_up_b(n) + B_p(A_p(n)),
                             n = RMSNorm'_b(a)

with no residual inside the block; ``A_p``/``B_p`` are the point's own
rank-``adapter_rank`` MLP adapter and ``linear_p`` its own D×D
projection, and each point keeps its own KV cache. ``t`` feeds only that
layer's Mamba input, not the residual.

Mamba-2 layer (SSD, scalar decay per head), state h ∈ R^{H×64×N}:
    [z, xBC] = in_proj(x), dt = dt_proj(x)
    xBC = silu(conv1d(xBC) + conv_b);  [x, B, C] = xBC
    h_i <- a_i·h_i + (Δ_i x_i) ⊗ B_g,  y_i = h_i C_g + D_i·x_i
with Δ = softplus(dt + dt_bias), a = exp(-exp(A_log)·Δ), and head i
reading B and C of group g = i // (H / ssm_groups); the output is
``out_proj(GroupRMSNorm(y * silu(z)))``, the norm over each of the
``ssm_groups`` channel groups. The published fused ``in_proj`` is held as
``in_proj`` ([z, x, B, C]) and ``dt_proj``: the same products, each a
whole number of 64-wide weight blocks where the fused width is not.

Each layer's weights and decode state are leaves of their own, so a step
hands them to the kernels and updates them without slicing or copying a
stack; only the per-head SSM vectors are stacked over layers (``heads``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .api import (ModelConfig, ModelFamily, ParamSpec, ring_prologue,
                  register_family)
from .layers import (AttnParams, QuantisedKV, causal_conv1d,
                     chunked_decode_attention, constrain_act, embed_lookup,
                     flash_attention, gelu_gated_mlp, linear, qkv_project,
                     rms_norm, update_kv_cache)

SSM_HEAD_DIM = 64


def _dims(cfg: ModelConfig):
    di = cfg.dinner
    H = di // SSM_HEAD_DIM
    N = cfg.ssm_state or 64
    G = cfg.ssm_groups
    assert H % G == 0, "SSM heads must divide into ssm_groups"
    return di, H, N, G


def points(cfg: ModelConfig) -> dict:
    """{hybrid layer: its application point p}; point p applies shared
    block p mod n_shared_blocks."""
    hp = tuple(cfg.hybrid_layers)
    assert list(hp) == sorted(set(hp)) and all(0 <= i < cfg.n_layers
                                               for i in hp), hp
    return {layer: p for p, layer in enumerate(hp)}


def attn_scale(cfg: ModelConfig) -> float:
    """Zamba2's score scale, ``(head_dim / 2) ** -0.5`` (transformers'
    ``Zamba2Attention``)."""
    return (cfg.hd / 2) ** -0.5


def mamba_param_specs(cfg: ModelConfig) -> dict:
    """One Mamba layer's weights, the per-head vectors aside."""
    D = cfg.d_model
    di, H, N, G = _dims(cfg)
    C = di + 2 * G * N                     # conv channels: [x, B, C]
    pd = cfg.param_dtype
    return {
        "norm": ParamSpec((D,), (None,), pd),
        "in_proj": ParamSpec((D, di + C), ("fsdp", "heads_flat"), pd),
        "dt_proj": ParamSpec((D, H), ("fsdp", None), pd),
        "conv_w": ParamSpec((cfg.conv_kernel, C), (None, None), pd),
        "conv_b": ParamSpec((C,), (None,), pd),
        "gate_norm": ParamSpec((di,), (None,), pd),
        "out_proj": ParamSpec((di, D), ("heads_flat", "fsdp"), pd),
    }


def head_param_specs(cfg: ModelConfig) -> dict:
    """The per-head SSM vectors of every layer, (L, H) each."""
    L, H = cfg.n_layers, _dims(cfg)[1]
    return {k: ParamSpec((L, H), ("layers", None), cfg.param_dtype)
            for k in ("A_log", "D_skip", "dt_bias")}


def shared_block_specs(cfg: ModelConfig) -> dict:
    """One shared transformer block: attention over concat(h, emb) and the
    gated MLP (gate and up in one (D, 2F) weight, gate half first)."""
    D, Hq, K, hd, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                       cfg.d_ff)
    pd = cfg.param_dtype
    return {
        "attn_norm": ParamSpec((2 * D,), (None,), pd),
        "wq": ParamSpec((2 * D, Hq, hd), ("fsdp", "heads", None), pd),
        "wk": ParamSpec((2 * D, K, hd), ("fsdp", "kv_heads", None), pd),
        "wv": ParamSpec((2 * D, K, hd), ("fsdp", "kv_heads", None), pd),
        "wo": ParamSpec((Hq, hd, D), ("heads", None, "fsdp"), pd),
        "mlp_norm": ParamSpec((D,), (None,), pd),
        "w_gate_up": ParamSpec((D, 2 * F), ("fsdp", "mlp"), pd),
        "w_down": ParamSpec((F, D), ("mlp", "fsdp"), pd),
    }


def point_specs(cfg: ModelConfig) -> dict:
    """What one application point owns: its MLP adapter and its linear."""
    D, r, pd = cfg.d_model, cfg.adapter_rank, cfg.param_dtype
    out = {"linear": ParamSpec((D, D), ("fsdp", None), pd)}
    if r:
        out["adapter_a"] = ParamSpec((D, r), ("fsdp", None), pd)
        out["adapter_b"] = ParamSpec((r, 2 * cfg.d_ff), (None, "mlp"), pd)
    return out


def param_specs(cfg: ModelConfig) -> dict:
    pd = cfg.param_dtype
    specs = {
        "embed": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "fsdp"), pd),
        "mamba": [mamba_param_specs(cfg) for _ in range(cfg.n_layers)],
        "heads": head_param_specs(cfg),
        "shared": [shared_block_specs(cfg)
                   for _ in range(cfg.n_shared_blocks)],
        "points": [point_specs(cfg) for _ in cfg.hybrid_layers],
        "final_norm": ParamSpec((cfg.d_model,), (None,), pd),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = ParamSpec((cfg.d_model, cfg.vocab),
                                     ("fsdp", "vocab"), pd)
    return specs


# ---------------------------------------------------------------- SSD core

def _per_head(m, H: int):
    """B or C, (B, T, N) for one group or (B, T, G, N), as (B, T, H, N):
    head i reads group i // (H / G)."""
    if m.ndim == 3:
        m = m[:, :, None]
    return jnp.repeat(m, H // m.shape[2], axis=2)


def ssd_scan(x, dt, a, Bm, Cm, h0=None):
    """x: (B,T,H,hd); dt,a: (B,T,H); Bm,Cm: (B,T,N) or (B,T,G,N).
    Returns (y (B,T,H,hd), h_final (B,H,hd,N))."""
    B, T, H, hd = x.shape
    N = Bm.shape[-1]
    Bm, Cm = _per_head(Bm, H), _per_head(Cm, H)
    h_init = (jnp.zeros((B, H, hd, N), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))

    def step(h, inp):
        xt, dtt, at, bt, ct = inp  # (B,H,hd) (B,H) (B,H) (B,H,N) (B,H,N)
        dx = (dtt[..., None] * xt).astype(jnp.float32)       # (B,H,hd)
        h = at[..., None, None].astype(jnp.float32) * h + \
            dx[..., :, None] * bt[:, :, None, :].astype(jnp.float32)
        y = jnp.einsum("bhpn,bhn->bhp", h, ct.astype(jnp.float32))
        return h, y

    xs = jax.tree.map(lambda v: jnp.moveaxis(v, 1, 0), (x, dt, a, Bm, Cm))
    h_fin, ys = jax.lax.scan(step, h_init, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h_fin


def ssd_chunked(x, dt, a, Bm, Cm, h0=None, chunk: int = 32):
    """Block-parallel SSD (Mamba-2's matmul form). x: (B,T,H,hd);
    dt,a: (B,T,H); Bm,Cm: (B,T,N) or (B,T,G,N). State is touched once per
    chunk; all inner work is (C×C)/(C×N) matmuls. Matches ssd_scan
    (tested; log-decays floored at -20 per step — exp(-20)≈2e-9, below f32
    visibility of the O(1) state update — and -80 cumulative per chunk:
    exp(±80) is f32-safe, and a ≤4-step chunk (the serving prefill path)
    can never reach the floor, so the pairwise factors exp(ca_t - ca_s)
    are undistorted)."""
    B, T, H, hd = x.shape
    N = Bm.shape[-1]
    assert T % chunk == 0
    C = chunk
    n = T // C
    f32 = jnp.float32
    xc = x.astype(f32).reshape(B, n, C, H, hd)
    dtc = dt.astype(f32).reshape(B, n, C, H)
    Bc = _per_head(Bm, H).astype(f32).reshape(B, n, C, H, N)
    Cc = _per_head(Cm, H).astype(f32).reshape(B, n, C, H, N)
    la = jnp.clip(jnp.log(jnp.maximum(a.astype(f32), 1e-38)),
                  -20.0, 0.0).reshape(B, n, C, H)
    ca = jnp.maximum(jnp.cumsum(la, axis=2), -80.0)      # inclusive
    h_init = (jnp.zeros((B, H, hd, N), f32) if h0 is None
              else h0.astype(f32))

    # intra-chunk: scores[t,s] = (C_t·B_s)·exp(ca_t − ca_s)·dt_s, s ≤ t
    CB = jnp.einsum("bnthN,bnshN->bnhts", Cc, Bc)
    Et = jnp.exp(ca).transpose(0, 1, 3, 2)               # (B,n,H,C)
    Esi = (jnp.exp(-ca) * dtc).transpose(0, 1, 3, 2)
    scores = CB * Et[..., :, None] * Esi[..., None, :]
    mask = jnp.tril(jnp.ones((C, C), bool))              # inclusive diag
    scores = jnp.where(mask[None, None, None], scores, 0.0)
    y_intra = jnp.einsum("bnhts,bnshp->bnthp", scores, xc)

    # inter-chunk state scan
    a_tot = jnp.exp(ca[:, :, -1])                        # (B,n,H)
    k_rem = jnp.exp(ca[:, :, -1:, :] - ca) * dtc         # (B,n,C,H)

    def chunk_step(h, inp):
        Cc_c, ca_c, x_c, B_c, krem_c, atot_c = inp
        y_state = jnp.einsum("bthN,bhpN->bthp", Cc_c, h) * \
            jnp.exp(ca_c)[..., None]
        h_new = atot_c[:, :, None, None] * h + \
            jnp.einsum("bth,bthp,bthN->bhpN", krem_c, x_c, B_c)
        return h_new, y_state

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in
               (Cc, ca, xc, Bc, k_rem, a_tot))
    h_fin, y_state = jax.lax.scan(chunk_step, h_init, xs)
    y = y_intra + jnp.moveaxis(y_state, 0, 1)
    return y.reshape(B, T, H, hd).astype(x.dtype), h_fin


def gated_group_norm(y, z, gain, groups: int, eps: float):
    """Mamba-2's gated RMSNorm, gate first: ``y * silu(z)`` normalised over
    each of ``groups`` equal channel groups, then the gain."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    gs = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    gs = gs * jax.lax.rsqrt(jnp.mean(gs * gs, axis=-1, keepdims=True) + eps)
    return (gs.reshape(g.shape) * gain.astype(f32)).astype(y.dtype)


def mamba_layer(x, lp, cfg, conv_state=None, ssm_state=None, valid=None):
    """Returns (out, (new_conv_state, new_ssm_state)). ``valid`` ((B, T)
    bool) masks ragged-chunk padding out of the streaming state: invalid
    steps get dt=0 / a=1 (the SSD identity update) and the conv state
    advances only past each row's valid prefix. The step sizes, decays and
    state stay float32."""
    Bsz, T, D = x.shape
    di, H, N, G = _dims(cfg)
    dt_ = x.dtype
    z, xbc = jnp.split(linear(x, lp["in_proj"], "btd,de->bte"), [di],
                       axis=-1)
    dt = linear(x, lp["dt_proj"], "btd,dh->bth")
    n_valid = None if valid is None else valid.sum(1).astype(jnp.int32)
    xbc, conv_new = causal_conv1d(xbc, lp["conv_w"].astype(dt_), conv_state,
                                  n_valid=n_valid,
                                  bias=lp["conv_b"].astype(dt_))
    xbc = jax.nn.silu(xbc)
    xc, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    xh = xc.reshape(Bsz, T, H, SSM_HEAD_DIM)
    Bm, Cm = Bm.reshape(Bsz, T, G, N), Cm.reshape(Bsz, T, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         lp["dt_bias"].astype(jnp.float32))
    a = jnp.exp(-jnp.exp(lp["A_log"].astype(jnp.float32)) * dt)
    if valid is not None:
        vm = valid[..., None]                 # (B, T, 1) over heads
        dt = jnp.where(vm, dt, 0.0)           # Δx -> 0: no state injection
        a = jnp.where(vm, a, 1.0)             # decay 1: h untouched
    ck = cfg.linear_chunk
    if ssm_state is None:
        use_chunked = bool(ck and T > ck and T % ck == 0)
        chunk = ck
    else:
        # streaming (serving): multi-token chunks run the block-parallel
        # form seeded with the carried state — batched chunked prefill.
        # Inner chunk ≤ 4 so the cumulative log-decay (≥ -20/step after
        # the per-step clip) never reaches the -80 floor: pairwise decays
        # stay undistorted and greedy tokens match token-by-token decode.
        chunk = next((c for c in (4, 3, 2) if T % c == 0), 1)
        use_chunked = T > 1 and chunk > 1
    ssd = (lambda *args: ssd_chunked(*args, chunk=chunk)) if use_chunked \
        else ssd_scan
    with jax.named_scope("ssd"):
        y, ssm_new = ssd(xh, dt, a, Bm, Cm, ssm_state)
    y = y + lp["D_skip"].astype(dt_)[None, None, :, None] * xh
    y = gated_group_norm(y.reshape(Bsz, T, di), z, lp["gate_norm"], G,
                         cfg.norm_eps)
    out = linear(y, lp["out_proj"], "bte,ed->btd")
    return out, (conv_new, ssm_new)


def _mamba_residual(h, inj, lp, cfg, conv=None, ssm=None, valid=None):
    """``h + Mamba(RMSNorm(h + inj))`` (``inj`` None: no shared block
    feeds the layer), and the layer's new state."""
    with jax.named_scope("mamba"):
        x = rms_norm(h if inj is None else h + inj, lp["norm"], cfg.norm_eps)
        y, st = mamba_layer(x, lp, cfg, conv, ssm, valid)
    return h + y, st


def _shared_block(h, emb, sp, pp, positions, cfg, attend):
    """One application of a shared block: ``linear_p(t)``, the input it
    adds to its layer's Mamba. ``attend(q, k, v) -> (o, aux)`` does the
    attention (over the sequence, or through the point's cache); returns
    (linear_p(t), aux)."""
    with jax.named_scope("shared_attention"):
        u = rms_norm(jnp.concatenate([h, emb], axis=-1), sp["attn_norm"],
                     cfg.norm_eps)
        q, k, v = qkv_project(u, AttnParams(sp["wq"], sp["wk"], sp["wv"],
                                            sp["wo"]), positions, cfg)
        o, aux = attend(q, k, v)
        a = linear(o, sp["wo"], "btnh,nhd->btd")
    with jax.named_scope("shared_mlp"):
        n = rms_norm(a, sp["mlp_norm"], cfg.norm_eps)
        adapter = ((pp["adapter_a"], pp["adapter_b"]) if cfg.adapter_rank
                   else None)
        t = gelu_gated_mlp(n, sp["w_gate_up"], sp["w_down"], adapter)
        return linear(t, pp["linear"], "btd,de->bte"), aux


def _unembed(params, x, cfg):
    with jax.named_scope("unembed"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = linear(x, params["embed"], "btd,vd->btv")
        else:
            logits = linear(x, params["unembed"], "btd,dv->btv")
    return logits.astype(jnp.float32)


def _layer(params, l: int) -> dict:
    """Layer l's Mamba weights with its per-head vectors."""
    return {**params["mamba"][l],
            **{k: v[l] for k, v in params["heads"].items()}}


def apply(params, batch, cfg: ModelConfig):
    tokens = batch["tokens"]
    dt_ = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        emb = embed_lookup(params["embed"], tokens, dtype=dt_)
    positions = jnp.arange(tokens.shape[1])
    at = points(cfg)

    def attend(q, k, v):
        return flash_attention(q, k, v, positions, positions, causal=True,
                               chunk=cfg.attn_chunk,
                               scale=attn_scale(cfg)), None

    def layer(h, inj, lp):
        h, _ = _mamba_residual(constrain_act(h), inj, lp, cfg)
        return constrain_act(h)

    layer = jax.checkpoint(layer) if cfg.remat == "full" else layer
    h = emb
    for l in range(cfg.n_layers):
        inj = None
        if l in at:
            p = at[l]
            inj, _ = _shared_block(
                h, emb, params["shared"][p % cfg.n_shared_blocks],
                params["points"][p], positions, cfg, attend)
        h = layer(h, inj, _layer(params, l))
    return _unembed(params, h, cfg)


# ------------------------------------------------------------------ decode

def cache_spec(cfg: ModelConfig, batch_size: int, kv_len: int,
               slack: int = 0, windowed: bool = True):
    """One global-attention cache group per application point of the
    shared blocks (``k{p}``/``v{p}``, one layer each, named by its hybrid
    layer): the points share weights, not keys and values."""
    from repro.serve.cache import CacheGroup, CacheSpec, parse_kv_formats
    fmts = parse_kv_formats(cfg.kv_format, len(cfg.hybrid_layers), cfg.hd)
    full = kv_len + slack
    groups = tuple(CacheGroup(index=p, window=0, layers=(layer,),
                              length=full, fmt=fmts[p])
                   for p, layer in enumerate(cfg.hybrid_layers))
    return CacheSpec(groups, batch_size, cfg.n_kv_heads, cfg.hd,
                     cfg.kv_dtype or cfg.dtype, full)


def decode_state_specs(cfg: ModelConfig, batch_size: int, kv_len: int,
                       slack: int = 0, windowed: bool = True) -> dict:
    di, H, N, G = _dims(cfg)
    L = cfg.n_layers
    conv = ParamSpec((batch_size, cfg.conv_kernel - 1, di + 2 * G * N),
                     ("batch", None, None), cfg.dtype)
    ssm = ParamSpec((batch_size, H, SSM_HEAD_DIM, N),
                    ("batch", "heads", None, None), "float32")
    return {
        "conv": [conv] * L,
        "ssm": [ssm] * L,
        **cache_spec(cfg, batch_size, kv_len, slack, windowed).state_specs(),
        "pos": ParamSpec((batch_size,), ("batch",), "int32"),
    }


def decode_step(params, state, batch, cfg: ModelConfig):
    """Ragged decode step. batch: {"tokens": (B, T), "t_valid": optional
    (B,) advance counts, "reset": optional (B,) mask}. T>1 is batched
    chunked prefill through ``ssd_chunked``; each row's conv/ssm state and
    per-slot KV position advance by exactly ``t_valid[b]``, with padding
    masked out of the state updates. ``reset`` zeroes a slot's conv/ssm
    state and every application point's KV rows inside the step (slot
    reuse). The layers are unrolled: each reads its own weights and
    state leaves and returns new ones."""
    from repro.serve.cache import kv_codebook, parse_kv_formats
    tokens = batch["tokens"]  # (B, T)
    T = tokens.shape[1]
    dt_ = jnp.dtype(cfg.dtype)
    n_points, nb = len(cfg.hybrid_layers), cfg.n_shared_blocks
    fmts = parse_kv_formats(cfg.kv_format, n_points, cfg.hd)
    pos, adv, valid, st = ring_prologue(
        state, batch, n_points, extra_reset={"conv": 0, "ssm": 0},
        formats=fmts)
    with jax.named_scope("embed"):
        emb = embed_lookup(params["embed"], tokens, dtype=dt_)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]  # (B, T)
    new_state = {"pos": pos + adv}

    def point(p, h):
        """The p-th application: its shared block through its own cache."""
        cb = None if fmts[p] == "f32" else kv_codebook(fmts[p])
        if cb is None:
            kc, vc = st[f"k{p}"][0], st[f"v{p}"][0]
        else:
            kc = QuantisedKV(st[f"k{p}"][0], st[f"k{p}s"][0])
            vc = QuantisedKV(st[f"v{p}"][0], st[f"v{p}s"][0])

        def attend(q, k, v):
            kn = update_kv_cache(kc, k, pos, codebook=cb)
            vn = update_kv_cache(vc, v, pos, codebook=cb)
            return chunked_decode_attention(q, kn, vn, positions,
                                            codebook=cb,
                                            scale=attn_scale(cfg)), (kn, vn)

        inj, (kc, vc) = _shared_block(h, emb, params["shared"][p % nb],
                                      params["points"][p], positions, cfg,
                                      attend)
        if cb is None:
            new_state.update({f"k{p}": kc[None], f"v{p}": vc[None]})
        else:
            new_state.update({f"k{p}": kc.codes[None],
                              f"k{p}s": kc.scales[None],
                              f"v{p}": vc.codes[None],
                              f"v{p}s": vc.scales[None]})
        return inj

    at, conv, ssm = points(cfg), [], []
    h = emb
    for l in range(cfg.n_layers):
        inj = point(at[l], h) if l in at else None
        h, (cs, ss) = _mamba_residual(h, inj, _layer(params, l), cfg,
                                      st["conv"][l], st["ssm"][l], valid)
        conv.append(cs.astype(st["conv"][l].dtype))
        ssm.append(ss)
    new_state.update(conv=conv, ssm=ssm)
    return _unembed(params, h, cfg), new_state


def init(rng, cfg: ModelConfig):
    from .api import init_from_specs
    params = init_from_specs(rng, param_specs(cfg))
    L, H = cfg.n_layers, _dims(cfg)[1]
    rng_np = np.random.default_rng(0)
    hp = params["heads"]
    hp["A_log"] = jnp.asarray(np.log(rng_np.uniform(1, 16, (L, H))),
                              jnp.float32)
    hp["dt_bias"] = jnp.asarray(
        np.log(np.expm1(rng_np.uniform(1e-3, 0.1, (L, H)))), jnp.float32)
    hp["D_skip"] = jnp.ones((L, H), jnp.float32)
    for lp in params["mamba"]:
        lp["conv_w"] = jnp.asarray(
            rng_np.normal(0, 0.1, lp["conv_w"].shape), jnp.float32)
    return params


def pack_layouts(cfg: ModelConfig) -> dict:
    """Packed-serving layouts: every matmul weight is a tensor of its own
    (0 lead dims): each layer's Mamba projections, each shared block's,
    each point's adapter and linear. The depthwise conv, its bias and the
    per-head SSM vectors are not matmuls."""
    lay = {f"['mamba'][{l}]['{k}']": (0, 1) for l in range(cfg.n_layers)
           for k in ("in_proj", "dt_proj", "out_proj")}
    for b in range(cfg.n_shared_blocks):
        lay.update({f"['shared'][{b}]['{k}']": (0, 2 if k == "wo" else 1)
                    for k in ("wq", "wk", "wv", "wo", "w_gate_up",
                              "w_down")})
    point = ("linear", "adapter_a", "adapter_b") if cfg.adapter_rank \
        else ("linear",)
    for p in range(len(cfg.hybrid_layers)):
        lay.update({f"['points'][{p}]['{k}']": (0, 1) for k in point})
    lay["['embed']"] = (0, 1)
    if not cfg.tie_embeddings:
        lay["['unembed']"] = (0, 1)
    return lay


register_family(ModelFamily(
    name="zamba2",
    param_specs=param_specs,
    init=init,
    apply=apply,
    decode_state_specs=decode_state_specs,
    decode_step=decode_step,
    prefill=apply,
    supports_ragged=True,
    cache_spec=cache_spec,
    pack_layouts=pack_layouts,
))
