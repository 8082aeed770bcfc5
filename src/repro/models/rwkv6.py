"""RWKV-6 "Finch" (arXiv:2404.05892): attention-free LM with token-shift
mixing and **data-dependent decay** in the WKV linear-attention state.

Per head (dim hd), state S ∈ R^{hd×hd}:
    y_t[j] = Σ_i r_t[i] · (S[i,j] + u[i]·k_t[i]·v_t[j])
    S[i,j] ← w_t[i]·S[i,j] + k_t[i]·v_t[j],   w_t = exp(-exp(w0 + LoRA(x_t)))

Training uses a lax.scan over time (a chunked matmul-parallel form is a
recorded §Perf candidate); decode carries (shift, S) state — O(1)/token, so
the long_500k cell is natively supported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .api import (ModelConfig, ModelFamily, ParamSpec, ragged_prologue,
                  register_family)
from .layers import embed_lookup, linear, rms_norm

LORA_R = 64
HEAD_DIM = 64


def _n_heads(cfg):
    return cfg.d_model // HEAD_DIM


def layer_param_specs(cfg: ModelConfig) -> dict:
    D, F, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    H, hd = _n_heads(cfg), HEAD_DIM
    pd = cfg.param_dtype
    lx = lambda *s: ("layers",) + tuple(s)
    return {
        "norm_tm": ParamSpec((L, D), lx(None), pd),
        "norm_cm": ParamSpec((L, D), lx(None), pd),
        # token-shift lerp coefficients
        "mu_r": ParamSpec((L, D), lx(None), pd),
        "mu_k": ParamSpec((L, D), lx(None), pd),
        "mu_v": ParamSpec((L, D), lx(None), pd),
        "mu_g": ParamSpec((L, D), lx(None), pd),
        "mu_w": ParamSpec((L, D), lx(None), pd),
        # data-dependent decay: w = exp(-exp(w0 + tanh(xw A) B))
        "w0": ParamSpec((L, D), lx(None), pd),
        "w_lora_a": ParamSpec((L, D, LORA_R), lx("fsdp", None), pd),
        "w_lora_b": ParamSpec((L, LORA_R, D), lx(None, "fsdp"), pd),
        "bonus_u": ParamSpec((L, H, hd), lx("heads", None), pd),
        # projections
        "wr": ParamSpec((L, D, D), lx("fsdp", "heads_flat"), pd),
        "wk": ParamSpec((L, D, D), lx("fsdp", "heads_flat"), pd),
        "wv": ParamSpec((L, D, D), lx("fsdp", "heads_flat"), pd),
        "wg": ParamSpec((L, D, D), lx("fsdp", "heads_flat"), pd),
        "wo": ParamSpec((L, D, D), lx("heads_flat", "fsdp"), pd),
        "ln_x": ParamSpec((L, D), lx(None), pd),  # per-head group norm gain
        # channel mix
        "mu_ck": ParamSpec((L, D), lx(None), pd),
        "mu_cr": ParamSpec((L, D), lx(None), pd),
        "wck": ParamSpec((L, D, F), lx("fsdp", "mlp"), pd),
        "wcv": ParamSpec((L, F, D), lx("mlp", "fsdp"), pd),
        "wcr": ParamSpec((L, D, D), lx("fsdp", None), pd),
    }


def param_specs(cfg: ModelConfig) -> dict:
    pd = cfg.param_dtype
    return {
        "embed": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "fsdp"), pd),
        "layers": layer_param_specs(cfg),
        "final_norm": ParamSpec((cfg.d_model,), (None,), pd),
        "unembed": ParamSpec((cfg.d_model, cfg.vocab), ("fsdp", "vocab"), pd),
    }


def _shift(x, last=None):
    """Token shift: x_{t-1} (zeros / `last` at t=0). x: (B, T, D)."""
    pad = jnp.zeros_like(x[:, :1]) if last is None else last[:, None]
    return jnp.concatenate([pad, x[:, :-1]], axis=1)


def _group_norm(y, gain, eps):
    """Per-head LayerNorm over hd. y: (B, T, H, hd); gain: (D,)."""
    m = jnp.mean(y, axis=-1, keepdims=True)
    v = jnp.var(y, axis=-1, keepdims=True)
    yn = (y - m) * jax.lax.rsqrt(v + eps)
    B, T, H, hd = y.shape
    return yn.reshape(B, T, -1) * gain.astype(y.dtype)


def wkv_scan(r, k, v, w, u, s0=None):
    """The WKV recurrence, one step at a time. r/k/v/w: (B, T, H, hd);
    u: (H, hd). Returns (y (B,T,H,hd), final state (B,H,hd,hd))."""
    B, T, H, hd = r.shape
    s_init = (jnp.zeros((B, H, hd, hd), jnp.float32) if s0 is None
              else s0.astype(jnp.float32))

    def step(s, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None].astype(jnp.float32) * vt[..., None, :].astype(jnp.float32)
        att = s + u[None, :, :, None].astype(jnp.float32) * kv
        y = jnp.einsum("bhi,bhij->bhj", rt.astype(jnp.float32), att)
        s_new = wt[..., :, None].astype(jnp.float32) * s + kv
        return s_new, y

    xs = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), (r, k, v, w))
    s_fin, ys = jax.lax.scan(step, s_init, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), s_fin


_LOG_CLAMP = -20.0   # per-STEP log-decay floor (numerics; exp(-20)≈2e-9 —
                     # below f32 visibility of the O(1) state update)
_CUM_CLAMP = -80.0   # per-chunk CUMULATIVE floor: exp(±80) stays finite in
                     # f32; deep enough that a ≤4-step chunk (the serving
                     # prefill path) never hits it, so the pairwise decay
                     # factors exp(cw_t - cw_s) are undistorted — a -20
                     # cumulative floor made saturated fast-decay channels
                     # collapse to decay 1 between floored positions


def wkv_chunked(r, k, v, w, u, s0=None, chunk: int = 32):
    """Block-parallel WKV (matmul form — the TPU-native formulation).

    Within a chunk of length C, with cumulative decays W_t = Π_{s≤t} w_s:
        y_t = r_t·(decay(·)·k_s v_sᵀ masked s<t) + r_t·(u⊙k_t) v_tᵀ
              + (r_t⊙W_{t-1})·S_prev
        S ← (W_C)⊙S_prev + Σ_s (k_s·W_C/W_s) v_sᵀ
    so the recurrent state is touched once per CHUNK (O(T/C) HBM traffic
    instead of O(T)), and all inner work is (C×C)/(C×hd) matmuls for the
    MXU. Matches wkv_scan (tested); decays are floored in log space at -20
    per step and -80 cumulative per chunk for f32 safety (exact for chunks
    of ≤4 steps — the serving prefill path).
    """
    B, T, H, hd = r.shape
    assert T % chunk == 0, (T, chunk)
    C = chunk
    n = T // C
    f32 = jnp.float32
    rs = r.astype(f32).reshape(B, n, C, H, hd)
    ks = k.astype(f32).reshape(B, n, C, H, hd)
    vs = v.astype(f32).reshape(B, n, C, H, hd)
    logw = jnp.clip(jnp.log(jnp.maximum(w.astype(f32), 1e-38)),
                    _LOG_CLAMP, 0.0).reshape(B, n, C, H, hd)
    s_init = (jnp.zeros((B, H, hd, hd), f32) if s0 is None
              else s0.astype(f32))
    u32 = u.astype(f32)

    # cumulative within chunk: cw_t = Σ_{s<=t} log w_s  (inclusive)
    cw = jnp.cumsum(logw, axis=2)
    cw = jnp.maximum(cw, _CUM_CLAMP)
    w_tot = jnp.exp(cw[:, :, -1])                    # (B,n,H,hd)
    # decay applied to incoming state at step t: Π_{s<t} w_s = cw_{t-1}
    cw_excl = jnp.concatenate(
        [jnp.zeros_like(cw[:, :, :1]), cw[:, :, :-1]], axis=2)
    r_dec = rs * jnp.exp(cw_excl)                    # r_t ⊙ W_{t-1}
    k_inv = ks * jnp.exp(-cw)                        # k_s / W_s
    k_rem = ks * jnp.exp(cw[:, :, -1:] - cw)         # k_s · W_C/W_s

    # intra-chunk attention (state-free, fully parallel over chunks):
    # scores[t,s] = Σ_i r_dec[t,i]·k_inv[s,i], causal strictly below diag
    scores = jnp.einsum("bnthi,bnshi->bnhts", r_dec, k_inv)
    mask = jnp.tril(jnp.ones((C, C), bool), k=-1)
    scores = jnp.where(mask[None, None, None], scores, 0.0)
    y_intra = jnp.einsum("bnhts,bnshj->bnthj", scores, vs)
    # diagonal (current-token) bonus term: r_t·(u⊙k_t) v_t
    coef = jnp.einsum("bnthi,hi->bnth", rs * ks, u32)
    y_intra = y_intra + coef[..., None] * vs

    # inter-chunk: only the state crosses chunk boundaries (scan over n)
    def chunk_step(S, inp):
        r_dec_c, k_rem_c, v_c, w_tot_c = inp   # (B,C,H,hd)… (B,H,hd)
        y_state = jnp.einsum("bthi,bhij->bthj", r_dec_c, S)
        S_new = w_tot_c[..., :, None] * S + \
            jnp.einsum("bthi,bthj->bhij", k_rem_c, v_c)
        return S_new, y_state

    xs = (jnp.moveaxis(r_dec, 1, 0), jnp.moveaxis(k_rem, 1, 0),
          jnp.moveaxis(vs, 1, 0), jnp.moveaxis(w_tot, 1, 0))
    s_fin, y_state = jax.lax.scan(chunk_step, s_init, xs)
    y = y_intra + jnp.moveaxis(y_state, 0, 1)
    return y.reshape(B, T, H, hd).astype(r.dtype), s_fin


def _last_valid(x, valid, last_x):
    """Token-shift state after a ragged chunk: row b's input at its last
    valid position (``valid``: (B, T) bool); rows with no valid token keep
    ``last_x``. x: (B, T, D)."""
    B, T, _ = x.shape
    li = jnp.clip(valid.sum(1) - 1, 0, T - 1)
    nl = jnp.take_along_axis(x, li[:, None, None], axis=1)[:, 0]
    keep = valid.any(1)[:, None]
    return nl if last_x is None else jnp.where(keep, nl, last_x)


def time_mix(x, lp, cfg, last_x=None, s0=None, valid=None):
    """Returns (out, (new_last_x, new_state)). ``valid`` ((B, T) bool) masks
    ragged-chunk padding out of the recurrent state: invalid steps get
    k=0 / w=1 (the WKV identity update), and the token-shift state advances
    to each row's last *valid* input."""
    B, T, D = x.shape
    H, hd = _n_heads(cfg), HEAD_DIM
    dt = x.dtype
    xs = _shift(x, last_x)

    def lerp(mu):
        return x + (xs - x) * mu.astype(dt)

    r = linear(lerp(lp["mu_r"]), lp["wr"], "btd,de->bte")
    k = linear(lerp(lp["mu_k"]), lp["wk"], "btd,de->bte")
    v = linear(lerp(lp["mu_v"]), lp["wv"], "btd,de->bte")
    g = linear(lerp(lp["mu_g"]), lp["wg"], "btd,de->bte")
    # data-dependent decay (the Finch contribution)
    w_lora = linear(jnp.tanh(linear(lerp(lp["mu_w"]), lp["w_lora_a"],
                                    "btd,dr->btr")),
                    lp["w_lora_b"], "btr,rd->btd")
    w = jnp.exp(-jnp.exp((lp["w0"].astype(jnp.float32) +
                          w_lora.astype(jnp.float32))))
    if valid is not None:
        vm = valid[..., None]
        k = jnp.where(vm, k, 0.0).astype(k.dtype)   # kv outer product -> 0
        w = jnp.where(vm, w, 1.0)                   # decay 1: S untouched
    hsplit = lambda a: a.reshape(B, T, H, hd)
    ck = cfg.linear_chunk
    if s0 is None:
        use_chunked = bool(ck and T > ck and T % ck == 0)
        chunk = ck
    else:
        # streaming (serving): multi-token chunks run the block-parallel
        # form seeded with the carried state — batched chunked prefill.
        # Inner chunk ≤ 4 so the cumulative log-decay (≥ -20/step) never
        # reaches the -80 floor: pairwise decays stay undistorted and
        # greedy tokens match the token-by-token scan.
        chunk = next((c for c in (4, 3, 2) if T % c == 0), 1)
        use_chunked = T > 1 and chunk > 1
    wkv = (lambda *a: wkv_chunked(*a, chunk=chunk)) if use_chunked \
        else wkv_scan
    y, s_fin = wkv(hsplit(r), hsplit(k), hsplit(v),
                   hsplit(w.astype(dt)), lp["bonus_u"], s0)
    y = _group_norm(y, lp["ln_x"], cfg.norm_eps)
    y = y * jax.nn.silu(g)
    out = linear(y.astype(dt), lp["wo"], "btd,de->bte")
    new_last = x[:, -1] if valid is None else _last_valid(x, valid, last_x)
    return out, (new_last, s_fin)


def channel_mix(x, lp, cfg, last_x=None, valid=None):
    dt = x.dtype
    xs = _shift(x, last_x)
    xk = x + (xs - x) * lp["mu_ck"].astype(dt)
    xr = x + (xs - x) * lp["mu_cr"].astype(dt)
    r = jax.nn.sigmoid(linear(xr, lp["wcr"], "btd,de->bte"))
    k = jnp.square(jax.nn.relu(linear(xk, lp["wck"], "btd,df->btf")))
    out = r * linear(k, lp["wcv"], "btf,fd->btd")
    new_last = x[:, -1] if valid is None else _last_valid(x, valid, last_x)
    return out, new_last


def apply(params, batch, cfg: ModelConfig):
    tokens = batch["tokens"]
    dt = jnp.dtype(cfg.dtype)
    x = embed_lookup(params["embed"], tokens, dtype=dt)

    def body(x, lp):
        from .layers import constrain_act
        x = constrain_act(x)
        h, _ = time_mix(rms_norm(x, lp["norm_tm"], cfg.norm_eps), lp, cfg)
        x = x + h
        h, _ = channel_mix(rms_norm(x, lp["norm_cm"], cfg.norm_eps), lp, cfg)
        return constrain_act(x + h), None

    body_fn = jax.checkpoint(body) if cfg.remat == "full" else body
    x, _ = jax.lax.scan(body_fn, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = linear(x, params["unembed"], "btd,dv->btv")
    return logits.astype(jnp.float32)


# ------------------------------------------------------------------ decode

def decode_state_specs(cfg: ModelConfig, batch_size: int, kv_len: int,
                       slack: int = 0, windowed: bool = True) -> dict:
    """Recurrent state: O(1) in sequence length (kv_len — and the grouped
    ring-cache knobs ``slack``/``windowed`` — unused: there is no KV cache
    to group; that is the point of an SSM for the long_500k cell). ``pos``
    is per-slot ((B,) int32): the ragged serving protocol (see
    ``ModelFamily``)."""
    D, L = cfg.d_model, cfg.n_layers
    H, hd = _n_heads(cfg), HEAD_DIM
    cd = cfg.dtype
    return {
        "tm_x": ParamSpec((L, batch_size, D), ("layers", "batch", None), cd),
        "cm_x": ParamSpec((L, batch_size, D), ("layers", "batch", None), cd),
        "wkv": ParamSpec((L, batch_size, H, hd, hd),
                         ("layers", "batch", "heads", None, None), "float32"),
        "pos": ParamSpec((batch_size,), ("batch",), "int32"),
    }


def decode_step(params, state, batch, cfg: ModelConfig):
    """Ragged decode step. batch: {"tokens": (B, T), "t_valid": optional
    (B,) advance counts, "reset": optional (B,) mask}. T=1 is plain decode;
    T>1 is batched chunked prefill through ``wkv_chunked``. Row b's
    recurrent state advances by exactly ``t_valid[b]`` tokens — padding
    beyond it is masked out of the WKV and token-shift updates. A set
    ``reset`` bit zeroes that slot's state (shift buffers + WKV matrix)
    before any token is processed, so a reused serving slot never sees the
    previous request's state."""
    tokens = batch["tokens"]  # (B, T)
    dt = jnp.dtype(cfg.dtype)
    pos, adv, valid, st = ragged_prologue(
        state, batch, {"tm_x": 1, "cm_x": 1, "wkv": 1})
    tm_x, cm_x, wkv_s = st["tm_x"], st["cm_x"], st["wkv"]
    # named scopes (embed, time_mix, channel_mix, unembed) put the layer kind
    # into each operation's op_name in the compiled step's metadata
    with jax.named_scope("embed"):
        x = embed_lookup(params["embed"], tokens, dtype=dt)

    def body(x, inputs):
        lp, tm, cm, s = inputs
        with jax.named_scope("time_mix"):
            h, (tm_new, s_new) = time_mix(
                rms_norm(x, lp["norm_tm"], cfg.norm_eps), lp, cfg,
                last_x=tm.astype(dt), s0=s, valid=valid)
            x = x + h
        with jax.named_scope("channel_mix"):
            h, cm_new = channel_mix(
                rms_norm(x, lp["norm_cm"], cfg.norm_eps), lp, cfg,
                last_x=cm.astype(dt), valid=valid)
        return x + h, (tm_new.astype(tm.dtype), cm_new.astype(cm.dtype),
                       s_new)

    x, (tm, cm, wkv) = jax.lax.scan(
        body, x, (params["layers"], tm_x, cm_x, wkv_s))
    with jax.named_scope("unembed"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = linear(x, params["unembed"], "btd,dv->btv").astype(
            jnp.float32)
    new_state = {"tm_x": tm, "cm_x": cm, "wkv": wkv, "pos": pos + adv}
    return logits, new_state


def init(rng, cfg: ModelConfig):
    from .api import init_from_specs
    params = init_from_specs(rng, param_specs(cfg))
    # decay bias init: spread per-channel decays (standard RWKV init)
    L, D = cfg.n_layers, cfg.d_model
    import numpy as np
    decay = -5.0 + 8.0 * (np.arange(D) / max(D - 1, 1)) ** 3.0
    params["layers"]["w0"] = jnp.tile(jnp.asarray(decay, jnp.float32), (L, 1))
    for mu in ["mu_r", "mu_k", "mu_v", "mu_g", "mu_w", "mu_ck", "mu_cr"]:
        params["layers"][mu] = jnp.full((L, D), 0.5, jnp.float32)
    return params


def pack_layouts(cfg: ModelConfig) -> dict:
    """Packed-serving layouts: every projection in time-mix (r/k/v/g, the
    decay LoRA pair, the output) and channel-mix, plus embed/unembed. The
    token-shift lerp coefficients, decay bias and group-norm gains are
    elementwise vectors — below the quantisable floor, never packed."""
    lay = {f"['layers']['{n}']": (1, 1)
           for n in ("wr", "wk", "wv", "wg", "wo",
                     "w_lora_a", "w_lora_b", "wck", "wcv", "wcr")}
    lay["['embed']"] = (0, 1)
    lay["['unembed']"] = (0, 1)
    return lay


register_family(ModelFamily(
    name="rwkv6",
    param_specs=param_specs,
    init=init,
    apply=apply,
    decode_state_specs=decode_state_specs,
    decode_step=decode_step,
    prefill=apply,
    supports_ragged=True,
    pack_layouts=pack_layouts,
))
