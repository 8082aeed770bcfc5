"""Decoder-only transformer (llama style): RMSNorm, grouped-query
attention with rotary positions (the two halves of each head rotate
together), SwiGLU MLP, embeddings optionally tied to the unembedding."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import rms_norm


def layout(m: dict) -> dict:
    L, D, H, K = m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd, F, V = m["head_dim"], m["d_ff"], m["vocab"]
    one = ("const", 1.0)
    fan = lambda k: ("normal", k ** -0.5)
    # the products that write into the residual stream are scaled by
    # 1/sqrt(2L), as in GPT-2's init, and the embedding has std 1: at the
    # usual 0.02 every position of a deep random model collapses onto one
    # token
    out = lambda k: ("normal", (2 * L * k) ** -0.5)
    p = {
        "embed": ((V, D), ("normal", 1.0)),
        "layers": {
            "attn_norm": ((L, D), one),
            "wq": ((L, D, H, hd), fan(D)),
            "wk": ((L, D, K, hd), fan(D)),
            "wv": ((L, D, K, hd), fan(D)),
            "wo": ((L, H, hd, D), out(H * hd)),
            "mlp_norm": ((L, D), one),
            "w_gate": ((L, D, F), fan(D)),
            "w_up": ((L, D, F), fan(D)),
            "w_down": ((L, F, D), out(F)),
        },
        "final_norm": ((D,), one),
    }
    if not m["tie_embeddings"]:
        p["unembed"] = ((D, V), fan(D))
    return p


def matmuls(m: dict) -> list:
    L, D, H, K = m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd, F, V = m["head_dim"], m["d_ff"], m["vocab"]
    return [(L, D, H * hd), (L, D, K * hd), (L, D, K * hd), (L, H * hd, D),
            (L, D, F), (L, D, F), (L, F, D), (1, D, V)]


def attention(m: dict) -> dict:
    return {"n_layers": m["n_layers"], "n_heads": m["n_heads"],
            "n_kv_heads": m["n_kv_heads"], "head_dim": m["head_dim"]}


def rope(x, pos, theta):
    """x (T, n, hd): rotate the pair (x[i], x[i + hd/2]) by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(p, tokens, m: dict, mm):
    T = tokens.shape[0]
    H, K, hd, eps = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["norm_eps"]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    rnd = mm.round
    x = rnd(p["embed"][tokens].astype(jnp.float32))

    def layer(x, lp):
        h = rms_norm(x, lp["attn_norm"], eps)
        q = rope(mm("td,dnh->tnh", h, lp["wq"]), pos, m["rope_theta"])
        k = rope(mm("td,dnh->tnh", h, lp["wk"]), pos, m["rope_theta"])
        v = mm("td,dnh->tnh", h, lp["wv"])
        k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
        s = mm("qnh,knh->nqk", q, k) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        x = rnd(x + mm("tnh,nhd->td", mm("nqk,knh->qnh", a, v), lp["wo"]))
        h = rms_norm(x, lp["mlp_norm"], eps)
        g = jax.nn.silu(mm("td,df->tf", h, lp["w_gate"]))
        x = rnd(x + mm("tf,fd->td", g * mm("td,df->tf", h, lp["w_up"]),
                       lp["w_down"]))
        return x, None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = rms_norm(x, p["final_norm"], eps)
    if m["tie_embeddings"]:
        return mm("td,vd->tv", x, p["embed"])
    return mm("td,dv->tv", x, p["unembed"])
