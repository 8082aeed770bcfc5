"""Zamba2 (arXiv:2411.15242; transformers' ``Zamba2``), as the program
states its block (departures from the published checkpoint are listed
under ``assumed`` in the configuration file). Every layer is a pre-norm
Mamba-2 layer on the residual stream h:

    h <- h + Mamba2(RMSNorm(h + linear_p(t)))   at the p-th hybrid layer
    h <- h + Mamba2(RMSNorm(h))                 elsewhere

where t is the output of shared block p mod n_shared_blocks on
concat(h, emb): multi-head attention with RoPE and the score scale
(head_dim / 2)^-0.5, then, with no residual, an RMSNorm and the MLP
down(gelu(g) * v), [g, v] = gate_up(n) + B_p(A_p(n)). Mamba-2, per head
i of 64 channels reading B and C of group g = i // (heads per group):

    S_i <- exp(-exp(A_log_i) dt_i) S_i + dt_i x_i B_g^T,
    y_i = S_i C_g + D_i x_i,

dt = softplus(dt_proj(x) + dt_bias), after a causal depthwise conv with
bias and a SiLU over [x, B, C]; the output is out_proj of y * silu(z)
RMS-normed over each of the ssm_groups channel groups. The recurrence runs
one token at a time, in float32; the head is tied to the embedding."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import HIGHEST, rms_norm
from .transformer import rope

HEAD = 64


def _sizes(m: dict):
    di, N, G = m["d_inner"], m["ssm_state"], m["ssm_groups"]
    return di, di // HEAD, N, G, di + 2 * G * N


def layout(m: dict) -> dict:
    L, D, V = m["n_layers"], m["d_model"], m["vocab"]
    H, K, hd, F = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    di, Hs, N, G, C = _sizes(m)
    Kw, r = m["conv_kernel"], m["adapter_rank"]
    one = ("const", 1.0)
    fan = lambda k: ("normal", k ** -0.5)
    block = {
        "attn_norm": ((2 * D,), one),
        "wq": ((2 * D, H, hd), fan(2 * D)),
        "wk": ((2 * D, K, hd), fan(2 * D)),
        "wv": ((2 * D, K, hd), fan(2 * D)),
        "wo": ((H, hd, D), fan(H * hd)),
        "mlp_norm": ((D,), one),
        "w_gate_up": ((D, 2 * F), fan(D)),
        "w_down": ((F, D), fan(F)),
    }
    point = {"linear": ((D, D), fan(D)),
             "adapter_a": ((D, r), fan(D)),
             "adapter_b": ((r, 2 * F), fan(r))}
    layer = {
        "norm": ((D,), one),
        "in_proj": ((D, di + C), fan(D)),
        "dt_proj": ((D, Hs), fan(D)),
        "conv_w": ((Kw, C), fan(Kw)),
        "conv_b": ((C,), ("uniform", -Kw ** -0.5, Kw ** -0.5)),
        "gate_norm": ((di,), one),
        "out_proj": ((di, D), fan(di)),
    }
    return {
        # std 0.02 keeps the tied head's logits near unit scale (std
        # 0.02 sqrt(D)); the first layer's RMSNorm takes the embedding to
        # unit scale
        "embed": ((V, D), ("normal", 0.02)),
        "mamba": [layer] * L,
        # per head, stacked over layers: A = exp(A_log) on [1, 16] and
        # dt_bias the inverse softplus of [1e-3, 0.1], the published
        # init's ranges
        "heads": {
            "A_log": ((L, Hs), ("uniform", 0.0, math.log(16.0))),
            "D_skip": ((L, Hs), one),
            "dt_bias": ((L, Hs), ("uniform", math.log(math.expm1(1e-3)),
                                  math.log(math.expm1(0.1)))),
        },
        "shared": [block] * m["n_shared_blocks"],
        "points": [point] * len(m["hybrid_layers"]),
        "final_norm": ((D,), one),
    }


def matmuls(m: dict) -> list:
    """The packed products of one token: each Mamba layer's [z, x, B, C]
    and output projections (its 112-wide dt projection is served dense:
    112 is no whole number of weight blocks), each hybrid point's shared
    block, adapter and linear, and the tied head."""
    L, D, V = m["n_layers"], m["d_model"], m["vocab"]
    H, K, hd, F = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["d_ff"]
    di, _, _, _, C = _sizes(m)
    P, r = len(m["hybrid_layers"]), m["adapter_rank"]
    return [(L, D, di + C), (L, di, D),
            (P, 2 * D, H * hd), (P, 2 * D, K * hd), (P, 2 * D, K * hd),
            (P, H * hd, D), (P, D, 2 * F), (P, D, r), (P, r, 2 * F),
            (P, F, D), (P, D, D), (1, D, V)]


def attention(m: dict) -> dict:
    return {"n_layers": len(m["hybrid_layers"]), "n_heads": m["n_heads"],
            "n_kv_heads": m["n_kv_heads"], "head_dim": m["head_dim"]}


def ssd(x, dt, a, Bh, Ch):
    """The recurrence over T tokens; x (T, Hs, 64), dt/a (T, Hs), Bh/Ch
    (T, Hs, N)."""
    Hs, N = x.shape[1], Bh.shape[-1]

    def step(S, inp):
        xt, dtt, at, bt, ct = inp
        S = at[:, None, None] * S + (dtt[:, None] * xt)[:, :, None] * \
            bt[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, ct, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((Hs, HEAD, N), jnp.float32),
                        (x, dt, a, Bh, Ch))
    return y


def mamba(x, lp, m: dict, mm):
    T, eps = x.shape[0], m["norm_eps"]
    di, Hs, N, G, C = _sizes(m)
    Kw = m["conv_kernel"]
    zx = mm("td,de->te", x, lp["in_proj"])
    z, xbc = zx[:, :di], zx[:, di:]
    dt = mm("td,dh->th", x, lp["dt_proj"])
    xp = jnp.concatenate([jnp.zeros((Kw - 1, C), jnp.float32), xbc])
    xbc = jax.nn.silu(sum(xp[k:k + T] * lp["conv_w"][k] for k in range(Kw))
                      + lp["conv_b"])
    xs = xbc[:, :di].reshape(T, Hs, HEAD)
    group = jnp.arange(Hs) // (Hs // G)
    Bh = xbc[:, di:di + G * N].reshape(T, G, N)[:, group]
    Ch = xbc[:, di + G * N:].reshape(T, G, N)[:, group]
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    a = jnp.exp(-jnp.exp(lp["A_log"]) * dt)
    y = ssd(xs, dt, a, Bh, Ch) + lp["D_skip"][None, :, None] * xs
    y = (y.reshape(T, di) * jax.nn.silu(z)).reshape(T, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return mm("te,ed->td", y.reshape(T, di) * lp["gate_norm"], lp["out_proj"])


def shared(h, emb, sp, pp, m: dict, mm):
    """linear_p(t) of one application of a shared block."""
    T, eps, theta = h.shape[0], m["norm_eps"], m["rope_theta"]
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pos = jnp.arange(T)
    u = rms_norm(jnp.concatenate([h, emb], axis=-1), sp["attn_norm"], eps)
    q = rope(mm("td,dnh->tnh", u, sp["wq"]), pos, theta)
    k = rope(mm("td,dnh->tnh", u, sp["wk"]), pos, theta)
    v = mm("td,dnh->tnh", u, sp["wv"])
    k, v = jnp.repeat(k, H // K, axis=1), jnp.repeat(v, H // K, axis=1)
    s = mm("qnh,knh->nqk", q, k) * (hd / 2) ** -0.5
    causal = pos[:, None] >= pos[None, :]
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = mm("tnh,nhd->td", mm("nqk,knh->qnh", w, v), sp["wo"])
    n = rms_norm(a, sp["mlp_norm"], eps)
    gu = mm("td,df->tf", n, sp["w_gate_up"]) + mm(
        "tr,rf->tf", mm("td,dr->tr", n, pp["adapter_a"]), pp["adapter_b"])
    g, v = jnp.split(gu, 2, axis=-1)
    t = mm("tf,fd->td", jax.nn.gelu(g, approximate=False) * v, sp["w_down"])
    return mm("td,de->te", t, pp["linear"])


def forward(p, tokens, m: dict, mm):
    rnd, eps = mm.round, m["norm_eps"]
    emb = rnd(p["embed"][tokens].astype(jnp.float32))
    point = {layer: i for i, layer in enumerate(m["hybrid_layers"])}
    h = emb
    for l in range(m["n_layers"]):
        x = h
        if l in point:
            i = point[l]
            x = rnd(h + shared(h, emb,
                               p["shared"][i % m["n_shared_blocks"]],
                               p["points"][i], m, mm))
        lp = {**p["mamba"][l], **{k: v[l] for k, v in p["heads"].items()}}
        h = rnd(h + mamba(rms_norm(x, lp["norm"], eps), lp, m, mm))
    return mm("td,vd->tv", rms_norm(h, p["final_norm"], eps), p["embed"])
