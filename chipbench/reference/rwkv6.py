"""RWKV-6 "Finch" (arXiv:2404.05892), as the program states its block
(departures from the published one are listed under ``assumed`` in the
configuration file). Per head of 64 channels, state S (64 x 64):

    y_t = r_t (S + diag(u) k_t v_t^T),   S <- diag(w_t) S + k_t v_t^T,
    w_t = exp(-exp(w0 + tanh(x_t A) B)),

then a per-head LayerNorm, a SiLU gate, and the output projection. The
channel mix is sigmoid(x_r Wr) * (relu(x_k Wk)^2 Wv). Token shift mixes
each normed input with the previous one by per-channel coefficients. The
recurrence runs one token at a time, in float32."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import HIGHEST, rms_norm, shift

HEAD = 64
LORA = 64


def layout(m: dict) -> dict:
    L, D, F, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]
    H = D // HEAD
    one, mu = ("const", 1.0), ("uniform", 0.0, 1.0)
    fan = lambda k: ("normal", k ** -0.5)
    return {
        # unit scale: the program has no ln0 after the embedding, and the
        # residual stream starts at the scale ln0 would give it
        "embed": ((V, D), ("normal", 1.0)),
        "layers": {
            "norm_tm": ((L, D), one), "norm_cm": ((L, D), one),
            "mu_r": ((L, D), mu), "mu_k": ((L, D), mu), "mu_v": ((L, D), mu),
            "mu_g": ((L, D), mu), "mu_w": ((L, D), mu),
            "w0": ((L, D), ("decay", -5.0, 8.0, 3.0)),
            "w_lora_a": ((L, D, LORA), fan(D)),
            "w_lora_b": ((L, LORA, D), ("normal", 0.05)),
            "bonus_u": ((L, H, HEAD), ("normal", 0.3)),
            "wr": ((L, D, D), fan(D)), "wk": ((L, D, D), fan(D)),
            "wv": ((L, D, D), fan(D)), "wg": ((L, D, D), fan(D)),
            "wo": ((L, D, D), fan(D)),
            "ln_x": ((L, D), one),
            "mu_ck": ((L, D), mu), "mu_cr": ((L, D), mu),
            # the published init zeroes the channel mix's value projection;
            # at full fan-in the mean of relu(k)^2 adds the same vector to
            # every position in every layer, and by layer 24 the logits no
            # longer depend on the input (one token is served throughout)
            "wck": ((L, D, F), fan(D)), "wcv": ((L, F, D), ("normal", 0.1 * F ** -0.5)),
            "wcr": ((L, D, D), fan(D)),
        },
        "final_norm": ((D,), one),
        "unembed": ((D, V), fan(D)),
    }


def matmuls(m: dict) -> list:
    L, D, F, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]
    return [(5 * L, D, D), (L, D, LORA), (L, LORA, D), (L, D, F), (L, F, D),
            (L, D, D), (1, D, V)]


def attention(m: dict):
    return None


def wkv(r, k, v, w, u):
    """The recurrence over T tokens; r/k/v/w (T, H, 64), u (H, 64)."""
    H = r.shape[1]

    def step(S, inp):
        rt, kt, vt, wt = inp
        kv = kt[:, :, None] * vt[:, None, :]
        y = jnp.einsum("hi,hij->hj", rt, S + u[:, :, None] * kv,
                       precision=HIGHEST)
        return wt[:, :, None] * S + kv, y

    _, y = jax.lax.scan(step, jnp.zeros((H, HEAD, HEAD), jnp.float32),
                        (r, k, v, w))
    return y


def forward(p, tokens, m: dict, mm):
    T, D, eps = tokens.shape[0], m["d_model"], m["norm_eps"]
    H = D // HEAD
    heads = lambda a: a.reshape(T, H, HEAD)
    rnd = mm.round
    x = rnd(p["embed"][tokens].astype(jnp.float32))

    def layer(x, lp):
        h = rms_norm(x, lp["norm_tm"], eps)
        hs = shift(h)
        lerp = lambda mu: h + (hs - h) * mu
        r = mm("td,de->te", lerp(lp["mu_r"]), lp["wr"])
        k = mm("td,de->te", lerp(lp["mu_k"]), lp["wk"])
        v = mm("td,de->te", lerp(lp["mu_v"]), lp["wv"])
        g = mm("td,de->te", lerp(lp["mu_g"]), lp["wg"])
        lora = mm("tr,rd->td", jnp.tanh(mm("td,dr->tr", lerp(lp["mu_w"]),
                                            lp["w_lora_a"])), lp["w_lora_b"])
        w = rnd(jnp.exp(-jnp.exp(lp["w0"] + lora)))
        y = wkv(heads(r), heads(k), heads(v), heads(w), lp["bonus_u"])
        mean = y.mean(-1, keepdims=True)
        var = ((y - mean) ** 2).mean(-1, keepdims=True)
        y = ((y - mean) * jax.lax.rsqrt(var + eps)).reshape(T, D) * lp["ln_x"]
        x = rnd(x + mm("td,de->te", y * jax.nn.silu(g), lp["wo"]))
        h = rms_norm(x, lp["norm_cm"], eps)
        hs = shift(h)
        xk = h + (hs - h) * lp["mu_ck"]
        xr = h + (hs - h) * lp["mu_cr"]
        kk = jnp.square(jax.nn.relu(mm("td,df->tf", xk, lp["wck"])))
        x = rnd(x + jax.nn.sigmoid(mm("td,de->te", xr, lp["wcr"])) * mm(
            "tf,fd->td", kk, lp["wcv"]))
        return x, None

    x, _ = jax.lax.scan(layer, x, p["layers"])
    return mm("td,dv->tv", rms_norm(x, p["final_norm"], eps), p["unembed"])
