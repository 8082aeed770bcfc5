"""Plain float32 references, one module per model family, written from the
published description and importing nothing of the program.

Each module gives

* ``layout(model)``: the parameter tree the program takes (key names and
  shapes), each leaf with the rule its random values are drawn by;
* ``forward(params, tokens, model, mm)``: the logits (T, V) of one
  sequence, every position at once, with every matrix product through
  ``mm``;
* ``matmuls(model)``: the weight products of one token, as
  ``(calls per step, K, N)``, for the operation and byte counts.

``mm`` is :func:`exact` for the reference (float32 at the highest matmul
precision) and :func:`fp8` for the control: float8 e4m3, with one scale
per tensor, wherever the program holds bfloat16 (the step below it): both
operands and the result of every product, and, through ``mm.round``, the
residual stream and (RWKV) the decay."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def exact(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


exact.round = lambda x: x


def _to_fp8(x):
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def fp8(spec, a, b):
    return _to_fp8(jnp.einsum(spec, _to_fp8(a), _to_fp8(b),
                              precision=HIGHEST))


fp8.round = _to_fp8


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def shift(x):
    """x_{t-1} along the first axis, zeros at t = 0."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)
