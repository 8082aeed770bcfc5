"""Random weights from the seed, made on the device in one jitted call, in
the form they are served in: for every tensor, block-absmax codes and
scales of the configuration's weight format.

Each leaf is drawn by its rule in the reference's ``layout`` and rounded
onto the grid: blocks of ``block`` consecutive values (row-major), each
with one scale, its absmax rounded up to a bfloat16, and each value coded
as the nearest codepoint. The weights are then exactly
``codepoints[code] * scale`` (:func:`dense`), which is what the program
serves and what the reference reads. The grid and the rounding rule are
this module's own copy; nothing is taken from the program."""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Coded(NamedTuple):
    """One tensor as served: codes uint8 (n_blocks, block) and scales bf16
    (n_blocks, 1), blocks running row-major over the tensor."""
    codes: jax.Array
    scales: jax.Array


def is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def ceil_bf16(x):
    """Round positive float32 values up to the next bfloat16."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    up = (u & jnp.uint32(0xFFFF0000)) + jnp.uint32(0x10000)
    return jax.lax.bitcast_convert_type(
        jnp.where((u & jnp.uint32(0xFFFF)) != 0, up, u), jnp.float32)


def to_codes(x, codepoints, block: int):
    """(codes uint8 (n_blocks, block), scales bf16 (n_blocks, 1))."""
    xb = x.reshape(-1, block)
    scale = ceil_bf16(jnp.max(jnp.abs(xb), axis=-1, keepdims=True).astype(
        jnp.float32))
    safe = jnp.where(scale == 0, 1.0, scale)
    v = xb / safe
    # the nearest codepoint: how many midpoints lie strictly below v
    codes = jnp.zeros(v.shape, jnp.uint8)
    for lo, hi in zip(codepoints[:-1], codepoints[1:]):
        codes += (v > (lo + hi) / 2).astype(jnp.uint8)
    return codes, scale.astype(jnp.bfloat16)


def dense(codes, scales, codepoints, shape):
    """The float32 values of one coded tensor."""
    cb = jnp.asarray(codepoints, jnp.float32)
    vals = cb[codes.astype(jnp.int32)] * scales.astype(jnp.float32)
    return vals.reshape(shape)


def draw(key, shape, rule):
    kind = rule[0]
    if kind == "const":
        return jnp.full(shape, rule[1], jnp.float32)
    # drawn in bfloat16: only the codes and scales they round to are kept
    if kind == "normal":
        return rule[1] * jax.random.truncated_normal(
            key, -3.0, 3.0, shape, jnp.bfloat16)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.bfloat16, rule[1], rule[2])
    if kind == "decay":      # per-channel decay bias lo + span * (i/(D-1))^p
        lo, span, power = rule[1:]
        i = jnp.arange(shape[-1], dtype=jnp.float32) / max(shape[-1] - 1, 1)
        return jnp.broadcast_to(lo + span * i ** power, shape)
    raise ValueError(f"unknown init rule {rule!r}")


def shapes(layout):
    return jax.tree.map(lambda leaf: leaf[0], layout, is_leaf=is_leaf)


def _coded(layout, weights: dict, seed: int):
    flat, tree = jax.tree_util.tree_flatten(layout, is_leaf=is_leaf)
    spec = tuple((leaf[0], tuple(leaf[1])) for leaf in flat)
    key = jax.random.key(seed % (2 ** 63))
    out = _make(key, spec, tuple(weights["codepoints"]), weights["block"])
    return [Coded(*x) for x in out], [leaf[0] for leaf in flat], tree


def make(layout, weights: dict, seed: int):
    """The tree of ``layout`` for ``seed``, each leaf :class:`Coded`."""
    coded, _, tree = _coded(layout, weights, seed)
    return jax.tree_util.tree_unflatten(tree, coded)


def make_dense(layout, weights: dict, seed: int):
    """The float32 weight tree of ``layout`` for ``seed``, one tensor
    decoded at a time."""
    coded, shape, tree = _coded(layout, weights, seed)
    cb = tuple(weights["codepoints"])
    decode = jax.jit(dense, static_argnums=(2, 3))
    out = []
    for i, s in enumerate(shape):
        out.append(decode(coded[i].codes, coded[i].scales, cb, s))
        coded[i] = None
    return jax.tree_util.tree_unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, spec, codepoints, block):
    return [to_codes(draw(jax.random.fold_in(key, i), shape, rule),
                     codepoints, block)
            for i, (shape, rule) in enumerate(spec)]
