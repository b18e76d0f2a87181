"""Sorts and prefix scans whose TPU compile time does not grow with the
array length.

The TPU compiler specializes both to their size, and each costs many
seconds of codegen once the array is large; a fused Euler program holds
about twenty of them (measured for a described v5e):

  * a native ``sort`` of more than a few thousand elements: 15-90 s each
    at 32K-8M elements;
  * ``lax.associative_scan``, which unrolls into 2·log2(n) slice/pad
    levels: slower still.

The functions here give the same results with a bounded compile:

  * :func:`lexsort` / :func:`argsort` / :func:`sort` — a bitonic sorting
    network, one ``fori_loop`` over length-independent stages, over the
    keys plus the element index as the last tiebreak key.  Every key
    tuple is then distinct, so the network's (unstable) result is exactly
    the stable order: byte-identical to ``jnp.lexsort`` /
    ``jnp.argsort(..., stable=True)``;
  * :func:`cummax` / :func:`cumsum` — Hillis-Steele doubling scans, one
    ``fori_loop`` each, exact for ``max`` and integer ``+``.

Sorts and scans of at most :data:`NATIVE_MAX` elements keep the native
ops, whose compile is quick at that size and whose run is shorter.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: Longest input that keeps the native sort/scan (compile < 1 s on a v5e).
NATIVE_MAX = 2048

_I32 = jnp.int32


def _shift(x: jnp.ndarray, d, fill) -> jnp.ndarray:
    """``y[i] = x[i - d]`` for ``0 ≤ d ≤ n`` (traced ``d`` allowed); the
    first ``d`` slots take ``fill``.  Negative ``d`` shifts the other way."""
    n = x.shape[0]
    pad = jnp.full((n,), fill, x.dtype)
    both = jnp.concatenate([pad, x, pad])
    return jax.lax.dynamic_slice(both, (n - d,), (n,))


def _doubling_scan(op, x: jnp.ndarray, identity) -> jnp.ndarray:
    n = x.shape[0]
    steps = int(np.ceil(np.log2(max(2, n))))

    def body(s, y):
        return op(y, _shift(y, jnp.left_shift(1, s), identity))

    return jax.lax.fori_loop(0, steps, body, x)


def cummax(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running maximum of a 1-D integer array."""
    if x.shape[0] <= NATIVE_MAX:
        return jax.lax.cummax(x)
    return _doubling_scan(jnp.maximum, x, jnp.iinfo(x.dtype).min)


def cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running sum of a 1-D integer array (wrapping int add, so
    exact and order-independent like ``jnp.cumsum``)."""
    if x.shape[0] <= NATIVE_MAX:
        return jnp.cumsum(x)
    return _doubling_scan(jnp.add, x, 0)


def _as_key(k: jnp.ndarray) -> jnp.ndarray:
    return k.astype(_I32) if k.dtype == jnp.bool_ else k


def lexsort(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Indices that sort by ``keys``, the LAST key primary — the argument
    order and the stable tie order of ``jnp.lexsort``."""
    keys = [_as_key(k) for k in keys]
    n = keys[0].shape[0]
    if n <= NATIVE_MAX:
        return jnp.lexsort(keys).astype(_I32)
    size = 1 << int(np.ceil(np.log2(n)))
    idx = jnp.arange(size, dtype=_I32)
    # primary key first; the index last: all tuples distinct.  Padding
    # rows take each key's maximum and an index past every real row, so
    # they sort after all real rows.
    ops = [jnp.concatenate([k, jnp.full((size - n,), jnp.iinfo(k.dtype).max,
                                        k.dtype)])
           for k in reversed(keys)] + [idx]

    # bitonic network: for block k = 2, 4, ..., size and partner distance
    # j = k/2, ..., 1 — one stage per (k, j), tabulated for the loop
    ks, js = [], []
    k = 2
    while k <= size:
        j = k // 2
        while j >= 1:
            ks.append(k)
            js.append(j)
            j //= 2
        k *= 2
    ks = jnp.asarray(ks, _I32)
    js = jnp.asarray(js, _I32)

    def stage(s, ops):
        k, j = ks[s], js[s]
        lo = (idx & j) == 0                 # lower slot of its pair
        up = (idx & k) == 0                 # block sorts ascending
        # partner of slot i is i ^ j: i + j for lower slots, else i - j
        part = [jnp.where(lo, _shift(v, -j, 0), _shift(v, j, 0))
                for v in ops]
        less = jnp.zeros((size,), bool)
        eq = jnp.ones((size,), bool)
        for v, p in zip(ops, part):
            less = less | (eq & (v < p))
            eq = eq & (v == p)
        keep = jnp.where(lo == up, less, ~less)
        return [jnp.where(keep, v, p) for v, p in zip(ops, part)]

    ops = jax.lax.fori_loop(0, len(js), stage, ops)
    return ops[-1][:n]


def argsort(x: jnp.ndarray) -> jnp.ndarray:
    """Stable ascending argsort of a 1-D array (``jnp.argsort(x,
    stable=True)``)."""
    return lexsort([x])


def sort(x: jnp.ndarray) -> jnp.ndarray:
    """Ascending sort of a 1-D array."""
    if x.shape[0] <= NATIVE_MAX:
        return jnp.sort(x)
    return x[argsort(x)]

