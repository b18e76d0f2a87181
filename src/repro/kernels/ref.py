"""Pure-jnp oracles for the Pallas kernels (the allclose targets), and the
pointer-doubling rounds of the Euler engine's Phase 3.

The doubling rounds are the only implementation on every backend: each is
a whole-table XLA gather.  Mosaic lowers only same-shape 2-D gathers inside
one vreg, so a lookup into an N-entry jump table has no Pallas TPU form.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def segment_sum_sorted_ref(values: jnp.ndarray, seg_ids: jnp.ndarray,
                           num_segments: int) -> jnp.ndarray:
    """values [N, D], seg_ids [N] sorted ascending (padding = num_segments)."""
    return jax.ops.segment_sum(values, seg_ids, num_segments=num_segments + 1,
                               indices_are_sorted=True)[:num_segments]


def pointer_double_ref(nxt: jnp.ndarray, lab: jnp.ndarray):
    """One pointer-doubling round: lab' = min(lab, lab[nxt]); nxt' = nxt[nxt]."""
    return nxt[nxt], jnp.minimum(lab, lab[nxt])


def pointer_double_rank_ref(ptr: jnp.ndarray, dist: jnp.ndarray,
                            reach: jnp.ndarray):
    """One list-ranking round: dist' = dist + dist[ptr];
    reach' = reach | reach[ptr]; ptr' = ptr[ptr]."""
    return ptr[ptr], dist + dist[ptr], jnp.maximum(reach, reach[ptr])


def _shard_own(q, base, s_real):
    idx = q - base
    own = (idx >= 0) & (idx < s_real)
    return own, jnp.where(own, idx, 0)


def pointer_double_shard_ref(q, a_nxt, a_lab, base, tbl_nxt, tbl_lab,
                             s_real: int):
    """One ring step of the sharded CC gather: queries owned by the
    visiting table slice (base ≤ q < base+s_real) take its values,
    others keep their current answers."""
    own, idx = _shard_own(q, base[0], s_real)
    return (jnp.where(own, tbl_nxt[idx], a_nxt),
            jnp.where(own, tbl_lab[idx], a_lab))


def pointer_double_rank_shard_ref(q, a_ptr, a_dist, a_reach, base,
                                  tbl_ptr, tbl_dist, tbl_reach,
                                  s_real: int):
    """One ring step of the sharded list-ranking gather (3-table twin of
    :func:`pointer_double_shard_ref`)."""
    own, idx = _shard_own(q, base[0], s_real)
    return (jnp.where(own, tbl_ptr[idx], a_ptr),
            jnp.where(own, tbl_dist[idx], a_dist),
            jnp.where(own, tbl_reach[idx], a_reach))


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True) -> jnp.ndarray:
    """q [B,S,H,D], k/v [B,T,H,D] (same head count — GQA is handled by the
    wrapper repeating kv heads)."""
    B, S, H, D = q.shape
    T = k.shape[1]
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32)
    scores = scores / np.sqrt(D)
    if causal:
        mask = jnp.arange(T)[None, :] <= jnp.arange(S)[:, None] + (T - S)
        scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)
