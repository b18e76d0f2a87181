"""Pallas TPU kernel: causal flash attention (online softmax, MXU tiles).

Standard FlashAttention-2 schedule adapted to TPU: grid over
(batch·head, q_block); the KV sequence streams through VMEM in k_block
tiles via a fori_loop of dynamic slices; running (max, sum, acc) carried
in VREGs/VMEM scratch.  Block sizes are multiples of 128 to keep the MXU
systolic array full.  Used by the LM archs' train/prefill path on TPU;
the jnp row-blocked attention in models/layers.py is the lowering used on
CPU (and the correctness oracle lives in kernels/ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool, q_block: int,
            k_block: int, kv_len: int, scale: float, q_offset: int):
    qi = pl.program_id(1)
    q = q_ref[0]                                 # [q_block, D]
    D = q.shape[-1]
    acc = jnp.zeros((q_block, D), jnp.float32)
    m = jnp.full((q_block,), -jnp.inf, jnp.float32)
    l = jnp.zeros((q_block,), jnp.float32)
    q_pos = q_offset + qi * q_block + jnp.arange(q_block)

    n_kv = kv_len // k_block

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * k_block, k_block), :]
        v = v_ref[0, pl.ds(j * k_block, k_block), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                # [q_block, k_block]
        if causal:
            k_pos = j * k_block + jnp.arange(k_block)
            mask = k_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l_new

    if causal:
        # only KV blocks at or before this Q block's last position contribute
        last = q_offset + (qi + 1) * q_block - 1
        n_iter = jnp.minimum(n_kv, last // k_block + 1)
    else:
        n_iter = n_kv
    acc, m, l = jax.lax.fori_loop(0, n_iter, body, (acc, m, l))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, q_block: int = 128,
                    k_block: int = 128, interpret: bool = True):
    """q [B,S,H,D], k/v [B,T,H,D] (equal head counts; GQA repeat happens in
    ops.py).  Causal with S < T treats queries as the suffix (decode-style
    offset T-S).  Returns [B,S,H,D]."""
    B, S, H, D = q.shape
    T = k.shape[1]
    assert S % q_block == 0 and T % k_block == 0, (S, T)
    q_offset = T - S
    scale = 1.0 / np.sqrt(D)
    # fold batch and head into the grid's first axis
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    grid = (B * H, S // q_block)
    kernel = functools.partial(
        _kernel, causal=causal, q_block=q_block, k_block=k_block,
        kv_len=T, scale=scale, q_offset=q_offset,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
