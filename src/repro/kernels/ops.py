"""Jit'd public wrappers around the Pallas kernels.

Each op routes between the Pallas kernel (TPU, or interpret mode for
CPU validation) and the pure-jnp oracle, based on problem size and
backend.  Models call these; tests sweep them against ``ref.py``.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention as _flash
from .segment_reduce import segment_sum_sorted as _segsum

_ON_TPU = None


def on_tpu() -> bool:
    global _ON_TPU
    if _ON_TPU is None:
        _ON_TPU = jax.default_backend() == "tpu"
    return _ON_TPU


@partial(jax.jit, static_argnames=("num_segments", "use_kernel", "interpret"))
def segment_sum_sorted(values, seg_ids, num_segments: int,
                       use_kernel: Optional[bool] = None,
                       interpret: bool = True):
    """Sorted-segment sum.  Kernel path for segment windows that fit VMEM
    (≤ 4096 segments); jnp oracle otherwise."""
    if use_kernel is None:
        use_kernel = on_tpu() and num_segments <= 4096
    if use_kernel:
        return _segsum(values, seg_ids, num_segments, interpret=interpret)
    return ref.segment_sum_sorted_ref(values, seg_ids, num_segments)


@partial(jax.jit, static_argnames=("causal", "use_kernel"))
def flash_attention_gqa(q, k, v, causal: bool = True,
                        use_kernel: Optional[bool] = None):
    """GQA flash attention: q [B,S,Hq,D], k/v [B,T,Hkv,D]."""
    Hq, Hkv = q.shape[2], k.shape[2]
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        return _flash(q, k, v, causal=causal, interpret=not on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=causal)
