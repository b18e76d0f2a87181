"""Per-kernel allclose sweeps (interpret mode) against the ref.py oracles,
and the Phase 3 doubling rounds of ref.py against NumPy."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.segment_reduce import segment_sum_sorted


@pytest.mark.parametrize("N,D,S", [(256, 32, 16), (1024, 64, 37),
                                   (2048, 128, 200), (512, 16, 1)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_segment_sum_sweep(N, D, S, dtype):
    rng = np.random.default_rng(N + S)
    seg = np.sort(rng.integers(0, S, N)).astype(np.int32)
    vals = rng.normal(size=(N, D)).astype(dtype)
    out_k = segment_sum_sorted(jnp.asarray(vals), jnp.asarray(seg), S,
                               interpret=True)
    # ground truth in f32 (the kernel accumulates f32 even for fp16 inputs,
    # which is *more* accurate than a same-dtype jnp segment_sum)
    out_r = ref.segment_sum_sorted_ref(
        jnp.asarray(vals.astype(np.float32)), jnp.asarray(seg), S
    )
    tol = 1e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol * 8)


def test_segment_sum_with_padding_ids():
    rng = np.random.default_rng(0)
    N, D, S = 512, 32, 20
    seg = np.sort(rng.integers(0, S + 5, N)).astype(np.int32)  # ids ≥ S pad
    vals = rng.normal(size=(N, D)).astype(np.float32)
    out_k = segment_sum_sorted(jnp.asarray(vals), jnp.asarray(seg), S,
                               interpret=True)
    out_r = ref.segment_sum_sorted_ref(jnp.asarray(vals), jnp.asarray(seg), S)
    np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-5)


def _np_double(nxt, lab):
    return nxt[nxt], np.minimum(lab, lab[nxt])


def _np_double_rank(ptr, dist, reach):
    return ptr[ptr], dist + dist[ptr], np.maximum(reach, reach[ptr])


@pytest.mark.parametrize("N", [1024, 4096, 8192])
def test_pointer_double_sweep(N):
    """One XLA doubling round (Phase 3's CC gather) matches NumPy."""
    rng = np.random.default_rng(N)
    nxt = rng.integers(0, N, N).astype(np.int32)
    lab = rng.permutation(N).astype(np.int32)
    nk, lk = jax.jit(ref.pointer_double_ref)(jnp.asarray(nxt),
                                              jnp.asarray(lab))
    nr, lr = _np_double(nxt, lab)
    assert (np.asarray(nk) == nr).all()
    assert (np.asarray(lk) == lr).all()


def test_pointer_double_converges_on_cycle():
    """log₂ N doubling rounds label a single cycle uniformly."""
    N = 512
    nxt = jnp.asarray((np.arange(N) + 1) % N, jnp.int32)
    lab = jnp.asarray(np.arange(N), jnp.int32)
    for _ in range(int(np.ceil(np.log2(N))) + 1):
        nxt, lab = ref.pointer_double_ref(nxt, lab)
    assert int(jnp.max(lab)) == 0


@pytest.mark.parametrize("N", [1024, 4096, 8192])
def test_pointer_double_rank_sweep(N):
    """One XLA list-ranking round matches NumPy."""
    rng = np.random.default_rng(N + 1)
    ptr = rng.integers(0, N, N).astype(np.int32)
    t = int(ptr[0])
    ptr[t] = t                                    # halt node self-loops
    dist = np.ones(N, np.int32)
    dist[t] = 0
    reach = np.zeros(N, np.int32)
    reach[t] = 1
    pk, dk, rk = jax.jit(ref.pointer_double_rank_ref)(
        jnp.asarray(ptr), jnp.asarray(dist), jnp.asarray(reach))
    pr, dr, rr = _np_double_rank(ptr, dist, reach)
    assert (np.asarray(pk) == pr).all()
    assert (np.asarray(dk) == dr).all()
    assert (np.asarray(rk) == rr).all()


def test_pointer_double_rank_ranks_a_list():
    """Doubling rounds of the rank gather compute list ranks on a chain."""
    N = 256
    ptr = np.minimum(np.arange(N) + 1, N - 1).astype(np.int32)  # i → i+1
    dist = np.ones(N, np.int32)
    dist[N - 1] = 0                                # halt at the tail
    reach = np.zeros(N, np.int32)
    reach[N - 1] = 1
    p, d, r = jnp.asarray(ptr), jnp.asarray(dist), jnp.asarray(reach)
    for _ in range(int(np.ceil(np.log2(N))) + 1):
        p, d, r = ref.pointer_double_rank_ref(p, d, r)
    assert (np.asarray(r) == 1).all()
    # dist[i] = hops from i to the tail
    assert (np.asarray(d) == (N - 1 - np.arange(N))).all()


@pytest.mark.parametrize("B,S,H,D,T", [(1, 128, 1, 64, 128),
                                       (2, 256, 3, 64, 256),
                                       (1, 256, 2, 128, 512)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, D, T, causal, dtype):
    rng = np.random.default_rng(S + H)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, T, H, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, T, H, D)), dtype)
    o_k = flash_attention(q, k, v, causal=causal, interpret=True)
    o_r = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == np.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32),
                               rtol=tol, atol=tol)


def test_flash_vs_chunked_model_path():
    """The model's jnp row-blocked attention and the Pallas kernel agree."""
    from repro.models.layers import chunked_gqa_attention

    rng = np.random.default_rng(7)
    B, S, Hq, Hkv, D = 2, 256, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    o_model = chunked_gqa_attention(q, k, v, q_block=128)
    kr = jnp.repeat(k, Hq // Hkv, axis=2)
    vr = jnp.repeat(v, Hq // Hkv, axis=2)
    o_kernel = flash_attention(q, kr, vr, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o_model), np.asarray(o_kernel),
                               rtol=2e-5, atol=2e-5)
