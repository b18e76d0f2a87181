"""repro.core.bounded against the native ops it stands in for: the
bitonic sorts and doubling scans above ``NATIVE_MAX`` must give
byte-identical results."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bounded

BIG = np.iinfo(np.int32).max


def _keys(n, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 40, n).astype(np.int32)
    a[::7] = BIG                                  # ties with the pad key
    b = rng.integers(0, 3, n).astype(np.int32)
    return a, b


@pytest.mark.parametrize("n", [1, 7, 2048, 2049, 5000, 70000])
def test_sorts_match_numpy_stable_order(n):
    a, b = _keys(n, n)
    got = jax.jit(bounded.lexsort)([b, a])
    assert (np.asarray(got) == np.lexsort([b, a])).all()
    mask = a % 3 == 0
    assert (np.asarray(jax.jit(bounded.argsort)(jnp.asarray(~mask)))
            == np.argsort(~mask, kind="stable")).all()
    assert (np.asarray(jax.jit(bounded.sort)(a)) == np.sort(a)).all()


@pytest.mark.parametrize("n", [1, 2048, 2049, 4097, 70000])
def test_scans_match_numpy(n):
    a, b = _keys(n, n + 1)
    assert (np.asarray(jax.jit(bounded.cummax)(a))
            == np.maximum.accumulate(a)).all()
    assert (np.asarray(jax.jit(bounded.cumsum)(b)) == np.cumsum(b)).all()

