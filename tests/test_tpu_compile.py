"""Compile the fused Euler programs for a described TPU v5e (no chip needed).

The TPU compiler ships with libtpu and compiles for a topology that is
described, not attached.  Each case lowers ``eng.fused_program(E)`` of a
real bucket onto the described devices, compiles it, and checks what the
chip would refuse or pay for: device memory within one v5e's 16 GB, no
Pallas custom call on the Euler path, the expected collectives, and a
compile time that grows slowly from scale 8 to scale 14 (no return of
the size-specialized sort codegen that ``repro.core.bounded`` avoids).

The topology is described inside a module fixture, never at import: one
process at a time may hold libtpu.
"""
import time

import numpy as np
import pytest

import jax

V5E_HBM_BYTES = 16 * 10**9

#: Bound on compile(scale 14) / compile(scale 8) of the P=1 program for a
#: described v5e, measured back to back so machine load cancels.  With
#: native ``sort`` ops the ratio was 132.7 s / 14.3 s = 9.3 (one
#: size-specialized sort codegen per call site); with the
#: length-independent sorts and scans of ``repro.core.bounded`` it is
#: 38.6 s / 11.3 s = 3.4 (8-core host).
COMPILE_GROWTH_BOUND = 7.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(topo, scale, n_parts, sharded=None, batch=None):
    """Lower and compile the fused program of ``eulerian_rmat(scale)``'s
    bucket for ``n_parts`` described v5e chips.  Returns (compiled, HLO
    text, seconds spent in compile)."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.euler import EulerSolver
    from repro.graphgen.eulerize import eulerian_rmat

    mesh = Mesh(np.array(topo.devices[:n_parts]), ("part",))
    solver = EulerSolver(n_parts=n_parts, mesh=mesh, sharded_phase3=sharded)
    g = eulerian_rmat(scale, avg_degree=5, seed=0)
    pg, _, key = solver._prepare(g, None)
    eng = solver._engine_for(key)
    ent = eng._load_cached(pg)
    sv = eng._pad_sv(ent["sv"]).astype(np.int32)
    sv_spec = P("part") if eng.sharded_phase3 else P(None)

    def sds(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    if batch is None:
        state = jax.tree.map(sds, ent["state"], eng._state_specs())
        args = (sds(ent["anc"], P(None, None)), state, sds(sv, sv_spec))
    else:
        def stack(x):                           # [n, ·] → [n, B, ·]
            return np.stack([x] * batch, axis=1)

        state = jax.tree.map(lambda x: sds(stack(x), P("part", None, None)),
                             ent["state"])
        args = (sds(np.stack([ent["anc"]] * batch), P(None, None, None)),
                state, sds(np.stack([sv] * batch), P(None, *sv_spec)))
    lowered = eng.fused_program(key[0], batch=batch).lower(*args)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    return compiled, compiled.as_text(), seconds


def _check_fits_and_no_kernel(compiled, hlo):
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < total <= V5E_HBM_BYTES, total
    assert "tpu_custom_call" not in hlo      # no Pallas on the Euler path


@pytest.mark.parametrize("scale", [6, 8])
def test_p1_bucket_compiles_for_v5e(topo, scale):
    compiled, hlo, _ = _compile(topo, scale, 1)
    _check_fits_and_no_kernel(compiled, hlo)


def test_p1_batched_b4_compiles_for_v5e(topo):
    compiled, hlo, _ = _compile(topo, 7, 1, batch=4)
    _check_fits_and_no_kernel(compiled, hlo)


@pytest.mark.parametrize("sharded", [True, False],
                         ids=["sharded_phase3", "replicated_phase3"])
def test_p4_compiles_for_v5e_mesh(topo, sharded):
    compiled, hlo, _ = _compile(topo, 9, 4, sharded=sharded)
    _check_fits_and_no_kernel(compiled, hlo)
    assert "all-to-all" in hlo               # the level-scan shipping
    # the sharded Phase 3 rotates table shards around the ring; the
    # replicated one gathers the mate array once and rotates nothing
    assert ("collective-permute" in hlo) is sharded


def test_p1_scale14_compile_time_is_bounded(topo):
    _, _, t8 = _compile(topo, 8, 1)
    compiled, hlo, t14 = _compile(topo, 14, 1)
    _check_fits_and_no_kernel(compiled, hlo)
    assert t14 < COMPILE_GROWTH_BOUND * t8, (t8, t14)
