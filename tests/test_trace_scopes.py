"""Named scopes on the fused program's phases (DESIGN.md §13).

The fused program's phases carry ``jax.named_scope``s — ``phase1``,
``merge_exchange`` and ``phase3`` in ``make_fused``; ``cc``, ``splice``,
``rank`` and ``emit`` inside Phase 3 — which reach the HLO metadata
(``op_name``) and so a profile.  Lowered at scale 8 for P=1 and for P=4
(four CPU devices, sharded Phase 3), every gather, scatter, sort,
``while`` and collective of the compiled program, and every fusion that
carries the name of one of the program's ops, lies under one of them.  XLA's own rewrites cannot: a fusion it makes with no
``op_name``, with the bare name of a reducer or comparator body
(``reduce_window_max``, ``or``), or with an instruction name the
partitioner gives (``.../shard_map/broadcast.145``).
"""
from conftest import run_with_devices

CODE = r'''
import re
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.euler import EulerSolver
from repro.graphgen.eulerize import eulerian_rmat

SCOPES = {"phase1", "merge_exchange", "phase3", "cc", "splice", "rank",
          "emit"}
KINDS = ("gather", "scatter", "sort", "while", "all-to-all", "all-gather",
         "all-reduce", "collective-permute", "reduce-scatter")
OP = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\(")


def census(text, kinds):
    """(kind, op_name) of every instruction of ``kinds`` in HLO text."""
    out = []
    for line in text.splitlines():
        m = OP.match(line)
        if m and any(m.group(1) == k or m.group(1).startswith(k + "-")
                     for k in kinds):
            nm = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), nm.group(1) if nm else ""))
    return out


def scoped(path):
    return bool(set(path.split("/")) & SCOPES)


def program_op(path):
    return path.startswith("jit(") and not re.search(r"\.\d+$", path)


g = eulerian_rmat(8, avg_degree=5, seed=0)
for n in (1, 4):
    solver = EulerSolver(n_parts=n)
    pg, _, key = solver._prepare(g, None)
    eng = solver._engine_for(key)
    ent = eng._load_cached(pg)
    sv = eng._pad_sv(ent["sv"]).astype(np.int32)

    def sds(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(solver.mesh, spec))

    args = (sds(ent["anc"], P(None, None)),
            jax.tree.map(sds, ent["state"], eng._state_specs()),
            sds(sv, P("part") if eng.sharded_phase3 else P(None)))
    hlo = eng.fused_program(key[0]).lower(*args).compile().as_text()
    ops = census(hlo, KINDS)
    unscoped = [e for e in ops if not scoped(e[1])]
    assert ops and not unscoped, (n, unscoped[:5])
    fusions = census(hlo, ("fusion",))
    unscoped = [e for e in fusions if program_op(e[1]) and not scoped(e[1])]
    assert fusions and not unscoped, (n, unscoped[:5])
    seen = {c for _, p in ops + fusions for c in p.split("/")} & SCOPES
    want = SCOPES - ({"merge_exchange"} if n == 1 else set())
    assert seen >= want, (n, sorted(want - seen))
    print(n, len(ops), len(fusions), sorted({k for k, _ in ops}))
'''


def test_fused_program_ops_lie_under_named_scopes():
    out = run_with_devices(CODE, n=4)
    lines = out.strip().splitlines()
    assert [ln.split()[0] for ln in lines] == ["1", "4"], out
    assert "all-to-all" in lines[1] and "collective-permute" in lines[1]
