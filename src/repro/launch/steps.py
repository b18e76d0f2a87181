"""Resolve (arch × shape × mesh) → step function, abstract inputs, shardings.

The single entry point is :func:`build_cell`; it powers the dry-run
(lower + compile on the production mesh), the roofline harness, the smoke
tests (reduced configs, real arrays, 1 device) and the train/serve
launchers — one code path for all of them.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig, ShapeCell
from ..models import gnn as gnn_mod
from ..models import recsys as rec_mod
from ..models.equivariant import AtomsBatch, NequIPConfig, init_nequip_params, \
    nequip_energy_loss, nequip_force_loss
from ..models.gnn import GNNConfig, GraphBatch, gnn_loss
from ..models.recsys import AutoIntConfig, RecsysBatch, autoint_loss, \
    init_autoint_params, retrieval_score, autoint_forward
from ..models.transformer import (KVCache, LMConfig, abstract_kv_cache,
                                  abstract_lm_params, decode_step,
                                  init_kv_cache, init_lm_params, lm_loss,
                                  prefill_step)
from ..optim.adamw import AdamWState, abstract_adamw, adamw_update, init_adamw
from ..optim.schedule import warmup_cosine
from ..parallel import sharding as shd


class Cell(NamedTuple):
    """Everything needed to lower/run one (arch × shape × mesh) cell."""

    fn: Callable                 # step function (donated state first)
    abstract_inputs: Tuple       # ShapeDtypeStruct pytree matching fn args
    in_shardings: Any
    out_shardings: Any
    model_flops: float           # analytic useful FLOPs for §Roofline
    note: str = ""
    donate: Tuple[int, ...] = ()  # donated arg indices (train: params+opt)


def _named(mesh, tree):
    if mesh is None:
        return None
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _dp(mesh) -> Any:
    return shd.dp_axes_of(mesh) if mesh is not None else None


def _tp(mesh) -> Optional[str]:
    return "model" if mesh is not None and "model" in mesh.axis_names else None


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def _lm_kv_specs(cfg: LMConfig, mesh) -> KVCache:
    """Shard KV heads over tp when divisible, else the sequence dim."""
    dp = shd.dp_axes_of(mesh)
    tp_size = mesh.shape["model"]
    if cfg.n_kv_heads % tp_size == 0:
        kspec = P(None, dp, None, "model", None)
    else:
        kspec = P(None, dp, "model", None, None)
    return KVCache(k=kspec, v=kspec, length=P(dp))


def _lm_train_flops(cfg: LMConfig, cell: ShapeCell) -> float:
    return 6.0 * cfg.active_param_count() * cell.batch * cell.seq_len


def build_lm_cell(arch: ArchConfig, cell: ShapeCell, mesh) -> Cell:
    cfg: LMConfig = arch.model
    dp, tp = _dp(mesh), _tp(mesh)
    params_abs = abstract_lm_params(cfg)
    pspecs = shd.lm_param_specs(params_abs, mesh) if mesh else None

    if cell.kind == "train":
        opt_abs = abstract_adamw(params_abs)
        batch_abs = {
            "tokens": jax.ShapeDtypeStruct((cell.batch, cell.seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((cell.batch, cell.seq_len), jnp.int32),
        }

        def step(params, opt, batch):
            lr = warmup_cosine(opt.step, 3e-4, 2000, 100_000)
            loss, grads = jax.value_and_grad(lm_loss)(
                params, cfg, batch["tokens"], batch["labels"], dp, tp,
                mesh
            )
            params, opt = adamw_update(params, grads, opt, lr)
            return params, opt, loss

        if mesh is None:
            return Cell(step, (params_abs, opt_abs, batch_abs), None, None,
                        _lm_train_flops(cfg, cell), donate=(0, 1))
        ospecs = AdamWState(step=P(), m=pspecs, v=pspecs)
        bspecs = {"tokens": P(dp, None), "labels": P(dp, None)}
        return Cell(
            step, (params_abs, opt_abs, batch_abs),
            _named(mesh, (pspecs, ospecs, bspecs)),
            _named(mesh, (pspecs, ospecs, P())),
            _lm_train_flops(cfg, cell), donate=(0, 1),
        )

    if cell.kind == "prefill":
        tokens_abs = jax.ShapeDtypeStruct((cell.batch, cell.seq_len), jnp.int32)

        def step(params, tokens):
            return prefill_step(params, cfg, tokens, dp, tp)

        flops = 2.0 * cfg.active_param_count() * cell.batch * cell.seq_len
        if mesh is None:
            return Cell(step, (params_abs, tokens_abs), None, None, flops)
        kv = _lm_kv_specs(cfg, mesh)
        return Cell(
            step, (params_abs, tokens_abs),
            _named(mesh, (pspecs, P(dp, None))),
            _named(mesh, (P(dp, None), kv)),
            flops,
        )

    if cell.kind == "decode":
        cache_abs = abstract_kv_cache(cfg, cell.batch, cell.seq_len)
        tokens_abs = jax.ShapeDtypeStruct((cell.batch,), jnp.int32)

        def step(params, cache, tokens):
            return decode_step(params, cfg, cache, tokens, dp, tp)

        flops = 2.0 * cfg.active_param_count() * cell.batch
        if mesh is None:
            return Cell(step, (params_abs, cache_abs, tokens_abs), None, None,
                        flops)
        kv = _lm_kv_specs(cfg, mesh)
        return Cell(
            step, (params_abs, cache_abs, tokens_abs),
            _named(mesh, (pspecs, kv, P(dp))),
            _named(mesh, (P(dp, None), kv)),
            flops, donate=(1,),
        )

    raise ValueError(cell.kind)


# ---------------------------------------------------------------------------
# GNN cells (gcn / gat / pna)
# ---------------------------------------------------------------------------

def _pad512(x: int) -> int:
    """Round up to a shardable size (512 = lcm of every mesh-axis layout)."""
    return (x + 511) // 512 * 512


def _graph_abstract(cell: ShapeCell, d_in: int) -> GraphBatch:
    if cell.name == "minibatch_lg":
        acc, tot = 1, 1
        for f in cell.fanout:
            acc *= f
            tot += acc
        n = cell.batch_nodes * tot
        e = n  # one in-edge per sampled node
    elif cell.name == "molecule":
        n = cell.n_nodes * cell.batch
        e = cell.n_edges * cell.batch
    else:
        n = cell.n_nodes
        e = cell.n_edges
    n, e = _pad512(n), _pad512(e)
    return GraphBatch(
        node_feat=jax.ShapeDtypeStruct((n, d_in), jnp.float32),
        edge_src=jax.ShapeDtypeStruct((e,), jnp.int32),
        edge_dst=jax.ShapeDtypeStruct((e,), jnp.int32),
        edge_mask=jax.ShapeDtypeStruct((e,), jnp.bool_),
        node_mask=jax.ShapeDtypeStruct((n,), jnp.bool_),
        labels=jax.ShapeDtypeStruct((n,), jnp.int32),
    )


def _gnn_flops(cfg: GNNConfig, n: int, e: int) -> float:
    # per layer: edge messages (≈2 dirs) + node transform
    d = cfg.d_hidden
    per_edge = 2 * 2 * d * len(cfg.aggregators)
    per_node = 2 * cfg.d_in * d + 2 * d * d * (cfg.n_layers - 1)
    return float(cfg.n_layers * e * per_edge + n * per_node) * 3  # fwd+bwd

def build_gnn_cell(arch: ArchConfig, cell: ShapeCell, mesh) -> Cell:
    cfg: GNNConfig = arch.model
    if cell.d_feat and cfg.d_in != cell.d_feat:
        cfg = dataclasses.replace(cfg, d_in=cell.d_feat,
                                  n_classes=max(cell.n_classes, 2))
    g_abs = _graph_abstract(cell, cfg.d_in)
    params_abs = jax.eval_shape(
        partial(gnn_mod.INITS[cfg.kind], cfg=cfg), jax.random.PRNGKey(0)
    )
    opt_abs = abstract_adamw(params_abs)

    def step(params, opt, g):
        lr = warmup_cosine(opt.step, 1e-3, 100, 10_000)
        loss, grads = jax.value_and_grad(gnn_loss)(params, cfg, g)
        params, opt = adamw_update(params, grads, opt, lr)
        return params, opt, loss

    n, e = g_abs.node_feat.shape[0], g_abs.edge_src.shape[0]
    flops = _gnn_flops(cfg, n, e)
    if mesh is None:
        return Cell(step, (params_abs, opt_abs, g_abs), None, None, flops,
                    donate=(0, 1))
    dp = _dp(mesh)
    pspecs = shd.gnn_param_specs(params_abs, mesh)
    gspecs = shd.gnn_batch_spec(mesh)
    ospecs = AdamWState(step=P(), m=pspecs, v=pspecs)
    return Cell(
        step, (params_abs, opt_abs, g_abs),
        _named(mesh, (pspecs, ospecs, gspecs)),
        _named(mesh, (pspecs, ospecs, P())),
        flops, donate=(0, 1),
    )


# ---------------------------------------------------------------------------
# NequIP cells
# ---------------------------------------------------------------------------

def _atoms_abstract(cell: ShapeCell) -> Tuple[AtomsBatch, Any, int]:
    if cell.name == "molecule":
        n = cell.n_nodes * cell.batch
        e = cell.n_edges * cell.batch
        ng = cell.batch
    elif cell.name == "minibatch_lg":  # noqa: SIM114 — distinct sizing
        acc, tot = 1, 1
        for f in cell.fanout:
            acc *= f
            tot += acc
        n = cell.batch_nodes * tot
        e = n
        ng = 1
    else:
        n, e, ng = cell.n_nodes, cell.n_edges, 1
    n, e = _pad512(n), _pad512(e)
    batch = AtomsBatch(
        species=jax.ShapeDtypeStruct((n,), jnp.int32),
        pos=jax.ShapeDtypeStruct((n, 3), jnp.float32),
        edge_src=jax.ShapeDtypeStruct((e,), jnp.int32),
        edge_dst=jax.ShapeDtypeStruct((e,), jnp.int32),
        edge_mask=jax.ShapeDtypeStruct((e,), jnp.bool_),
        node_mask=jax.ShapeDtypeStruct((n,), jnp.bool_),
        graph_id=jax.ShapeDtypeStruct((n,), jnp.int32),
    )
    return batch, jax.ShapeDtypeStruct((ng,), jnp.float32), e, ng


def build_nequip_cell(arch: ArchConfig, cell: ShapeCell, mesh) -> Cell:
    cfg: NequIPConfig = arch.model
    batch_abs, e_abs, e, ng = _atoms_abstract(cell)
    params_abs = jax.eval_shape(
        partial(init_nequip_params, cfg=cfg), jax.random.PRNGKey(0)
    )
    opt_abs = abstract_adamw(params_abs)
    use_forces = cell.name == "molecule"

    def step(params, opt, batch, targets):
        lr = warmup_cosine(opt.step, 5e-3, 100, 10_000)
        if use_forces:
            f_t = jnp.zeros_like(batch.pos)
            lfn = lambda p: nequip_force_loss(p, cfg, batch, targets, f_t,
                                              n_graphs=ng)
        else:
            lfn = lambda p: nequip_energy_loss(p, cfg, batch, targets,
                                               n_graphs=ng)
        loss, grads = jax.value_and_grad(lfn)(params)
        params, opt = adamw_update(params, grads, opt, lr)
        return params, opt, loss

    C = cfg.channels
    flops = float(cfg.n_layers * e * (8 * C * 15 + 2 * cfg.n_rbf * 32
                                      + 2 * 32 * 8 * C)) * (4 if use_forces else 3)
    if mesh is None:
        return Cell(step, (params_abs, opt_abs, batch_abs, e_abs), None, None,
                    flops, donate=(0, 1))
    dp = _dp(mesh)
    pspecs = jax.tree.map(lambda p: P(*([None] * p.ndim)), params_abs)
    bspecs = AtomsBatch(
        species=P(dp), pos=P(dp, None), edge_src=P(dp), edge_dst=P(dp),
        edge_mask=P(dp), node_mask=P(dp), graph_id=P(dp),
    )
    ospecs = AdamWState(step=P(), m=pspecs, v=pspecs)
    return Cell(
        step, (params_abs, opt_abs, batch_abs, e_abs),
        _named(mesh, (pspecs, ospecs, bspecs, P(None))),
        _named(mesh, (pspecs, ospecs, P())),
        flops, donate=(0, 1),
    )


# ---------------------------------------------------------------------------
# Recsys cells
# ---------------------------------------------------------------------------

def _rec_abstract(cfg: AutoIntConfig, batch: int) -> RecsysBatch:
    return RecsysBatch(
        ids=jax.ShapeDtypeStruct((batch, cfg.n_fields, cfg.max_bag), jnp.int32),
        bag_mask=jax.ShapeDtypeStruct((batch, cfg.n_fields, cfg.max_bag),
                                      jnp.float32),
        labels=jax.ShapeDtypeStruct((batch,), jnp.float32),
    )


def _rec_flops(cfg: AutoIntConfig, batch: int, train: bool) -> float:
    F, d, H, D = cfg.n_fields, cfg.embed_dim, cfg.n_heads, cfg.d_attn
    attn = cfg.n_attn_layers * (3 * 2 * F * d * H * D + 2 * F * F * H * D * 2)
    dims = (F * H * D,) + tuple(cfg.mlp_dims)
    mlp = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    return float(batch * (attn + mlp)) * (3 if train else 1)


def build_recsys_cell(arch: ArchConfig, cell: ShapeCell, mesh) -> Cell:
    cfg: AutoIntConfig = arch.model
    params_abs = jax.eval_shape(
        partial(init_autoint_params, cfg=cfg), jax.random.PRNGKey(0)
    )
    pspecs = shd.recsys_param_specs(params_abs, mesh) if mesh else None
    dp = _dp(mesh)

    if cell.kind == "train":
        batch_abs = _rec_abstract(cfg, cell.batch)
        opt_abs = abstract_adamw(params_abs)

        def step(params, opt, batch):
            lr = warmup_cosine(opt.step, 1e-3, 1000, 300_000)
            loss, grads = jax.value_and_grad(autoint_loss)(params, cfg, batch)
            params, opt = adamw_update(params, grads, opt, lr)
            return params, opt, loss

        flops = _rec_flops(cfg, cell.batch, True)
        if mesh is None:
            return Cell(step, (params_abs, opt_abs, batch_abs), None, None,
                        flops, donate=(0, 1))
        bspecs = shd.recsys_batch_spec(mesh)
        ospecs = AdamWState(step=P(), m=pspecs, v=pspecs)
        return Cell(
            step, (params_abs, opt_abs, batch_abs),
            _named(mesh, (pspecs, ospecs, bspecs)),
            _named(mesh, (pspecs, ospecs, P())),
            flops, donate=(0, 1),
        )

    if cell.kind == "serve":
        batch_abs = _rec_abstract(cfg, cell.batch)

        def step(params, batch):
            return autoint_forward(params, cfg, batch)

        flops = _rec_flops(cfg, cell.batch, False)
        if mesh is None:
            return Cell(step, (params_abs, batch_abs), None, None, flops)
        return Cell(
            step, (params_abs, batch_abs),
            _named(mesh, (pspecs, shd.recsys_batch_spec(mesh))),
            _named(mesh, P(dp)),
            flops,
        )

    if cell.kind == "retrieval":
        batch_abs = _rec_abstract(cfg, cell.batch)
        cand_abs = jax.ShapeDtypeStruct(
            (cell.n_candidates, cfg.embed_dim), jnp.float32
        )

        def step(params, batch, cand):
            return retrieval_score(params, cfg, batch, cand, top_k=100)

        flops = float(2 * cell.n_candidates * cfg.embed_dim * cell.batch)
        if mesh is None:
            return Cell(step, (params_abs, batch_abs, cand_abs), None, None,
                        flops)
        # batch=1 query replicates; the 10⁶ candidates shard over dp
        rep_batch = RecsysBatch(ids=P(None, None, None),
                                bag_mask=P(None, None, None), labels=P(None))
        return Cell(
            step, (params_abs, batch_abs, cand_abs),
            _named(mesh, (pspecs, rep_batch, P(dp, None))),
            _named(mesh, (P(None, None), P(None, None))),
            flops,
        )

    raise ValueError(cell.kind)


# ---------------------------------------------------------------------------
# Euler cells (the paper's own architecture)
# ---------------------------------------------------------------------------

def build_euler_cell(arch: ArchConfig, cell: ShapeCell, mesh) -> Cell:
    # engine types come through the public facade (DESIGN.md §7); the AOT
    # cells are the one sanctioned use of the engine below the solver
    from ..euler import DistributedEngine, EngineState, FusedOut, StepOut

    ecfg = arch.model
    axes = tuple(mesh.axis_names)
    eng = DistributedEngine(mesh, axes, ecfg.caps, ecfg.n_levels)
    n, c = eng.n, ecfg.caps

    def sds(cap, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n, cap), dtype)

    state_abs = EngineState(
        pk_eid=sds(c.park_cap), pk_u=sds(c.park_cap), pk_v=sds(c.park_cap),
        pk_lau=sds(c.park_cap), pk_lav=sds(c.park_cap),
        pk_act=sds(c.park_cap), pk_own0=sds(c.park_cap),
        pk_mask=sds(c.park_cap, jnp.bool_),
        op_stub=sds(c.open_cap), op_vert=sds(c.open_cap),
        op_la=sds(c.open_cap), op_comp=sds(c.open_cap),
        op_own0=sds(c.open_cap), op_mask=sds(c.open_cap, jnp.bool_),
        tc_s1=sds(c.touch_cap), tc_s2=sds(c.touch_cap),
        tc_vert=sds(c.touch_cap), tc_la=sds(c.touch_cap),
        tc_comp=sds(c.touch_cap), tc_own0=sds(c.touch_cap),
        tc_mask=sds(c.touch_cap, jnp.bool_),
        le_eid=sds(c.edge_cap), le_u=sds(c.edge_cap), le_v=sds(c.edge_cap),
        le_lau=sds(c.edge_cap), le_lav=sds(c.edge_cap),
        le_mask=sds(c.edge_cap, jnp.bool_),
    )
    anc_abs = jax.ShapeDtypeStruct((ecfg.n_levels, n), jnp.int32)
    state_specs = shd.euler_state_specs(mesh, axes)

    # estimate useful work: sort + pairing + CC over the pool
    pool = 2 * c.new_cap + c.open_cap
    flops = float(n * pool * np.log2(max(2, pool)) * 8)

    if cell.name == "fused":
        # the whole-run program: level scan + on-device mate accumulation
        # + device Phase 3 (DESIGN.md §4), one host sync
        E = ecfg.fused_edges or n * c.edge_cap
        fn = eng.make_fused(E)
        sv_abs = jax.ShapeDtypeStruct((2 * E,), jnp.int32)
        in_sh = (NamedSharding(mesh, P(None, None)), _named(mesh, state_specs),
                 NamedSharding(mesh, P(None)))
        out_specs = FusedOut(
            circuit=P(None), mate=P(None),
            flags=P(axes, None, None), metrics=P(axes, None, None),
            phase3_ok=P(), phase3_rounds=P(),
        )
        p3 = float(2 * E * np.log2(max(2, 2 * E)) * 6)  # splice + list-rank
        return Cell(
            fn, (anc_abs, state_abs, sv_abs),
            in_sh, _named(mesh, out_specs), flops * ecfg.n_levels + p3,
            note="the full fused run: all levels + mate accumulation + "
                 "device Phase 3, one host sync",
        )

    level_abs = jax.ShapeDtypeStruct((), jnp.int32)
    fn = eng.make_superstep()
    in_sh = (NamedSharding(mesh, P()), NamedSharding(mesh, P(None, None)),
             _named(mesh, state_specs))
    out_specs = StepOut(
        state=state_specs,
        log_s1=P(axes, None), log_s2=P(axes, None), log_mask=P(axes, None),
        flags=P(axes, None), metrics=P(axes, None),
    )
    return Cell(
        fn, (level_abs, anc_abs, state_abs),
        in_sh, _named(mesh, out_specs), flops,
        note="one BSP superstep (ship + Phase 1) on the production mesh",
    )


# ---------------------------------------------------------------------------

BUILDERS = {
    "lm": build_lm_cell,
    "gnn": build_gnn_cell,
    "nequip": build_nequip_cell,
    "recsys": build_recsys_cell,
    "euler": build_euler_cell,
}


def build_cell(arch: ArchConfig, shape_name: str, mesh) -> Cell:
    cell = arch.shapes[shape_name]
    if cell.skip:
        raise SkippedCell(cell.skip)
    return BUILDERS[arch.family](arch, cell, mesh)


class SkippedCell(Exception):
    pass


def input_specs(arch: ArchConfig, shape_name: str, mesh=None):
    """ShapeDtypeStruct stand-ins for every model input (dry-run pattern)."""
    return build_cell(arch, shape_name, mesh).abstract_inputs
