"""E7 — roofline analysis per (arch × shape) on the production mesh.

Methodology (EXPERIMENTS.md §Roofline):
  · compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
  · memory term     = HLO_bytes / HBM_bw               (per chip)
  · collective term = collective_bytes / link_bw       (per chip)

Sources: ``compiled.cost_analysis()`` + HLO-text collective parsing from
the dry-run (launch.dryrun.analyse).  **Scan-body correction**: XLA counts
while/scan bodies once, so for LM cells the scanned transformer stack is
costed *compositionally* — a one-layer program (full attention, no remat,
dense xent) is lowered on the same mesh and scaled by L, then embed/head +
optimizer programs are added.  GNN/recsys cells contain no scans (direct).
The Euler superstep is re-lowered in static-rounds analysis mode so every
hook/splice round is visible; sorts and scans longer than
``repro.core.bounded.NATIVE_MAX`` stay loops and count one stage each.  The dominant term and the 6·N·D
useful-FLOPs ratio are reported per cell.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from functools import partial
from typing import Dict, Optional

import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def _analyse_program(fn, abstract_inputs, mesh, in_sh=None, out_sh=None,
                     donate=()):
    import jax

    from repro.launch.dryrun import parse_collective_bytes

    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        compiled = jitted.lower(*abstract_inputs).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    coll = parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
        "peak_bytes": compiled.memory_analysis().temp_size_in_bytes,
    }


# ---------------------------------------------------------------------------
# compositional LM cost model
# ---------------------------------------------------------------------------

def lm_cell_cost(arch, shape_name: str, mesh) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    from repro.launch import steps as S
    from repro.models import transformer as T
    from repro.optim.adamw import abstract_adamw, adamw_update
    from repro.parallel import sharding as shd
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = arch.model
    cell = arch.shapes[shape_name]
    dp = shd.dp_axes_of(mesh)
    tp = "model"
    B, Sq = cell.batch, cell.seq_len

    one = dataclasses.replace(cfg, n_layers=1, remat=False)
    layer_abs = jax.eval_shape(
        lambda: T.init_layer_params(jax.random.PRNGKey(0), one))
    positions_abs = jax.ShapeDtypeStruct((B, 1 if cell.kind == "decode"
                                          else Sq), jnp.int32)

    lspecs = shd.lm_param_specs({"layers": jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), layer_abs
    )}, mesh)["layers"]
    lspecs = jax.tree.map(lambda p: P(*tuple(p)[1:]), lspecs,
                          is_leaf=lambda x: isinstance(x, P))
    named = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))

    if cell.kind == "train":
        x_abs = jax.ShapeDtypeStruct((B, Sq, cfg.d_model), cfg.dtype)

        def layer_prog(x, layer, positions):
            def loss_fn(x):
                y, aux = T._layer_fwd(one, x, layer, positions, dp, tp,
                                      mesh=mesh)
                return jnp.sum(y.astype(jnp.float32)) + aux
            return jax.grad(loss_fn)(x)

        lay = _analyse_program(
            layer_prog, (x_abs, layer_abs, positions_abs), mesh,
            in_sh=(NamedSharding(mesh, P(dp, tp, None)), named(lspecs),
                   NamedSharding(mesh, P(dp, None))),
        )

        # embed + head + xent + their backward
        tok_abs = jax.ShapeDtypeStruct((B, Sq), jnp.int32)
        emb_abs = jax.eval_shape(lambda: {
            "embed": T.dense_init(jax.random.PRNGKey(0), cfg.vocab,
                                  cfg.d_model, cfg.dtype),
            "lm_head": T.dense_init(jax.random.PRNGKey(0), cfg.d_model,
                                    cfg.vocab, cfg.dtype),
        })

        def embhead_prog(p, tokens):
            from repro.models.layers import chunked_cross_entropy

            def loss_fn(p):
                x = p["embed"][tokens]
                return chunked_cross_entropy(
                    x.reshape(B * Sq, -1), p["lm_head"],
                    tokens.reshape(B * Sq))
            return jax.grad(loss_fn)(p)

        espec = {"embed": P(tp, dp), "lm_head": P(dp, tp)}
        emb = _analyse_program(
            embhead_prog, (emb_abs, tok_abs), mesh,
            in_sh=(named(espec), NamedSharding(mesh, P(dp, None))),
        )

        # optimizer over the full parameter tree
        params_abs = T.abstract_lm_params(cfg)
        opt_abs = abstract_adamw(params_abs)
        pspecs = shd.lm_param_specs(params_abs, mesh)

        def opt_prog(params, opt):
            grads = jax.tree.map(jnp.ones_like, params)
            return adamw_update(params, grads, opt, jnp.float32(1e-4))

        from repro.optim.adamw import AdamWState
        opt_cost = _analyse_program(
            opt_prog, (params_abs, opt_abs), mesh,
            in_sh=(named(pspecs),
                   named(AdamWState(step=P(), m=pspecs, v=pspecs))),
            donate=(0, 1),
        )
        L = cfg.n_layers
        return {k: emb[k] + L * lay[k] + opt_cost[k]
                for k in ("flops", "bytes", "coll")}

    if cell.kind == "prefill":
        x_abs = jax.ShapeDtypeStruct((B, Sq, cfg.d_model), cfg.dtype)

        def layer_prog(x, layer, positions):
            y, _ = T._layer_fwd(one, x, layer, positions, dp, tp)
            return y

        lay = _analyse_program(
            layer_prog, (x_abs, layer_abs, positions_abs), mesh,
            in_sh=(NamedSharding(mesh, P(dp, tp, None)), named(lspecs),
                   NamedSharding(mesh, P(dp, None))),
        )
        # embed + last-position head
        tok_abs = jax.ShapeDtypeStruct((B, Sq), jnp.int32)
        emb_abs = jax.eval_shape(lambda: {
            "embed": T.dense_init(jax.random.PRNGKey(0), cfg.vocab,
                                  cfg.d_model, cfg.dtype),
            "lm_head": T.dense_init(jax.random.PRNGKey(0), cfg.d_model,
                                    cfg.vocab, cfg.dtype),
        })

        def embhead_prog(p, tokens):
            x = p["embed"][tokens]
            return x[:, -1] @ p["lm_head"]

        emb = _analyse_program(
            embhead_prog, (emb_abs, tok_abs), mesh,
            in_sh=(named({"embed": P(tp, dp), "lm_head": P(dp, tp)}),
                   NamedSharding(mesh, P(dp, None))),
        )
        L = cfg.n_layers
        return {k: emb[k] + L * lay[k] for k in ("flops", "bytes", "coll")}

    if cell.kind == "decode":
        kv1_abs = jax.ShapeDtypeStruct(
            (B, Sq, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
        x_abs = jax.ShapeDtypeStruct((B, 1, cfg.d_model), cfg.dtype)
        pos_abs = jax.ShapeDtypeStruct((B,), jnp.int32)

        def layer_prog(x, layer, kc, vc, pos):
            from repro.models.layers import apply_rope, gqa_attention, rmsnorm

            h = rmsnorm(x, layer["ln1"])
            dh = one.head_dim
            q = (h @ layer["wq"]).reshape(B, 1, one.n_heads, dh)
            k = (h @ layer["wk"]).reshape(B, 1, one.n_kv_heads, dh)
            v = (h @ layer["wv"]).reshape(B, 1, one.n_kv_heads, dh)
            q = apply_rope(q, pos[:, None], one.rope_theta)
            k = apply_rope(k, pos[:, None], one.rope_theta)
            bidx = jnp.arange(B)
            kc = kc.at[bidx, pos].set(k[:, 0])
            vc = vc.at[bidx, pos].set(v[:, 0])
            attn = gqa_attention(q, kc, vc, causal=False, kv_len=pos + 1)
            x = x + attn.reshape(B, 1, -1) @ layer["wo"]
            h = rmsnorm(x, layer["ln2"])
            if one.moe:
                from repro.models.moe import moe_ffn
                y, _ = moe_ffn(layer["moe"], h.reshape(B, -1), one.moe,
                               ep_axis=tp, dp_axes=dp)
                x = x + y.reshape(B, 1, -1)
            else:
                y = jax.nn.silu(h @ layer["w_gate"]) * (h @ layer["w_up"])
                x = x + y @ layer["w_down"]
            return x, kc, vc

        from repro.launch.steps import _lm_kv_specs
        kv_specs = _lm_kv_specs(cfg, mesh)
        kspec = P(*tuple(kv_specs.k)[1:])
        lay = _analyse_program(
            layer_prog,
            (x_abs, layer_abs, kv1_abs, kv1_abs, pos_abs), mesh,
            in_sh=(NamedSharding(mesh, P(dp, None, None)), named(lspecs),
                   NamedSharding(mesh, kspec), NamedSharding(mesh, kspec),
                   NamedSharding(mesh, P(dp))),
        )
        L = cfg.n_layers
        # embed + head for one token
        return {k: L * lay[k] for k in ("flops", "bytes", "coll")}

    raise ValueError(cell.kind)


def euler_cell_cost(arch, mesh) -> Dict[str, float]:
    from repro.configs.registry import get_config
    from repro.launch.steps import build_euler_cell

    a = get_config("euler-rmat")
    model = dataclasses.replace(a.model,
                                caps=dataclasses.replace(
                                    a.model.caps, static_splice=True))
    a = dataclasses.replace(a, model=model)
    cell = build_euler_cell(a, a.shapes["superstep"], mesh)
    return _analyse_program(cell.fn, cell.abstract_inputs, mesh,
                            in_sh=cell.in_shardings,
                            out_sh=cell.out_shardings)


def terms(costs: Dict[str, float], model_flops_per_dev: float) -> Dict:
    t_c = costs["flops"] / PEAK_FLOPS
    t_m = costs["bytes"] / HBM_BW
    t_x = costs["coll"] / ICI_BW
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])[0]
    bound = max(t_c, t_m, t_x)
    return {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom,
        "useful_frac": model_flops_per_dev / costs["flops"]
        if costs["flops"] else 0.0,
        "roofline_frac": (model_flops_per_dev / PEAK_FLOPS) / bound
        if bound else 0.0,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--json", default="roofline.json")
    ap.add_argument("--from-dryrun", default="dryrun_single_pod.json")
    args = ap.parse_args()

    import os
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    from repro.configs.registry import ARCH_IDS, get_config
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh()
    n_chips = 256

    dry = {}
    if os.path.exists(args.from_dryrun):
        for rec in json.load(open(args.from_dryrun)):
            dry[(rec["arch"], rec["shape"])] = rec

    rows = []
    archs = [args.arch] if args.arch else ARCH_IDS
    for aid in archs:
        arch = get_config(aid)
        shapes = [args.shape] if args.shape else list(arch.shapes)
        for sname in shapes:
            cell_cfg = arch.shapes[sname]
            if cell_cfg.skip:
                rows.append({"arch": aid, "shape": sname, "skip": cell_cfg.skip})
                continue
            rec = dry.get((aid, sname), {})
            try:
                if arch.family == "lm":
                    costs = lm_cell_cost(arch, sname, mesh)
                    method = "compositional (per-layer × L + embed/head + opt)"
                elif arch.family == "euler":
                    costs = euler_cell_cost(arch, mesh)
                    method = "static-rounds analysis mode"
                else:
                    pd = rec.get("per_device")
                    if pd is None:
                        from repro.launch.dryrun import run_cell
                        rec = run_cell(aid, sname, False, verbose=False)
                        pd = rec["per_device"]
                    costs = {"flops": pd["hlo_flops"],
                             "bytes": pd["hlo_bytes"],
                             "coll": pd["collective_bytes"]}
                    method = "direct (no scans)"
                from repro.launch.steps import build_cell
                mf = build_cell(arch, sname, mesh).model_flops / n_chips
                row = {"arch": aid, "shape": sname, "method": method,
                       "model_flops_per_dev": mf, **costs,
                       **terms(costs, mf)}
                if rec.get("memory"):
                    row["peak_temp_gib"] = rec["memory"]["temp_bytes"] / 2**30
                rows.append(row)
                print(f"[roofline] {aid} × {sname}: "
                      f"c={row['compute_s']*1e3:.2f}ms "
                      f"m={row['memory_s']*1e3:.2f}ms "
                      f"x={row['collective_s']*1e3:.2f}ms "
                      f"→ {row['dominant']} "
                      f"(roofline {row['roofline_frac']*100:.1f}%)")
            except Exception as e:  # noqa: BLE001
                rows.append({"arch": aid, "shape": sname, "error": repr(e)})
                print(f"[roofline] {aid} × {sname} ERROR: {e}")
    with open(args.json, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"[roofline] wrote {args.json} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
