import os
# 512 placeholder devices on the CPU platform, pinned explicitly: on a
# machine with a chip the default backend would be the TPU, which would
# take the chip and could not build the production mesh
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["REPRO_DRYRUN_INNER"] = "1"   # unroll inner streaming loops

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract memory / cost / collective stats.

The environment lines MUST precede any jax import: jax locks the
platform and device count at first init, and the dry-run needs 512
placeholder host devices to build the 16x16 and 2x16x16 production
meshes.  Smoke tests and benchmarks do NOT set this (they see the
host's real device count).

Cost accounting: XLA's HloCostAnalysis counts a while-loop body ONCE,
so the scanned layer stack hides (R-1)/R of the FLOPs and collectives.
Each cell therefore compiles twice:
  1. the full rolled model  -> memory_analysis (true remat behaviour),
     base costs, out-of-loop collectives, and ONE super-block's costs;
  2. a single super-block probe (same shardings, inner loops unrolled)
     -> per-layer costs, added (R-1) more times.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
      --shape train_4k [--multi-pod] [--strategy optimized] [--out f.json]
"""
import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (  # noqa: E402
    BASELINE, OPTIMIZED, SHAPES, STRATEGIES, TrainConfig, registry,
    shape_applicable,
)
from repro.dist import sharding as shd  # noqa: E402
from repro.dist import steps as dsteps  # noqa: E402
from repro.dist.actsharding import activation_sharding  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import params as P  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.models.model import Model, input_specs  # noqa: E402


def _train_cfg(cfg, overrides=None) -> TrainConfig:
    kw = dict(param_dtype=("bfloat16" if cfg.opt_state_dtype == "bfloat16"
                           else "float32"))
    kw.update(overrides or {})
    return TrainConfig(**kw)


# ---------------------------------------------------------------------------
# Full-model lowering
# ---------------------------------------------------------------------------


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               strategy=None, train_overrides=None):
    """Lower one cell; returns (lowered, meta) without compiling."""
    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, {"skipped": why}
    strategy = strategy or BASELINE
    mesh = make_production_mesh(multi_pod=multi_pod)

    if shape.kind == "train":
        tcfg = _train_cfg(cfg, train_overrides)
        step, sshard, bshard = dsteps.build_train_step(
            cfg, tcfg, strategy, mesh, shape)
        state_abs = dsteps.abstract_train_state(cfg, tcfg, strategy)
        batch_abs = input_specs(cfg, shape)
        jitted = jax.jit(step,
                         in_shardings=(sshard, bshard),
                         out_shardings=(sshard, shd.replicated(mesh)),
                         donate_argnums=(0,))
        with mesh:
            lowered = jitted.lower(state_abs, batch_abs)
    elif shape.kind == "prefill":
        step, pshard, bshard, out_sh = dsteps.build_prefill_step(
            cfg, strategy, mesh, shape)
        model = Model(cfg)
        params_abs = model.abstract_params(jnp.bfloat16)
        batch_abs = input_specs(cfg, shape)
        jitted = jax.jit(step, in_shardings=(pshard, bshard),
                         out_shardings=out_sh)
        with mesh:
            lowered = jitted.lower(params_abs, batch_abs)
    else:  # decode
        step, in_sh, out_sh = dsteps.build_serve_step(
            cfg, strategy, mesh, shape)
        model = Model(cfg)
        params_abs = model.abstract_params(jnp.bfloat16)
        caches, tokens, idx = dsteps.abstract_serve_inputs(cfg, shape)
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(1,))
        with mesh:
            lowered = jitted.lower(params_abs, caches, tokens, idx)

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "strategy": strategy.name,
            "n_devices": 512 if multi_pod else 256}
    return lowered, meta


# ---------------------------------------------------------------------------
# Super-block probe (per-layer costs; loop bodies counted exactly once here)
# ---------------------------------------------------------------------------


def lower_probe(arch: str, shape_name: str, *, multi_pod=False,
                strategy=None, train_overrides=None, encoder=False):
    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    strategy = strategy or BASELINE
    mesh = make_production_mesh(multi_pod=multi_pod)
    cross = bool(cfg.encoder_layers)
    b = shape.global_batch
    s = 1 if shape.kind == "decode" else shape.seq_len
    cdt = jnp.bfloat16
    rules = shd.param_rules(strategy)

    if encoder:
        pdefs = {"p0": {k: v for k, v in
                        transformer.position_defs(
                            cfg_enc(cfg), 0, cross=False).items()}}
    else:
        pdefs = {f"p{i}": transformer.position_defs(cfg, i, cross)
                 for i in range(cfg.pattern_len)}
    pshard = shd.tree_shardings(pdefs, mesh, rules)
    pdt = jnp.bfloat16 if shape.kind != "train" else jnp.dtype(
        _train_cfg(cfg, train_overrides).param_dtype)
    pabs = P.abstract_params(pdefs, pdt)

    seq_ok = strategy.seq_shard_activations and \
        s % mesh.shape["model"] == 0
    xshard = shd.batch_sharding(mesh, 3, b, strategy,
                                seq_dim=1 if seq_ok else None)
    xabs = jax.ShapeDtypeStruct((b, s, cfg.d_model), cdt)
    positions = jnp.arange(s)

    enc_len = (shape.seq_len // max(cfg.encoder_seq_divisor, 1)
               if cross else 0)
    eabs = (jax.ShapeDtypeStruct((b, enc_len, cfg.d_model), cdt)
            if (cross and shape.kind != "decode" and not encoder) else None)
    eshard = (shd.batch_sharding(mesh, 3, b, strategy)
              if eabs is not None else None)

    def _enc_block(ps, xx):
        import repro.models.layers as L
        c2 = cfg_enc(cfg)
        h = L.norm_apply(c2, ps["p0"]["norm1"], xx)
        out, _ = L.attention_apply(c2, ps["p0"]["attn"], h,
                                   positions=None, causal=False)
        xx = xx + out
        h = L.norm_apply(c2, ps["p0"]["norm2"], xx)
        return xx + L.mlp_apply(c2, ps["p0"]["mlp"], h)

    if shape.kind == "train":
        if encoder:
            def probe(pslice, x):
                def inner(ps, xx):
                    return _enc_block(ps, xx).astype(jnp.float32).sum()
                inner = jax.checkpoint(inner)
                with activation_sharding(mesh, strategy):
                    return jax.grad(inner, argnums=(0, 1))(pslice, x)
            args = (pabs, xabs)
            in_sh = (pshard, xshard)
            probe_out_sh = (pshard, xshard)
        else:
            def probe(pslice, x, enc_out=None):
                def inner(ps, xx):
                    out, _, aux = transformer.superblock_apply(
                        cfg, ps, xx, positions=positions, enc_out=enc_out,
                        mode="train")
                    return out.astype(jnp.float32).sum() + aux
                inner = jax.checkpoint(inner)
                with activation_sharding(mesh, strategy):
                    return jax.grad(inner, argnums=(0, 1))(pslice, x)
            args = (pabs, xabs) + ((eabs,) if eabs is not None else ())
            in_sh = (pshard, xshard) + ((eshard,) if eabs is not None
                                        else ())
            # grads keep the param sharding (reduce-scatter, not
            # all-reduce), matching the real train step's constraint
            probe_out_sh = (pshard, xshard)
        probe_out_sh = None if shape.kind != "train" else probe_out_sh
    elif encoder:                      # prefill-time encoder layer (fwd)
        probe_out_sh = None
        def probe(pslice, x):
            with activation_sharding(mesh, strategy):
                return _enc_block(pslice, x)
        # encoder runs at enc_len, not seq_len
        e_len = shape.seq_len // max(cfg.encoder_seq_divisor, 1)
        xabs = jax.ShapeDtypeStruct((b, e_len, cfg.d_model), cdt)
        args = (pabs, xabs)
        in_sh = (pshard, shd.batch_sharding(mesh, 3, b, strategy))
    else:
        cdefs = transformer.cache_defs(cfg, b, shape.seq_len,
                                       enc_len, stacked=False)
        cshard = shd.cache_shardings(cdefs, mesh,
                                     shd_strategy_for_cache(strategy))
        cabs = P.abstract_params(cdefs, jnp.bfloat16)
        mode = shape.kind
        idx = jnp.int32(shape.seq_len - 1) if mode == "decode" else \
            jnp.int32(0)

        def probe(pslice, cslice, x, enc_out=None):
            with activation_sharding(mesh, strategy):
                out, new_cs, _ = transformer.superblock_apply(
                    cfg, pslice, x, positions=(
                        positions + idx if mode == "decode" else positions),
                    cslice=cslice, cache_index=idx, enc_out=enc_out,
                    mode=mode)
                return out, new_cs
        args = (pabs, cabs, xabs) + ((eabs,) if eabs is not None else ())
        in_sh = (pshard, cshard, xshard) + ((eshard,) if eabs is not None
                                            else ())
        probe_out_sh = None

    with mesh:
        if probe_out_sh is not None:
            jitted = jax.jit(probe, in_shardings=in_sh,
                             out_shardings=probe_out_sh)
        else:
            jitted = jax.jit(probe, in_shardings=in_sh)
        lowered = jitted.lower(*args)
    return lowered


def cfg_enc(cfg):
    """Encoder probe uses a single 'attn' pattern position, no cross."""
    import dataclasses
    return dataclasses.replace(cfg, block_pattern=("attn",),
                               encoder_layers=0, causal=False, moe=None)


def shd_strategy_for_cache(strategy):
    return strategy


# ---------------------------------------------------------------------------
# Cell runner: full + probe, combined accounting
# ---------------------------------------------------------------------------


def _cost_dict(ca):
    """Normalize Compiled.cost_analysis() across jax versions (dict vs
    one-element list of dicts)."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def _merge_coll(full, probe, reps, enc=None, enc_reps=0):
    out = {}
    ops = set(full) | set(probe) | set(enc or {})
    for op in ops:
        c = full.get(op, {"count": 0, "bytes": 0})
        p = probe.get(op, {"count": 0, "bytes": 0})
        e = (enc or {}).get(op, {"count": 0, "bytes": 0})
        out[op] = {
            "count": c["count"] + (reps - 1) * p["count"]
            + max(enc_reps - 1, 0) * e["count"],
            "bytes": c["bytes"] + (reps - 1) * p["bytes"]
            + max(enc_reps - 1, 0) * e["bytes"],
        }
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod=False, strategy=None,
             train_overrides=None, verbose=True):
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, multi_pod=multi_pod,
                               strategy=strategy,
                               train_overrides=train_overrides)
    if lowered is None:
        meta.update({"arch": arch, "shape": shape_name, "ok": True})
        return meta
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = _cost_dict(compiled.cost_analysis())
    coll = rl.collective_stats(compiled.as_text())

    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    reps = cfg.n_repeats

    # ---- probe: per-layer cost x (reps - 1) ----
    probe_l = lower_probe(arch, shape_name, multi_pod=multi_pod,
                          strategy=strategy,
                          train_overrides=train_overrides)
    probe_c = probe_l.compile()
    pcost = _cost_dict(probe_c.cost_analysis())
    pcoll = rl.collective_stats(probe_c.as_text())

    ecost, ecoll, enc_reps = {}, {"by_op": {}, "bytes": 0,
                                  "weighted_bytes": 0.0}, 0
    if cfg.encoder_layers and shape.kind != "decode":
        enc_reps = cfg.encoder_layers
        enc_l = lower_probe(arch, shape_name, multi_pod=multi_pod,
                            strategy=strategy,
                            train_overrides=train_overrides, encoder=True)
        enc_c = enc_l.compile()
        ecost = _cost_dict(enc_c.cost_analysis())
        ecoll = rl.collective_stats(enc_c.as_text())

    for key in ("flops", "bytes accessed"):
        cost[key] = (float(cost.get(key, 0.0))
                     + (reps - 1) * float(pcost.get(key, 0.0))
                     + max(enc_reps - 1, 0) * float(ecost.get(key, 0.0)))
    coll_total = {
        "by_op": _merge_coll(coll["by_op"], pcoll["by_op"], reps,
                             ecoll["by_op"], enc_reps),
        "bytes": coll["bytes"] + (reps - 1) * pcoll["bytes"]
        + max(enc_reps - 1, 0) * ecoll["bytes"],
        "weighted_bytes": coll["weighted_bytes"]
        + (reps - 1) * pcoll["weighted_bytes"]
        + max(enc_reps - 1, 0) * ecoll["weighted_bytes"],
    }

    mflops = rl.analytic_model_flops(cfg, shape) / meta["n_devices"]
    roof = rl.roofline(cost, mem, coll_total,
                       model_flops_per_device=mflops,
                       n_devices=meta["n_devices"])
    meta.update({
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "roofline": roof,
        "collectives": coll_total["by_op"],
    })
    if verbose:
        mb = roof["memory_per_device_bytes"]["total_live"] / 2**30
        print(f"[dryrun] {arch} {shape_name} {meta['mesh']} "
              f"{meta['strategy']}: compile={t_compile:.1f}s "
              f"mem/dev={mb:.2f}GiB dom={roof['dominant']} "
              f"frac={roof['roofline_fraction']:.3f} "
              f"useful={roof['useful_flops_ratio']:.2f}")
        print("  memory_analysis:", mem)
        print("  cost_analysis: flops=%.3e bytes=%.3e (per device)" %
              (roof["hlo_flops_per_device"], roof["hlo_bytes_per_device"]))
        print("  collectives:", json.dumps(coll_total["by_op"]))
    return meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--spec", default=None,
                    help="declarative WorkloadSpec JSON (kind: dryrun); "
                         "arch/shape/mesh/strategy come from the spec")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="baseline",
                    choices=list(STRATEGIES))
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.spec:
        from repro.spec import load_spec
        wspec = load_spec(args.spec)
        assert wspec.kind == "dryrun", \
            f"launch.dryrun needs a dryrun spec, got kind={wspec.kind!r}"
        args.arch = wspec.arch
        args.shape = wspec.dryrun.shape
        args.multi_pod = args.multi_pod or wspec.dryrun.multi_pod
        strategy = wspec.resolved_strategy
    else:
        assert args.arch and args.shape, \
            "--arch and --shape (or --spec) are required"
        strategy = STRATEGIES[args.strategy]
    res = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   strategy=strategy)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
