"""Sweep XLA flag sets over the serving steps; record the winners.

For one (arch, mesh) cell the sweep builds the engine's two hot jitted
programs — the fixed-slot paged decode step and the padded prefill step
— lowers each once, then compiles the SAME lowering under every
candidate flag set via ``compiler_options`` and times it.  Backends
that reject a flag (the CPU backend knows no ``xla_tpu_*``) mark the
set unsupported and fall back to the base compile, so the sweep runs —
and the plumbing stays testable — on any machine.

Winners persist to ``TUNED_FLAGS.json`` keyed by ``tune_key(arch,
mesh)`` (``"yi-6b@2x4"``): launchers and benchmarks look the tuned set
up by key instead of re-sweeping.

  PYTHONPATH=src python -m repro.tune.autotune --arch yi-6b \
      --dp 1 --tp 1 --iters 10

"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, Optional, Sequence

import numpy as np

from repro.tune.flagsets import FLAG_SETS

TUNED_FLAGS = "TUNED_FLAGS.json"


def tune_key(arch: str, mesh) -> str:
    """Stable lookup key for one (arch, mesh) cell: ``"arch@DxT"``.

    ``mesh`` is a jax Mesh or a plain shape sequence — the key encodes
    axis sizes only, in mesh order, so a relaunch on an equal-shaped
    mesh finds its tuned flags.
    """
    if hasattr(mesh, "shape"):
        dims = [int(s) for s in dict(mesh.shape).values()]
    else:
        dims = [int(s) for s in mesh]
    return f"{arch}@{'x'.join(str(d) for d in dims)}"


# --------------------------------------------------------------------------
# Timing one compiled step
# --------------------------------------------------------------------------


def _time_compiled(compiled, args, iters: int, warmup: int) -> float:
    """Median wall-clock ms per call of an AOT-compiled step."""
    import jax
    for _ in range(warmup):
        out = compiled(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def _compile_with(lowered, flags: Dict[str, str]):
    """(compiled, supported): flag sets the backend rejects fall back to
    the base compile so every row of the sweep still yields a number."""
    if not flags:
        return lowered.compile(), True
    try:
        return lowered.compile(compiler_options=dict(flags)), True
    except Exception:
        return lowered.compile(), False


# --------------------------------------------------------------------------
# The sweep
# --------------------------------------------------------------------------


def sweep(cfg, mesh, *, strategy=None, n_slots: int = 4, page_size: int = 8,
          max_seq_len: int = 64, prompt_len: int = 16,
          flag_names: Optional[Sequence[str]] = None, iters: int = 10,
          warmup: int = 3, seed: int = 0) -> Dict:
    """Time decode + prefill under every flag set; return the cell record.

    Returns ``{"key_shape": ..., "results": {set: {"decode_ms",
    "prefill_ms", "supported"}}, "best": set, "flags": {...}}`` —
    ``best`` minimizes decode time (the serving steady state) over the
    supported sets, ties broken toward fewer flags.
    """
    import jax
    import jax.numpy as jnp

    from repro.configs import BASELINE
    from repro.configs.base import WorkloadShape
    from repro.dist import sharding as shd
    from repro.dist import steps as dsteps
    from repro.models.model import Model
    from repro.serve import paging

    strategy = strategy or BASELINE
    names = list(flag_names or FLAG_SETS)
    pps = max_seq_len // page_size
    layout = dsteps.PagedLayout(page_size=page_size, pages_per_slot=pps,
                                n_pages=n_slots * pps + 1)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(seed))

    # -- decode: the fixed-slot paged step (no donation: one lowering is
    # re-compiled and re-run under every flag set)
    dshape = WorkloadShape(f"tune{n_slots}", "decode", max_seq_len, n_slots)
    raw_decode, din, dout = dsteps.build_decode_step(
        cfg, strategy, mesh, dshape, paged=layout)
    params = jax.tree_util.tree_map(jax.device_put, params, din[0])
    pool = jax.tree_util.tree_map(
        jax.device_put, paging.init_pool(cfg, n_slots, layout), din[1])
    bt = np.zeros((n_slots, pps), np.int32)
    for s in range(n_slots):           # every slot mid-sequence, 1 page
        bt[s, 0] = 1 + s
    dec_args = (params, pool, np.ones((n_slots, 1), np.int32), bt,
                np.full((n_slots,), page_size // 2, np.int32))
    dec_low = jax.jit(raw_decode, in_shardings=din,
                      out_shardings=dout).lower(*dec_args)

    # -- prefill: the padded fixed-capacity step
    pshape = WorkloadShape(f"tune_prefill{prompt_len}", "prefill",
                           prompt_len, 1)
    raw_prefill, pp_sh, bshard, pout = dsteps.build_prefill_step(
        cfg, strategy, mesh, pshape, ragged=True)
    pre_args = (params, {"tokens": np.ones((1, prompt_len), np.int32)},
                np.array([prompt_len - 1], np.int32))
    pre_low = jax.jit(raw_prefill, in_shardings=(
        pp_sh, {"tokens": bshard["tokens"]}, shd.replicated(mesh)),
        out_shardings=pout).lower(*pre_args)

    results: Dict[str, Dict] = {}
    for name in names:
        flags = FLAG_SETS[name]
        dec_c, dec_ok = _compile_with(dec_low, flags)
        pre_c, pre_ok = _compile_with(pre_low, flags)
        results[name] = {
            "decode_ms": _time_compiled(dec_c, dec_args, iters, warmup),
            "prefill_ms": _time_compiled(pre_c, pre_args, iters, warmup),
            "supported": bool(dec_ok and pre_ok),
            "n_flags": len(flags),
        }

    supported = [n for n in names if results[n]["supported"]] or names
    best = min(supported, key=lambda n: (results[n]["decode_ms"],
                                         results[n]["n_flags"]))
    return {
        "mesh_shape": dict(mesh.shape),
        "results": results,
        "best": best,
        "flags": dict(FLAG_SETS[best]),
    }


# --------------------------------------------------------------------------
# The TUNED_FLAGS.json registry
# --------------------------------------------------------------------------


def record(key: str, cell: Dict, path: str = TUNED_FLAGS) -> Dict:
    """Merge one swept cell into the tuned-flags file under ``key``."""
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data[key] = cell
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    return data


def load_tuned(key: str, path: str = TUNED_FLAGS) -> Optional[Dict[str, str]]:
    """The winning flag dict for ``key``, or None when never swept."""
    if not os.path.exists(path):
        return None
    with open(path) as f:
        data = json.load(f)
    cell = data.get(key)
    return None if cell is None else dict(cell.get("flags", {}))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b-smoke")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default=TUNED_FLAGS)
    args = ap.parse_args()

    from repro.configs import registry
    from repro.launch.mesh import make_local_mesh
    cfg, mesh = registry.get(args.arch), make_local_mesh(args.dp, args.tp)
    cell = sweep(cfg, mesh, iters=args.iters)
    key = tune_key(args.arch, mesh)
    record(key, cell, args.out)
    print(f"{key}: best={cell['best']}")
    for name, row in cell["results"].items():
        mark = "" if row["supported"] else "  (unsupported, base timing)"
        print(f"  {name:<18} decode {row['decode_ms']:7.3f} ms  "
              f"prefill {row['prefill_ms']:7.3f} ms{mark}")


if __name__ == "__main__":
    main()
