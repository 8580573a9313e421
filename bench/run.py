#!/usr/bin/env python3
"""The on-chip serving benchmark's entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the checkout's root on a machine with the chips the cell asks
for.  It builds the cell's server from its configuration file (one
engine, or one on each of the cell's chips behind the Router) with
weights made from ``--seed``, warms up the programs the window runs,
offers the cell's traffic for ``--seconds``, then checks a sample of
the served tokens against the plain reference.  The last line of its
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``check``: each number compared with its limit.
The same numbers are the last lines of standard error.

Without a TPU, with fewer chips than the cell asks for, or on a device
kind missing from ``bench/peaks.json`` it exits non-zero and prints no
result.  JAX's compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def enable_cache(jax) -> None:
    """Every program in the persistent cache, at a fixed path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness, peaks
    cell = harness.load_cell(args.workload)

    import jax
    enable_cache(jax)
    dev = harness.device_info(jax)
    if dev["platform"] != "tpu":
        print(f"no TPU: JAX runs on {dev['platform']!r}", file=sys.stderr)
        return 2
    if dev["count"] < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, JAX sees "
              f"{dev['count']}", file=sys.stderr)
        return 2
    try:
        peak_table = peaks.for_kind(dev["kind"])
    except peaks.UnknownDevice as e:
        print(e, file=sys.stderr)
        return 2

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, peak_table)
    for name, n in result["check"].items():
        print(f"check {name}: {n['value']!r} (limit {n['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
