"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call is the natural
microseconds quantity for the row; derived carries the human-readable
values and claim checks).
"""
from __future__ import annotations

import sys
import traceback


def main() -> int:
    def emit(name, us, derived=""):
        print(f"{name},{us:.3f},{derived}")

    from benchmarks import (comm, creation, elasticity, kernelbench,
                            roofline_table, serving, throughput, workload)
    mods = [("fig2_creation", creation), ("fig3_fig5_workload", workload),
            ("etcd_throughput", throughput), ("elasticity", elasticity),
            ("kernels", kernelbench), ("roofline", roofline_table),
            ("serving", serving), ("comm", comm)]
    failed = []
    for name, mod in mods:
        try:
            mod.main(emit)
        except Exception as e:
            # keep going so every module reports, but never exit 0
            traceback.print_exc()
            print(f"{name},0,ERROR {e}")
            failed.append(name)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
