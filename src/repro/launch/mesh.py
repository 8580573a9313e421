"""Mesh builders for the launchers.

Functions, not module-level constants: importing this module must not
touch jax device state (device count is locked at first jax init).
"""
from __future__ import annotations

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Smoke-test mesh over however many devices this host has."""
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (roofline targets).  Link bandwidths live
# with the comm layer's tier model (repro/comm/topology.py) so the
# roofline and the collective scheduler price the same hardware.
from repro.comm.topology import DCN_BW, ICI_BW  # noqa: E402,F401

PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
