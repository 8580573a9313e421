"""Config registry: ``get(config_id)`` is the one resolver.

Each assigned architecture lives in its own module (``configs/<id>.py``,
dashes become underscores) and exposes ``CONFIG`` (the exact published
config) and ``SMOKE`` (a reduced same-family config for CPU tests).
Both are registry ids: ``"yi-6b"`` names the published config and
``"yi-6b-smoke"`` the reduced one, so a caller always says which it
runs and no layer substitutes one for the other.
"""
from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig

ARCH_IDS = [
    "chatglm3-6b",
    "yi-6b",
    "qwen2-72b",
    "deepseek-67b",
    "xlstm-1.3b",
    "arctic-480b",
    "granite-moe-1b-a400m",
    "pixtral-12b",
    "jamba-v0.1-52b",
    "whisper-base",
]

# The paper itself has no model; its workload proxy (LAMMPS / CORAL-2
# stand-in) is a small compute-bound config used by orchestration benches.
EXTRA_IDS = ["lammps-proxy"]

SMOKE_SUFFIX = "-smoke"

# every id ``get`` resolves: published configs, then their smoke cuts
CONFIG_IDS = (ARCH_IDS + EXTRA_IDS
              + [a + SMOKE_SUFFIX for a in ARCH_IDS + EXTRA_IDS])


def _module(arch_id: str) -> str:
    return "repro.configs." + arch_id.replace("-", "_").replace(".", "_")


def get(config_id: str) -> ModelConfig:
    """The config a registry id names: ``<arch>`` is the published
    config, ``<arch>-smoke`` its reduced CPU-test config."""
    if config_id not in CONFIG_IDS:
        raise KeyError(f"unknown config {config_id!r}; known: {CONFIG_IDS}")
    smoke = config_id.endswith(SMOKE_SUFFIX)
    arch = config_id[:-len(SMOKE_SUFFIX)] if smoke else config_id
    mod = importlib.import_module(_module(arch))
    return mod.SMOKE if smoke else mod.CONFIG


def all_configs():
    return {a: get(a) for a in ARCH_IDS}
