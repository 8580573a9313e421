"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A device kind missing from ``peaks.json`` is an error, never a default:
a share of a peak is only meaningful against the chip that ran it.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


class UnknownDevice(LookupError):
    pass


def for_kind(kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if kind not in table:
        raise UnknownDevice(
            f"device kind {kind!r} has no entry in {TABLE.name} "
            f"(known: {sorted(table)})")
    return table[kind]
