"""The chip's published peaks, keyed by the ``device_kind`` JAX reports.

The table is ``peaks.json`` beside this file; each entry names its source.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def load_table(path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def peaks_for(device_kind: str, table: dict | None = None) -> dict:
    """The peaks of ``device_kind``; ``KeyError`` naming it when absent."""
    table = load_table() if table is None else table
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; the table "
                       f"has {sorted(table)}")
    return table[device_kind]
