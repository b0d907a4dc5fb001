"""The one table of chip peaks (``peaks.json``), keyed by ``device_kind``."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def for_kind(kind: str) -> dict:
    """The peaks of a device kind; an unknown kind is an error."""
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]
