"""The chips' published peaks (``bench/peaks.json``), keyed by ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

PATH = Path(__file__).with_name("peaks.json")


def for_kind(kind: str) -> dict:
    """Peaks of one device kind; an unknown kind is an error, never a default."""
    with open(PATH) as f:
        kinds = json.load(f)["kinds"]
    if kind not in kinds:
        raise KeyError(f"no published peaks for device kind {kind!r} in {PATH.name}")
    return kinds[kind]
