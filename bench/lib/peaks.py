"""The chip's published peaks, from ``bench/peaks.json``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" in {path}")
    return table[device_kind]
