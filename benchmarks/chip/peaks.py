"""Published peaks of the chips the benchmark runs on, by ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no entry in ``peaks.json``."""


def peak(kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of ``kind``; an unknown device is an error, not a default."""
    table = json.loads(Path(path).read_text())
    if kind not in table:
        raise UnknownDevice(f"no peaks for device kind {kind!r} in {path}; "
                            f"known: {sorted(table)}")
    return table[kind]
