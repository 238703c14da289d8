"""Published peaks of a chip, keyed by JAX's ``device_kind``
(``peaks.json``). A kind that is not in the table is an error."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def for_kind(kind: str, table: Path = TABLE) -> dict:
    peaks = json.loads(table.read_text())
    if kind not in peaks:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{table.name}; add them with their source")
    return peaks[kind]
