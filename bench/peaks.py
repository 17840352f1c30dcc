"""Published peaks of each chip, keyed by ``device_kind`` as JAX reports
it. A kind that is not in ``peaks.json`` is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    """The chip has no entry in the table of peaks."""


def peaks_for(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no published peaks for device_kind "
                            f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
