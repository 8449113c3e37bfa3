"""The least work a sweep must do, and the chip peaks it is held against.

The byte count follows from the graph's sizes alone, never from the
engine's tile layout or sweep implementation, so a later change that packs
tiles tighter or fuses the sweep is judged against the same work:

- per active edge: its 4-byte source id, the gathered source attribute of
  each query, and a 4-byte weight when the graph is weighted;
- per vertex and query: the attribute read, the accumulator, and the
  attribute written back.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["sweep_min_bytes", "peaks"]

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
ID_BYTES = 4
WEIGHT_BYTES = 4


def sweep_min_bytes(
    n: int, active_edges: int, *, attr_bytes: int, queries: int = 1,
    weighted: bool = False,
) -> int:
    """Bytes one sweep over ``active_edges`` edges and ``n`` vertices must move."""
    per_edge = ID_BYTES + queries * attr_bytes + (WEIGHT_BYTES if weighted else 0)
    per_vertex = 3 * queries * attr_bytes
    return active_edges * per_edge + n * per_vertex


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
