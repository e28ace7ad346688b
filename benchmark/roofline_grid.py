"""Bytes one dispatch of the grids' ``matrix_cells`` kernel must move,
from its shapes: what ``kernel.matrix_cells_roofline_share`` is computed
from. The peak is ``roofline.py``'s.

One dispatch reads, for each of the G grids, its two vector rows' slot
planes (``length``, ``text_start``, ``ins_seq``, ``ins_client``,
``rem_seq``, ``rem_client_a``, ``rem_client_b``: 7 int32 planes of S
slots, and the int32 count) and its [K, 6] int32 setCell rows; and for
each cell written, one int32 cell read and written in the [G, H, H]
store and its int8 mark written.
"""

from __future__ import annotations

from benchmark.roofline import hbm_bytes_per_s

#: int32 planes of a vector row the kernel reads
VECTOR_PLANES = 7
#: int32 fields of a setCell row
CELL_FIELDS = 6


def matrix_cells_bytes(grids: int, slots: int, ops: int) -> int:
    """The bytes of one dispatch apart from the cells it writes."""
    vectors = 2 * grids * (VECTOR_PLANES * slots + 1) * 4
    return vectors + grids * ops * CELL_FIELDS * 4


def cell_bytes(cells: int) -> int:
    """One int32 cell read and one written, and one int8 mark written,
    per cell set."""
    return 9 * cells


def roofline_share(kernel: dict, device_kind: str):
    """% of the HBM peak: the bytes of the window's dispatches
    (``kernel``: their count ``n``, device ``seconds``, ``cells`` set and
    ``shape`` [G, S, K]) over their device seconds times the peak; None
    without a dispatch."""
    if not kernel.get("n") or not kernel.get("seconds"):
        return None
    moved = (kernel["n"] * matrix_cells_bytes(*kernel["shape"])
             + cell_bytes(kernel["cells"]))
    return 100.0 * moved / (kernel["seconds"] * hbm_bytes_per_s(device_kind))
