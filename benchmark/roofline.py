"""Bytes a kernel must move, from its shapes, and the device's peak:
what a kernel's roofline share is computed from, kept with the
benchmark so that no change to the program moves the yardstick.

Peaks by ``device_kind`` (as JAX reports it). Source: Google Cloud
documentation, "TPU v5e": 16 GB of HBM at 819 GB/s per chip. A device
not in the table is an error, not a default.
"""

from __future__ import annotations

HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}

#: int32 planes of a slot row: length, text_start, flags, ins_seq,
#: ins_client, rem_seq, rem_client_a, rem_client_b
SLOT_PLANES = 8
OP_FIELDS = 12


def hbm_bytes_per_s(device_kind: str) -> float:
    if device_kind not in HBM_BYTES_PER_S:
        raise KeyError(f"no HBM peak for device kind {device_kind!r}")
    return HBM_BYTES_PER_S[device_kind]


def long_apply_bytes(rows: int, slots: int, ops: int, props: int = 8) -> int:
    """One dispatch of the long tier's apply over [rows, slots]: every
    state array read once and written once (8 int32 slot planes, the two
    [slots, props] int32 property tables, the int32 count and the bool
    overflow per row), plus the [rows, ops, 12] int32 op rows read."""
    state = rows * (slots * (SLOT_PLANES + 2 * props) * 4 + 4 + 1)
    return 2 * state + rows * ops * OP_FIELDS * 4
