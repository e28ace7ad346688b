"""Reading a saved applier's grids, for the grid cell.

The stage's base (``applier.json`` + ``applier.g{gen}.npz``, written by
``save_applier_checkpoint`` at the drain-stop) holds, beside the farm:

- ``grids`` in the sidecar: ``{"grids": G, "handles": H, "docs": {g:
  [row-vector slot, column-vector slot, row handles, column handles,
  host replica or None]}}``; ``doc_keys`` names a row-vector slot by
  the grid's doc id plus ``ROWS_SUFFIX`` (the doc's own slot, if any,
  holds its text);
- ``grid_idx`` and ``grid_val`` in the ``.npz``: every cell written, as
  the flat index ``(g * H + rh) * H + ch`` and the value read back from
  the device's grid store.

A vector slot's live segments, in order, give its handles: segment i
covers ``text_start .. text_start + length - 1`` (the arena holds only
handles, in insert order). Numpy alone, as in ``answers.py``.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark.answers import NO_SEQ
from benchmark.editor import DS_ID
from benchmark.grid_editor import GRID_CHANNEL

#: the applier's name for a grid's row vector: its doc id plus this
ROWS_SUFFIX = "\x00rows"


def read_grid_log(log, tenant: str, doc: str) -> list:
    """[(seq, cseq, refseq, client_id, grid wire op or None)] of one
    doc's sequenced stream, in log order."""
    from fluidframework_tpu.service.durable_log import record_messages

    topic = f"deltas/{tenant}/{doc}"
    out = []
    for off in range(log.refresh_topic(topic)):
        for m in record_messages(log.read(topic, off)):
            env, op = m.contents, None
            if getattr(m.type, "value", m.type) == "op" \
                    and type(env) is dict and env.get("kind") == "chanop" \
                    and env.get("address") == DS_ID:
                inner = env["contents"]
                if inner.get("address") == GRID_CHANNEL \
                        and "attach" not in inner:
                    op = inner["contents"]
            out.append((m.sequence_number, m.client_sequence_number,
                        m.reference_sequence_number, m.client_id, op))
    return out


def _handles(data, slot: int) -> list[int]:
    n = int(data["count"][slot])
    live = data["rem_seq"][slot, :n] == NO_SEQ
    return [h for s, ln in zip(data["text_start"][slot, :n][live].tolist(),
                               data["length"][slot, :n][live].tolist())
            for h in range(s, s + ln)]


def device_grids(ckpt_path: str) -> tuple[dict, set]:
    """({(tenant, doc): {(row, col): value}} of the grids on the device,
    the (tenant, doc) keys of the grids on the host)."""
    with open(ckpt_path + ".json") as f:
        meta = json.load(f)
    grids = meta.get("grids")
    if not grids:
        return {}, set()
    npz = (f"{ckpt_path}.g{meta['gen']}.npz" if "gen" in meta
           else ckpt_path + ".npz")
    data = np.load(npz)
    H = grids["handles"]
    idx = data["grid_idx"] if "grid_idx" in data.files else np.zeros(0)
    val = data["grid_val"] if "grid_val" in data.files else np.zeros(0)
    by_grid: dict = {}
    for flat, v in zip(idx.tolist(), val.tolist()):
        g, rest = divmod(flat, H * H)
        by_grid.setdefault(g, []).append((*divmod(rest, H), v))
    out, host = {}, set()
    for g_s, (rs, cs, _nr, _nc, on_host) in grids["docs"].items():
        tenant, name = meta["doc_keys"][str(rs)]
        key = (tenant, name.removesuffix(ROWS_SUFFIX))
        if on_host is not None:
            host.add(key)
            continue
        rows = {h: i for i, h in enumerate(_handles(data, rs))}
        cols = {h: i for i, h in enumerate(_handles(data, cs))}
        out[key] = {(rows[rh], cols[ch]): v
                    for rh, ch, v in by_grid.get(int(g_s), ())
                    if rh in rows and ch in cols}
    return out, host
