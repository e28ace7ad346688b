"""Reading both tiers of a saved applier, for the long-document cell.

``answers.device_texts`` reads the farm: the ``[D, S]`` arrays and the
sidecar's ``doc_keys``, which a doc promoted to the long tier leaves.
The long tier's arrays are ``long_<field>`` in the same ``.npz`` and
its rows are named in the sidecar's ``long`` entry ({row: [slot,
tenant, doc]}); a long doc's text is in its farm slot's arena. Numpy
alone, as in ``answers.py``.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark.answers import FLAG_MARKER, NO_SEQ, device_texts as _farm

LONG_PREFIX = "long_"


def device_texts(ckpt_path: str) -> tuple[dict, int, set]:
    """({(tenant, doc): text} over both tiers, how many docs were on the
    host, and the (tenant, doc) keys read from the long tier)."""
    texts, host = _farm(ckpt_path)
    with open(ckpt_path + ".json") as f:
        meta = json.load(f)
    rows = (meta.get("long") or {}).get("rows") or {}
    if not rows:
        return texts, host, set()
    npz = (f"{ckpt_path}.g{meta['gen']}.npz" if "gen" in meta
           else ckpt_path + ".npz")
    data = np.load(npz)
    count, rem, flags, start, length = (
        data[LONG_PREFIX + f] for f in ("count", "rem_seq", "flags",
                                        "text_start", "length"))
    on_long = set()
    for row_s, (slot, tenant, doc) in rows.items():
        row, arena = int(row_s), meta["arenas"][slot]
        n = int(count[row])
        live = (rem[row, :n] == NO_SEQ) & ((flags[row, :n] & FLAG_MARKER)
                                           == 0)
        texts[(tenant, doc)] = "".join(
            arena[s:s + ln] for s, ln in zip(start[row, :n][live].tolist(),
                                             length[row, :n][live].tolist()))
        on_long.add((tenant, doc))
    return texts, host, on_long
