"""Device-resident document state: structure-of-arrays segment store.

Per document, ``max_slots`` fixed-capacity int32 arrays (XLA needs static
shapes; capacity overflow raises a per-doc flag for host escalation):

- ``length``     segment length (0 ⇒ unused slot; markers have length 1)
- ``text_start`` offset into the host-side text arena; segment splits are
                 pure arithmetic (tail start = head start + offset), so the
                 device never touches text bytes
- ``flags``      bit 0 = marker. Marker-ness is out-of-band — the arena
                 byte is NOT the classifier, so user text containing the
                 marker glyph U+FFFC round-trips correctly
- ``ins_seq``, ``ins_client``          insert stamp
- ``rem_seq``    earliest remove seq (NO_SEQ = never removed)
- ``rem_client_a``, ``rem_client_b``   up to two removing clients; a third
                 concurrent remover of the same segment sets ``overflow``
                 and the host replays that doc on the scalar oracle
- ``prop_key``, ``prop_val``  [S, P] per-slot annotation table: up to P
                 interned (key, value) property pairs (key -1 = empty
                 slot). LWW per key falls out of seq-ordered apply; a slot
                 needing a (P+1)th distinct key sets ``overflow``.
                 Ref: annotateRange mergeTree.ts:2598 +
                 segmentPropertiesManager.ts, tensorized
- ``count``      used slots (slots [0, count) are ordered and contiguous)

Ref: this is the tensorized form of the segment metadata in
packages/dds/merge-tree/src/mergeTree.ts (insert/remove stamps) with the
per-block PartialSequenceLengths cache (partialLengths.ts:62) replaced by
on-the-fly masked prefix sums.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..mergetree.mergetree import MergeTree
from ..mergetree.segments import NO_CLIENT, Segment

NO_SEQ = -1  # "never removed" sentinel
NO_KEY = -1  # empty property-table slot
FLAG_MARKER = 1  # flags bit 0

DEFAULT_MAX_PROPS = 8  # P: per-slot property-table capacity


class PropTable:
    """Host-side interning of annotation keys and values to dense int32
    ids. Dense interning (not hashing) — no collisions by construction.
    Values are canonicalised through JSON so equal values share one id."""

    def __init__(self):
        self._keys: list[str] = []
        self._key_ids: dict[str, int] = {}
        self._vals: list[Any] = []
        self._val_ids: dict[str, int] = {}

    def intern_key(self, key: str) -> int:
        kid = self._key_ids.get(key)
        if kid is None:
            kid = len(self._keys)
            self._key_ids[key] = kid
            self._keys.append(key)
        return kid

    def intern_val(self, value: Any) -> int:
        canon = json.dumps(value, sort_keys=True)
        vid = self._val_ids.get(canon)
        if vid is None:
            vid = len(self._vals)
            self._val_ids[canon] = vid
            self._vals.append(value)
        return vid

    def key(self, kid: int) -> str:
        return self._keys[kid]

    def val(self, vid: int) -> Any:
        return self._vals[vid]

    def snapshot(self) -> dict:
        return {"keys": list(self._keys), "vals": list(self._vals)}

    @classmethod
    def load(cls, snap: dict) -> "PropTable":
        t = cls()
        for k in snap["keys"]:
            t.intern_key(k)
        for v in snap["vals"]:
            t.intern_val(v)
        return t


@jax.tree_util.register_dataclass
@dataclass
class DocState:
    """One document (or, with a leading batch dim, D documents)."""

    length: jax.Array  # [S] int32
    text_start: jax.Array  # [S] int32
    flags: jax.Array  # [S] int32 (bit 0: marker)
    ins_seq: jax.Array  # [S] int32
    ins_client: jax.Array  # [S] int32
    rem_seq: jax.Array  # [S] int32
    rem_client_a: jax.Array  # [S] int32
    rem_client_b: jax.Array  # [S] int32
    prop_key: jax.Array  # [S, P] int32 (NO_KEY = empty)
    prop_val: jax.Array  # [S, P] int32
    count: jax.Array  # [] int32
    overflow: jax.Array  # [] bool — capacity / remove-client / prop overflow

    @property
    def max_slots(self) -> int:
        return self.length.shape[-1]

    @property
    def max_props(self) -> int:
        return self.prop_key.shape[-1]

    @classmethod
    def empty(cls, max_slots: int, max_props: int = DEFAULT_MAX_PROPS) -> "DocState":
        z = jnp.zeros((max_slots,), jnp.int32)
        return cls(
            length=z,
            text_start=z,
            flags=z,
            ins_seq=z,
            ins_client=jnp.full((max_slots,), NO_CLIENT, jnp.int32),
            rem_seq=jnp.full((max_slots,), NO_SEQ, jnp.int32),
            rem_client_a=jnp.full((max_slots,), NO_CLIENT, jnp.int32),
            rem_client_b=jnp.full((max_slots,), NO_CLIENT, jnp.int32),
            prop_key=jnp.full((max_slots, max_props), NO_KEY, jnp.int32),
            prop_val=jnp.zeros((max_slots, max_props), jnp.int32),
            count=jnp.asarray(0, jnp.int32),
            overflow=jnp.asarray(False, jnp.bool_),
        )


class TextArena:
    """Host-side append-only text store; the device sees only offsets."""

    def __init__(self):
        self._chunks: list[str] = []
        self._len = 0

    def append(self, text: str) -> int:
        start = self._len
        self._chunks.append(text)
        self._len += len(text)
        return start

    def __len__(self) -> int:
        return self._len

    def text(self) -> str:
        if len(self._chunks) > 1:
            self._chunks = ["".join(self._chunks)]
        return self._chunks[0] if self._chunks else ""

    def tail(self, start: int) -> str:
        """The text from ``start`` on, joined from the newest chunks only
        (its cost follows the tail, not the arena)."""
        need, parts = self._len - start, []
        for chunk in reversed(self._chunks):
            if need <= 0:
                break
            parts.append(chunk)
            need -= len(chunk)
        joined = "".join(reversed(parts))
        return joined[len(joined) - (self._len - start):]

    def slice(self, start: int, length: int) -> str:
        return self.text()[start : start + length]


def encode_tree(
    tree: MergeTree,
    arena: TextArena,
    max_slots: int,
    max_props: int = DEFAULT_MAX_PROPS,
    prop_table: Optional[PropTable] = None,
) -> DocState:
    """Encode a (fully-acked) oracle MergeTree into device arrays.

    Used to upload a doc snapshot to the device batch and by the
    kernel-vs-oracle validation tests. Segment properties require a
    ``prop_table`` to intern into (omitted ⇒ props raise).
    """
    n = len(tree.segments)
    if n > max_slots:
        raise ValueError(f"{n} segments exceed {max_slots} slots")
    length = np.zeros(max_slots, np.int32)
    text_start = np.zeros(max_slots, np.int32)
    flags = np.zeros(max_slots, np.int32)
    ins_seq = np.zeros(max_slots, np.int32)
    ins_client = np.full(max_slots, NO_CLIENT, np.int32)
    rem_seq = np.full(max_slots, NO_SEQ, np.int32)
    rem_a = np.full(max_slots, NO_CLIENT, np.int32)
    rem_b = np.full(max_slots, NO_CLIENT, np.int32)
    prop_key = np.full((max_slots, max_props), NO_KEY, np.int32)
    prop_val = np.zeros((max_slots, max_props), np.int32)
    overflow = False
    for i, seg in enumerate(tree.segments):
        if seg.is_pending():
            raise ValueError("cannot encode pending local state")
        length[i] = seg.length
        if seg.is_marker:
            # a 1-char placeholder keeps arena offsets consistent; the
            # flag, not the byte, marks it as a marker
            text_start[i] = arena.append("￼")
            flags[i] |= FLAG_MARKER
        else:
            text_start[i] = arena.append(seg.text)
        ins_seq[i] = seg.ins_seq
        ins_client[i] = seg.ins_client
        if seg.rem_seq is not None:
            rem_seq[i] = seg.rem_seq
            removers = sorted(seg.rem_clients)
            rem_a[i] = removers[0]
            if len(removers) > 1:
                rem_b[i] = removers[1]
            if len(removers) > 2:
                overflow = True
        if seg.props:
            if prop_table is None:
                raise ValueError("segment has props but no prop_table given")
            items = list(seg.props.items())
            if len(items) > max_props:
                overflow = True
                items = items[:max_props]
            for p, (k, v) in enumerate(items):
                prop_key[i, p] = prop_table.intern_key(k)
                prop_val[i, p] = prop_table.intern_val(v)
    return DocState(
        length=jnp.asarray(length),
        text_start=jnp.asarray(text_start),
        flags=jnp.asarray(flags),
        ins_seq=jnp.asarray(ins_seq),
        ins_client=jnp.asarray(ins_client),
        rem_seq=jnp.asarray(rem_seq),
        rem_client_a=jnp.asarray(rem_a),
        rem_client_b=jnp.asarray(rem_b),
        prop_key=jnp.asarray(prop_key),
        prop_val=jnp.asarray(prop_val),
        count=jnp.asarray(n, jnp.int32),
        overflow=jnp.asarray(overflow, jnp.bool_),
    )


def decode_state(
    state: DocState,
    arena: TextArena,
    prop_table: Optional[PropTable] = None,
) -> MergeTree:
    """Decode device arrays back into an oracle MergeTree (for comparison,
    summaries, and host escalation)."""
    tree = MergeTree()
    count = int(state.count)
    length = np.asarray(state.length)
    text_start = np.asarray(state.text_start)
    flags = np.asarray(state.flags)
    ins_seq = np.asarray(state.ins_seq)
    ins_client = np.asarray(state.ins_client)
    rem_seq = np.asarray(state.rem_seq)
    rem_a = np.asarray(state.rem_client_a)
    rem_b = np.asarray(state.rem_client_b)
    prop_key = np.asarray(state.prop_key)
    prop_val = np.asarray(state.prop_val)
    for i in range(count):
        is_marker = bool(flags[i] & FLAG_MARKER)
        text = "" if is_marker else arena.slice(int(text_start[i]), int(length[i]))
        props = {}
        for p in range(prop_key.shape[1]):
            if prop_key[i, p] != NO_KEY:
                if prop_table is None:
                    raise ValueError("state has props but no prop_table given")
                props[prop_table.key(int(prop_key[i, p]))] = prop_table.val(
                    int(prop_val[i, p])
                )
        seg = Segment(
            text=text,
            marker={"refType": 1} if is_marker else None,
            props=props,
            ins_seq=int(ins_seq[i]),
            ins_client=int(ins_client[i]),
        )
        if rem_seq[i] != NO_SEQ:
            seg.rem_seq = int(rem_seq[i])
            seg.rem_client = int(rem_a[i])
            seg.rem_clients = {int(rem_a[i])}
            if rem_b[i] != NO_CLIENT:
                seg.rem_clients.add(int(rem_b[i]))
        tree.segments.append(seg)
    return tree
