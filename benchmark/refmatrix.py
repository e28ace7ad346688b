"""The plain reference for SharedMatrix grids.

It imports nothing of the program. A grid is two permutation vectors and
a dict of cells (the semantics of packages/dds/matrix):

- each vector is a ``refmerge_runs.RunString`` whose characters are
  handles (``chr(HANDLE_BASE + h)``), allocated in the order the inserts
  are applied, so every run is a run of handles with refmerge's insert,
  remove and perspective rules;
- ops are applied in seq order, each at its author's (refSeq, client)
  perspective: ``insertRows``/``insertCols`` (pos, count) insert count new
  handles, ``removeRows``/``removeCols`` (pos, count) remove count items,
  ``setCell`` (row, col, value) finds the handles at row and col and sets
  the cell, the later seq winning;
- a setCell whose row or column is already removed (by any op applied
  before it), or whose position is outside its perspective's vector,
  sets nothing;
- the answer is ``{(row, col): value}`` of the set cells whose row and
  column are both still there, by their current positions.
"""

from __future__ import annotations

from benchmark.refmerge_runs import _REM, _TXT, RunString, _visible

HANDLE_BASE = 0x100

_AXES = {"insertRows": (0, True), "insertCols": (1, True),
         "removeRows": (0, False), "removeCols": (1, False)}


class _Vector(RunString):
    def __init__(self):
        super().__init__()
        self.next = 0

    def insert_items(self, seq, ref, client, pos, count) -> None:
        text = "".join(chr(HANDLE_BASE + h)
                       for h in range(self.next, self.next + count))
        self.next += count
        self.insert(seq, ref, client, pos, text)

    def item_at(self, pos: int, ref: int, client: str):
        """(handle, removed now) of the item at ``pos`` in the (ref,
        client) perspective; None outside it."""
        if pos < 0:
            return None
        seen = 0
        for b in self.blocks:
            n = self._vis_len(b, ref, client)
            if seen + n <= pos:
                seen += n
                continue
            for r in b.runs:
                if not _visible(r, ref, client):
                    continue
                ln = len(r[_TXT])
                if seen + ln > pos:
                    return (ord(r[_TXT][pos - seen]) - HANDLE_BASE,
                            bool(r[_REM]))
                seen += ln
        return None

    def live(self) -> list[int]:
        """Handles of the items not removed, in order."""
        return [ord(ch) - HANDLE_BASE for b in self.blocks for r in b.runs
                if not r[_REM] for ch in r[_TXT]]


class RefGrid:
    def __init__(self):
        self.vectors = (_Vector(), _Vector())
        self.cells: dict = {}
        self.dead = 0

    def apply(self, seq: int, ref: int, client: str, op: dict) -> None:
        kind = op["op"]
        if kind == "setCell":
            rh = self.vectors[0].item_at(op["row"], ref, client)
            ch = self.vectors[1].item_at(op["col"], ref, client)
            if rh is None or ch is None or rh[1] or ch[1]:
                self.dead += 1
                return
            self.cells[(rh[0], ch[0])] = op["value"]
            return
        axis, insert = _AXES[kind]
        vec = self.vectors[axis]
        if insert:
            vec.insert_items(seq, ref, client, op["pos"], op["count"])
        else:
            vec.remove(seq, ref, client, op["pos"], op["pos"] + op["count"])

    def answer(self) -> dict:
        rows = {h: i for i, h in enumerate(self.vectors[0].live())}
        cols = {h: i for i, h in enumerate(self.vectors[1].live())}
        return {(rows[rh], cols[ch]): v for (rh, ch), v in self.cells.items()
                if rh in rows and ch in cols}


def reference_grid(ops) -> dict:
    """The cells after (seq, refSeq, client, op) tuples in seq order."""
    grid = RefGrid()
    last = 0
    for seq, ref, client, op in ops:
        if seq <= last:
            raise ValueError(f"seq {seq} after {last}: not in order")
        last = seq
        grid.apply(seq, ref, client, op)
    return grid.answer()
