"""The plain reference over runs of characters: the semantics of
``refmerge.py`` (read its docstring), at a cost a book-length document
can pay.

It imports nothing of the program. A run is characters that share one
insert (seq, client) and one remove state ([text, ins_seq, ins_client,
rem_seq or 0, removers]); an op splits runs at its boundaries, so every
run is wholly visible or wholly hidden in any perspective. Runs sit in
blocks of about ``BLOCK`` runs, and a block keeps the highest seq of any
insert or remove in it and its length without removed characters. In a
perspective whose refSeq is at least that seq, every insert in the
block is seen and every remove is final, whoever the client: the block
counts as that length without a look inside. A character-at-a-time
replay of a 700,000-character document would take of the order of 10^9
Python steps; this one looks into the blocks an op's perspective cannot
settle and the one its position lands in.
"""

from __future__ import annotations

BLOCK = 64
_TXT, _INS, _CLI, _REM, _RMV = range(5)


class _Block:
    __slots__ = ("runs", "max_seq", "settled")

    def __init__(self, runs):
        self.runs = runs
        self.refresh()

    def refresh(self) -> None:
        self.max_seq = max((max(r[_INS], r[_REM]) for r in self.runs),
                           default=0)
        self.settled = sum(len(r[_TXT]) for r in self.runs if not r[_REM])


def _visible(r, ref: int, client: str) -> bool:
    if r[_CLI] != client and r[_INS] > ref:
        return False
    return not r[_REM] or (client not in r[_RMV] and r[_REM] > ref)


class RunString:
    def __init__(self):
        self.blocks: list[_Block] = []

    def _vis_len(self, b: _Block, ref: int, client: str) -> int:
        if b.max_seq <= ref:
            return b.settled
        return sum(len(r[_TXT]) for r in b.runs if _visible(r, ref, client))

    def _split_block(self, bi: int) -> None:
        b = self.blocks[bi]
        if len(b.runs) > 2 * BLOCK:
            half = len(b.runs) // 2
            self.blocks[bi:bi + 1] = [_Block(b.runs[:half]),
                                      _Block(b.runs[half:])]

    def insert(self, seq: int, ref: int, client: str, pos: int,
               text: str) -> None:
        new = [text, seq, client, 0, None]
        if pos == 0:
            # the earliest boundary with no visible character before it
            if not self.blocks:
                self.blocks.append(_Block([]))
            self.blocks[0].runs.insert(0, new)
            self.blocks[0].refresh()
            self._split_block(0)
            return
        seen = 0
        for bi, b in enumerate(self.blocks):
            n = self._vis_len(b, ref, client)
            if seen + n < pos:
                seen += n
                continue
            for ri, r in enumerate(b.runs):
                if not _visible(r, ref, client):
                    continue
                ln = len(r[_TXT])
                if seen + ln < pos:
                    seen += ln
                    continue
                cut = pos - seen  # 1..ln: right after the pos-th character
                if cut < ln:
                    tail = [r[_TXT][cut:], r[_INS], r[_CLI], r[_REM],
                            set(r[_RMV]) if r[_RMV] else r[_RMV]]
                    r[_TXT] = r[_TXT][:cut]
                    b.runs[ri + 1:ri + 1] = [new, tail]
                else:
                    b.runs.insert(ri + 1, new)
                b.refresh()
                self._split_block(bi)
                return
            raise AssertionError("a block's visible length was miscounted")
        raise ValueError(f"insert at {pos} past the end ({seen})")

    def remove(self, seq: int, ref: int, client: str, start: int,
               end: int) -> None:
        seen, touched = 0, []
        for bi, b in enumerate(self.blocks):
            if seen >= end:
                break
            n = self._vis_len(b, ref, client)
            if seen + n <= start:
                seen += n
                continue
            out = []
            for r in b.runs:
                if not _visible(r, ref, client):
                    out.append(r)
                    continue
                ln = len(r[_TXT])
                lo, hi = max(start - seen, 0), min(end - seen, ln)
                seen += ln
                if hi <= lo:
                    out.append(r)
                    continue
                for a, z, hit in ((0, lo, False), (lo, hi, True),
                                  (hi, ln, False)):
                    if z <= a:
                        continue
                    piece = [r[_TXT][a:z], r[_INS], r[_CLI], r[_REM],
                             set(r[_RMV]) if r[_RMV] else r[_RMV]]
                    if hit:
                        if not piece[_REM]:
                            piece[_REM], piece[_RMV] = seq, {client}
                        else:
                            piece[_RMV].add(client)
                    out.append(piece)
            b.runs = out
            b.refresh()
            touched.append(bi)
        for bi in reversed(touched):
            self._split_block(bi)
        if seen < end:
            raise ValueError(f"remove {start}..{end} past the end ({seen})")

    def apply(self, seq: int, ref: int, client: str, op: dict) -> None:
        t = op["type"]
        if t == 0:
            if "marker" in op:
                raise ValueError("the reference models text inserts only")
            text = op.get("text") or ""
            if text:
                self.insert(seq, ref, client, op["pos"], text)
        elif t == 1:
            self.remove(seq, ref, client, op["start"], op["end"])
        elif t != 2:
            raise ValueError(f"op type {t} is not modelled")

    def text(self) -> str:
        return "".join(r[_TXT] for b in self.blocks for r in b.runs
                       if not r[_REM])


def reference_text(ops) -> str:
    """The text after (seq, refSeq, client, op) tuples in seq order."""
    doc = RunString()
    last = 0
    for seq, ref, client, op in ops:
        if seq <= last:
            raise ValueError(f"seq {seq} after {last}: not in order")
        last = seq
        doc.apply(seq, ref, client, op)
    return doc.text()
