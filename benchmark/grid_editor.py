"""The synthetic SharedMatrix editor that makes the grid cell's ops.

Wire ops of packages/dds/matrix (``dds/matrix.py``): ``setCell`` (row,
col, value) and ``insertRows``/``insertCols``/``removeRows``/
``removeCols`` (pos, count). The mix is the cell's (``grid_mix`` in its
traffic file); positions are uniform over the rows and columns this
client can see, and values are int32 from the seed. In a boxcar of more
than one op the setCells fill a range: each one after the first is the
next cell along its row (a paste or a fill).

The counts it keeps are lower bounds on what its perspective sees: its
own ops apply at once, another client's insert adds its count and a
remove takes its count off (two concurrent removes of one range take it
off twice), so every position it names exists in its perspective."""

from __future__ import annotations

import json
import zlib

#: a grid's SharedMatrix channel, under the text's data store
GRID_CHANNEL = "matrix"
KINDS = ("setCell", "insertRows", "insertCols", "removeRows", "removeCols")
_INT32 = 1 << 31


class GridEditor:
    def __init__(self, rng, mix: dict, max_count: int = 8):
        self.rng = rng
        self.rows = self.cols = 0
        self.max_count = max_count
        self.client_seq = 0
        self.ref_seq = 0
        total, self._cum = 0.0, []
        for kind in KINDS:
            total += mix.get(kind, 0.0)
            self._cum.append((total, kind))

    def _kind(self) -> str:
        r = self.rng.random() * self._cum[-1][0]
        return next(k for c, k in self._cum if r < c)

    def next_wire_ops(self, count: int) -> list[dict]:
        rng, out, cell = self.rng, [], None
        for _ in range(count):
            kind = self._kind()
            n = 1 + rng.randrange(self.max_count)
            size = self.rows if kind.endswith("Rows") else self.cols
            if kind.startswith("remove") and size - n < 1:
                kind = "setCell"
            if kind == "setCell" and (not self.rows or not self.cols):
                kind = "insertRows" if not self.rows else "insertCols"
            if kind == "setCell":
                if cell is None:
                    cell = [rng.randrange(self.rows), rng.randrange(self.cols)]
                elif cell[1] + 1 < self.cols:
                    cell[1] += 1
                else:
                    cell = [(cell[0] + 1) % self.rows, 0]
                out.append({"op": "setCell", "row": cell[0], "col": cell[1],
                            "value": rng.randrange(-_INT32, _INT32)})
                continue
            cell = None
            size = self.rows if kind.endswith("Rows") else self.cols
            if kind.startswith("insert"):
                op = {"op": kind, "pos": rng.randrange(size + 1), "count": n}
            else:
                op = {"op": kind, "pos": rng.randrange(size - n + 1),
                      "count": n}
            out.append(op)
            self.observe(op)
        return out

    def observe(self, op: dict) -> None:
        """A vector op (another client's, or this one's own as it makes
        it): the count it moves."""
        kind = op.get("op", "")
        if kind == "setCell" or kind not in KINDS:
            return
        delta = op["count"] if kind.startswith("insert") else -op["count"]
        if kind.endswith("Rows"):
            self.rows = max(0, self.rows + delta)
        else:
            self.cols = max(0, self.cols + delta)


def grid_digest(ops) -> int:
    """crc32 of a boxcar's grid wire ops, as sent and as read from the
    log."""
    return zlib.crc32(json.dumps(ops, sort_keys=True,
                                 separators=(",", ":")).encode())


def concurrent_stream(seed, mix: dict, n_clients: int = 4, n_ops: int = 300,
                      size: int = 16, lag: int = 3, max_box: int = 6,
                      max_count: int = 2) -> list:
    """Boxcars [(client, [(seq, ref, msn, op)])] of one grid, for the
    tests: client 0 inserts ``size`` rows and columns, then clients take
    seeded turns, each seeing the others' ops up to ``lag`` ops late, so
    that their ops are concurrent."""
    import random

    rng = random.Random(f"grid/{seed}")
    eds = [GridEditor(random.Random(f"{seed}/{c}"), mix, max_count)
           for c in range(n_clients)]
    first = [{"op": "insertRows", "pos": 0, "count": size},
             {"op": "insertCols", "pos": 0, "count": size}]
    for op in first:
        eds[0].observe(op)
    log = [(1, 0, first[0]), (2, 0, first[1])]
    out = [(0, [(1, 0, 0, first[0]), (2, 0, 0, first[1])])]
    seen, seq, n = [2] + [0] * (n_clients - 1), 2, 2
    while n < n_ops:
        c = rng.randrange(n_clients)
        upto = max(seen[c], len(log) - rng.randint(0, lag))
        for s, who, op in log[seen[c]:upto]:
            if who != c:
                eds[c].observe(op)
            eds[c].ref_seq = s
        seen[c] = upto
        ops = eds[c].next_wire_ops(rng.randint(1, max_box))
        ref, msn = eds[c].ref_seq, min(e.ref_seq for e in eds)
        box = []
        for op in ops:
            seq += 1
            log.append((seq, c, op))
            box.append((seq, ref, msn, op))
        n += len(ops)
        out.append((c, box))
    return out
