"""The grid cell's load worker: ``loadgen.py``'s open-loop worker (its
``Client``, its timing and its records) for the text docs' clients, and
``GridClient`` for the grids' clients, with one phase more, before the
warm-up, in which each grid this worker serves is made through the
served path.

``READY`` (all clients connected) <- ``LOAD`` -> ``LOADED <n>`` (n
loads not acked, or grids whose clients did not see their load) <-
``WARM`` -> ``WARMED <n>`` <- ``GO <t0>`` -> ``DONE``. A grid's loader
is its first client: one boxcar of the SharedMatrix channel's attach,
``insertRows(0, rows)`` and ``insertCols(0, cols)``. Every client of a
grid is in the same worker, which waits until each has seen the load.
A grid client's boxcars are ``GridEditor`` ops (``grid_editor.py``);
it follows the others' row and column counts by decoding the broadcast
ops of its grid.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import random
import time

from benchmark.editor import DS_ID
from benchmark.grid_editor import GRID_CHANNEL, GridEditor, grid_digest
from benchmark.loadgen import Client, _line


def grid_envelope(contents: dict) -> dict:
    return {"kind": "chanop", "address": DS_ID,
            "contents": {"address": GRID_CHANNEL, **contents}}


class GridClient(Client):
    def __init__(self, host, port, tenant, doc, gid, seed, traffic):
        super().__init__(host, port, tenant, doc, gid, seed)
        self.editor = GridEditor(random.Random(f"{seed}/{gid}"),
                                 traffic["grid_mix"],
                                 traffic["grid_max_count"])

    def _observe(self, body: bytes) -> None:
        from fluidframework_tpu.protocol import binwire

        me, ed = self.client_id, self.editor
        _topic, msgs = binwire.decode_ops(body)
        for m in msgs:
            ed.ref_seq = m.sequence_number
            if m.client_id is None or me is None:
                continue
            if m.client_id == me:
                rec = self.pending.pop(m.client_sequence_number, None)
                if rec is not None:
                    rec["ack"] = time.monotonic()
                    rec["seq"] = m.sequence_number
                    rec["deli_wall"] = next(
                        (t.timestamp for t in reversed(m.traces or ())
                         if t.service == "deli"), None)
                continue
            env = m.contents
            if type(env) is dict and env.get("kind") == "chanop":
                inner = env.get("contents") or {}
                if inner.get("address") == GRID_CHANNEL \
                        and "contents" in inner:
                    ed.observe(inner["contents"])

    def send_wire(self, ops: list, rec: dict, envelopes=None) -> None:
        """Send ``ops`` (grid wire ops, or whole envelopes) as one
        boxcar."""
        from fluidframework_tpu.protocol import binwire
        from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                          MessageType)

        ed = self.editor
        envs = envelopes or [grid_envelope({"contents": op}) for op in ops]
        base = ed.client_seq
        ed.client_seq = base + len(envs)
        msgs = [DocumentMessage(
            client_sequence_number=base + i + 1,
            reference_sequence_number=ed.ref_seq,
            type=MessageType.OPERATION, contents=env)
            for i, env in enumerate(envs)]
        rec["cseq0"], rec["cseq"] = base + 1, base + len(msgs)
        rec["digest"] = grid_digest(ops)
        rec["client_id"] = self.client_id
        self.pending[rec["cseq"]] = rec
        rec["sent"] = time.monotonic()
        rec["sent_wall"] = time.time()
        self.writer.write(binwire.frame(binwire.encode_submit(msgs)))

    def send(self, rec: dict) -> None:
        self.send_wire(self.editor.next_wire_ops(rec["size"]), rec)


async def load_grid(client: GridClient, load: dict) -> dict:
    """Attach the grid and insert its rows and columns: one boxcar."""
    ops = [{"op": "insertRows", "pos": 0, "count": load["rows"]},
           {"op": "insertCols", "pos": 0, "count": load["cols"]}]
    envs = [grid_envelope({"attach": {"type": "shared-matrix"}})]
    envs += [grid_envelope({"contents": op}) for op in ops]
    for op in ops:
        client.editor.observe(op)
    rec = {"size": len(envs), "load": True}
    client.send_wire(ops, rec, envelopes=envs)
    deadline = time.monotonic() + load["wait_s"]
    while "ack" not in rec and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    return rec


async def run(args, plan: dict):
    tr = plan["traffic"]
    clients = {}
    for gid, doc, kind in plan["clients"]:
        clients[gid] = (GridClient(args.host, args.port, plan["tenant"], doc,
                                   gid, plan["seed"], tr) if kind == "grid"
                        else Client(args.host, args.port, plan["tenant"], doc,
                                    gid, plan["seed"]))
    sem = asyncio.Semaphore(plan.get("connect_concurrency", 64))

    async def staged(c):
        async with sem:
            await c.connect()

    await asyncio.gather(*(staged(c) for c in clients.values()))
    readers = [asyncio.ensure_future(c.read_loop())
               for c in clients.values()]
    print("READY", flush=True)

    assert await _line() == "LOAD"
    load = plan["load"]
    loaded = await asyncio.gather(*(load_grid(clients[gid], load)
                                    for gid in plan["loaders"]))
    grid_clients = [c for c in clients.values()
                    if isinstance(c, GridClient)]
    deadline = time.monotonic() + load["wait_s"]
    while time.monotonic() < deadline and any(
            c.editor.rows < load["rows"] or c.editor.cols < load["cols"]
            for c in grid_clients):
        await asyncio.sleep(0.01)
    unseen = sum(c.editor.rows < load["rows"] or c.editor.cols < load["cols"]
                 for c in grid_clients)
    print(f"LOADED {sum('ack' not in r for r in loaded) + unseen}",
          flush=True)

    assert await _line() == "WARM"
    warm = []
    for c in clients.values():
        rec = {"size": 1, "due": time.monotonic(), "warm": True}
        c.send(rec)
        warm.append(rec)
    deadline = time.monotonic() + plan["ack_wait_s"]
    while time.monotonic() < deadline and any(
            "ack" not in r and not r.get("nacked") for r in warm):
        await asyncio.sleep(0.01)
    print(f"WARMED {sum('ack' not in r for r in warm)}", flush=True)

    cmd = await _line()
    assert cmd.startswith("GO "), cmd
    t0 = float(cmd.split()[1])
    records = []
    for idx, gid, size, due in plan["schedule"]:
        t = t0 + due
        delay = t - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = {"idx": idx, "gid": gid, "size": size, "due": t}
        clients[gid].send(rec)
        records.append(rec)
    deadline = t0 + plan["seconds"] + plan["ack_wait_s"]
    while time.monotonic() < deadline and any(
            "ack" not in r and not r.get("nacked") for r in records):
        await asyncio.sleep(0.01)
    errors = [c.error for c in clients.values() if c.error]
    for r in readers:
        r.cancel()
    for c in clients.values():
        c.close()
    return records, errors


def main() -> None:
    p = argparse.ArgumentParser(description="grid cell open-loop worker")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--plan", required=True, help="this worker's schedule")
    p.add_argument("--out", required=True, help="where records go")
    args = p.parse_args()
    with open(args.plan) as f:
        plan = json.load(f)
    gc.collect()
    gc.freeze()
    gc.disable()
    records, errors = asyncio.run(run(args, plan))
    with open(args.out, "w") as f:
        json.dump({"records": records, "errors": errors}, f)
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
