"""The long-document cell's load worker: ``loadgen.py``'s open-loop
worker (its ``Client``, its timing and its records) with one phase more,
before the warm-up, in which each long doc this worker builds is loaded
to its length through the served path.

``READY`` (all clients connected) <- ``LOAD`` -> ``LOADED <n>`` (n
appends not acked) <- ``WARM`` -> ``WARMED <n>`` <- ``GO <t0>`` ->
``DONE``. A long doc's loader is its first client: it appends
``load_append_chars`` characters at the doc's end, in boxcars of
``load_boxcar``, with at most ``load_window`` boxcars unacked. The
doc's other clients are connected while it loads, so their editors
observe every append and know the length it built; the loader's own
editor counts its appends. After the load every client of the doc edits
at positions drawn over the whole document.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import random
import time

from benchmark.editor import CHANNEL_ID, DS_ID
from benchmark.loadgen import Client, _line


def load_texts(seed, chars: int, n: int = 8) -> list[str]:
    """``n`` seeded texts of ``chars`` characters the appends draw from."""
    rng = random.Random(f"{seed}/texts")
    pool = "abcdefghijklmnopqrstuvwxyz "
    return ["".join(rng.choice(pool) for _ in range(chars))
            for _ in range(n)]


def send_ops(client: Client, ops: list, rec: dict) -> None:
    """``Client.send`` with the boxcar's ops given, not drawn."""
    from fluidframework_tpu.protocol import binwire
    from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                      MessageType)

    ed = client.editor
    base = ed.client_seq
    ed.client_seq = base + len(ops)
    msgs = [DocumentMessage(
        client_sequence_number=base + i + 1,
        reference_sequence_number=ed.ref_seq, type=MessageType.OPERATION,
        contents={"kind": "chanop", "address": DS_ID,
                  "contents": {"address": CHANNEL_ID, "contents": op}})
        for i, op in enumerate(ops)]
    body = binwire.encode_submit_columns(msgs)
    if body is None:
        body = binwire.encode_submit(msgs)
    rec["cseq0"], rec["cseq"] = base + 1, base + len(ops)
    rec["client_id"] = client.client_id
    client.pending[rec["cseq"]] = rec
    rec["sent"] = time.monotonic()
    client.writer.write(binwire.frame(body))


async def load_doc(client: Client, load: dict, texts: list, seed) -> list:
    """Append the doc's whole length; the boxcars' records."""
    rng = random.Random(f"{seed}/load/{client.gid}")
    n, chars, box = load["appends"], load["chars"], load["boxcar"]
    recs, pos = [], 0

    def unacked():
        return sum("ack" not in r and not r.get("nacked") for r in recs)

    for b0 in range(0, n, box):
        ops = []
        for _ in range(min(box, n - b0)):
            ops.append({"type": 0, "pos": pos,
                        "text": texts[rng.randrange(len(texts))]})
            pos += chars
        rec = {"size": len(ops), "load": True}
        send_ops(client, ops, rec)
        recs.append(rec)
        while unacked() >= load["window"]:
            await asyncio.sleep(0.002)
    client.editor.length += pos
    deadline = time.monotonic() + load["wait_s"]
    while unacked() and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    return recs


async def run(args, plan: dict):
    clients = {gid: Client(args.host, args.port, plan["tenant"], doc, gid,
                           plan["seed"])
               for gid, doc in plan["clients"]}
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
    texts = load_texts(plan["seed"], load["chars"])
    loaded = await asyncio.gather(*(
        load_doc(clients[gid], load, texts, plan["seed"])
        for gid in plan["loaders"]))
    bad = sum("ack" not in r for recs in loaded for r in recs)
    print(f"LOADED {bad}", flush=True)

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
    p = argparse.ArgumentParser(description="long-doc open-loop worker")
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
