"""Split a read-only stage's tail of the shared log into poll and drain.

The applier stage tails the core's log directory read-only: each turn
``DurableLog.poll`` refreshes every subscribed deltas topic and marks
the grown ones dirty, then ``DurableLog.drain`` reads and decodes their
new records into the subscribers. This builds that setting on the local
disk with no chip and no applier:

  - a writer ``DurableLog`` over N seg-lane ``deltas/`` topics with H
    records of history each, and a ``readonly=True`` view subscribing
    every topic from its end (a trivial handler, so the drain is the
    log's read and decode alone);
  - T turns, each appending R boxcars of 1-32 ops to random topics,
    flushing, then timing one poll and one drain of the view.

It prints one JSON line: poll ms (median, p90), drain ms a record
(median, p90), the host tail a turn, and the view's ``storage.*``
counter deltas over the turns. Usage:

    python tools/profile_stage_tail.py [--topics N] [--history H]
        [--records R] [--turns T] [--seed S] [--dir D]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _record(tenant: str, doc: str, base_seq: int, n: int, ts: float):
    from fluidframework_tpu.service.array_batch import (
        ArrayBoxcar,
        SequencedArrayBatch,
    )

    text = "x" * n
    box = ArrayBoxcar(
        tenant_id=tenant, document_id=doc, client_id="c0", ds_id="root",
        channel_id="text", kind=np.zeros(n, np.int8),
        a=np.arange(n, dtype=np.int32), b=np.zeros(n, np.int32),
        cseq=np.arange(1, n + 1, dtype=np.int32),
        rseq=np.full(n, max(0, base_seq - 1), np.int32), text=text,
        text_off=np.arange(n + 1, dtype=np.int32), props=None,
        timestamp=ts)
    return {"tenant_id": tenant, "document_id": doc,
            "abatch": SequencedArrayBatch(
                boxcar=box, base_seq=base_seq,
                msns=np.full(n, max(1, base_seq - 1), np.int64),
                timestamp=ts)}


def _pctl(xs: list, q: float) -> float:
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def profile(directory: str, topics: int, history: int, records: int,
            turns: int, seed: int) -> dict:
    from fluidframework_tpu.service.durable_log import DurableLog

    rng = random.Random(seed)
    writer = DurableLog(directory)
    seq = [1] * topics
    names = [f"deltas/t/doc{i:05d}" for i in range(topics)]

    def append(i: int) -> None:
        n = 1 if rng.random() < 0.75 else rng.randint(2, 32)
        writer.append(names[i], _record("t", f"doc{i:05d}", seq[i], n,
                                        time.time()))
        seq[i] += n

    for _ in range(history):
        for i in range(topics):
            append(i)
    writer.flush()

    view = DurableLog(directory, readonly=True)
    for name in names:
        # the drain reads and decodes each record before the handler
        view.subscribe(name, lambda message: None,
                       from_offset=view.length(name))
    view.poll()
    view.drain()

    def storage() -> dict:
        return {k: v for k, v in view.counters.snapshot().items()
                if k.startswith("storage.")}

    before = storage()
    poll_ms, drain_ms, tail_ms = [], [], []
    for _ in range(turns):
        for _ in range(records):
            append(rng.randrange(topics))
        writer.flush()
        t0 = time.perf_counter()
        view.poll()
        t1 = time.perf_counter()
        n = view.drain()
        t2 = time.perf_counter()
        poll_ms.append(1e3 * (t1 - t0))
        drain_ms.append(1e3 * (t2 - t1) / max(1, n))
        tail_ms.append(1e3 * (t2 - t0))
    after = storage()
    view.close()
    writer.close()
    return {
        "topics": topics, "history": history, "records_per_turn": records,
        "turns": turns,
        "poll_ms": [statistics.median(poll_ms), _pctl(poll_ms, 0.9)],
        "drain_ms_per_record": [statistics.median(drain_ms),
                                _pctl(drain_ms, 0.9)],
        "tail_ms_per_turn": [statistics.median(tail_ms),
                             _pctl(tail_ms, 0.9)],
        "storage": {k: after[k] - before.get(k, 0) for k in sorted(after)
                    if after[k] != before.get(k, 0)},
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--topics", type=int, default=1000)
    ap.add_argument("--history", type=int, default=30)
    ap.add_argument("--records", type=int, default=26)
    ap.add_argument("--turns", type=int, default=40)
    ap.add_argument("--seed", type=int, default=28)
    ap.add_argument("--dir", default=None,
                    help="log directory (default: a fresh temporary one, "
                         "removed after)")
    args = ap.parse_args()
    directory = args.dir or tempfile.mkdtemp(prefix="stage-tail-")
    try:
        print(json.dumps(profile(directory, args.topics, args.history,
                                 args.records, args.turns, args.seed)))
    finally:
        if args.dir is None:
            shutil.rmtree(directory, ignore_errors=True)


if __name__ == "__main__":
    main()
