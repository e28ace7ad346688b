"""A read-only DurableLog's tail of a shared segment-stream directory.

A consumer process (the applier and scribe stages) opens the producer's
log directory read-only, polls it for grown topics and drains their new
records into its subscribers. Its seg-lane topics refresh in one native
call over every subscribed stream, and their new blocks come out of the
native index in one batch read per topic — no SegmentReader. These
tests pin that tail against the writer's own records and against
SegmentReader's admission rule:

- a poll marks dirty exactly the topics that grew, and the
  ``storage.tail.*`` counters agree;
- a torn tail is never delivered before its bytes land;
- reads cross segment rolls; SEG_COLS and SEG_JSON blocks decode to the
  same values as through SegmentReader;
- the ``log.poll`` rewind redelivers, a pre-segment producer still
  reroutes to the record lane, and the native fd cap holds;
- no SegmentReader is built across a stage-like tail's turns.
"""

from __future__ import annotations

import os
import random
import struct

import numpy as np
import pytest

from fluidframework_tpu.protocol import binwire
from fluidframework_tpu.protocol.messages import (
    MessageType,
    SequencedDocumentMessage,
)
from fluidframework_tpu.service.array_batch import (
    ArrayBoxcar,
    SequencedArrayBatch,
)
from fluidframework_tpu.service.durable_log import DurableLog, _sanitize
from fluidframework_tpu.service.segment_store import SegmentReader

N_TOPICS = 300


def _record(base_seq, n=3, tenant="t", doc="d0", ts=100.0):
    text = "ab" * n
    box = ArrayBoxcar(
        tenant_id=tenant, document_id=doc, client_id="c1", ds_id="root",
        channel_id="seq", kind=np.zeros(n, np.int8),
        a=np.arange(n, dtype=np.int32), b=np.zeros(n, np.int32),
        cseq=np.arange(1, n + 1, dtype=np.int32),
        rseq=np.zeros(n, np.int32), text=text,
        text_off=np.arange(0, 2 * n + 1, 2, dtype=np.int32),
        props=None, timestamp=ts)
    return {"tenant_id": tenant, "document_id": doc,
            "abatch": SequencedArrayBatch(
                boxcar=box, base_seq=base_seq,
                msns=np.arange(base_seq, base_seq + n, dtype=np.int64),
                timestamp=ts)}


def _topic(i):
    return f"deltas/t/d{i}"


def _storage(log):
    return {k: v for k, v in log.counters.snapshot().items()
            if k.startswith("storage.")}


def _delta(before, after, key):
    return after.get(key, 0) - before.get(key, 0)


class _Farm:
    """A writer over ``n`` deltas topics and a read-only view that
    subscribes every topic and records each delivery's base seq."""

    def __init__(self, d, n=N_TOPICS, fd_cap=None, segment_bytes=None):
        self.writer = DurableLog(d, segment_bytes=segment_bytes)
        self.seq = [1] * n
        for i in range(n):
            self.append(i)
        self.writer.flush()
        self.reader = DurableLog(d, readonly=True, fd_cap=fd_cap)
        self.got = {i: [] for i in range(n)}
        for i in range(n):
            self.reader.subscribe(
                _topic(i),
                lambda m, i=i: self.got[i].append(
                    m.value["abatch"].base_seq))

    def append(self, i, n_ops=3):
        self.writer.append(_topic(i), _record(self.seq[i], n=n_ops,
                                              doc=f"d{i}"))
        self.seq[i] += n_ops

    def want(self, i):
        return list(range(1, self.seq[i], 3))

    def turn(self):
        moved = self.reader.poll()
        if moved:
            self.reader.drain()
        return moved

    def close(self):
        self.reader.close()
        self.writer.close()


def test_poll_marks_exactly_the_grown_topics(tmp_path):
    farm = _Farm(str(tmp_path))
    r = farm.reader
    assert farm.turn()
    assert all(farm.got[i] == [1] for i in range(N_TOPICS))
    rng = random.Random(7)
    for _ in range(5):
        grown = set(rng.sample(range(N_TOPICS), 23))
        for i in grown:
            farm.append(i)
        farm.writer.flush()
        before = _storage(r)
        assert r.poll()
        assert set(r._dirty) == {_topic(i) for i in grown}
        after = _storage(r)
        assert _delta(before, after, "storage.tail.streams") == N_TOPICS
        assert _delta(before, after, "storage.tail.grown") == len(grown)
        assert r.drain() == len(grown)
        assert _delta(after, _storage(r), "storage.tail.blocks") \
            == len(grown)
    assert not r.poll() and not r._dirty
    assert all(farm.got[i] == farm.want(i) for i in range(N_TOPICS))
    farm.close()


def _segment_files(d, stream):
    return sorted(f for f in os.listdir(d)
                  if f.startswith(stream + ".seg") and f[-1].isdigit())


@pytest.mark.parametrize("tear", ["half_block", "half_entry",
                                  "entry_before_bytes"])
def test_torn_tail_waits_for_its_bytes(tmp_path, tear):
    """A torn tail is delivered only once its bytes have landed, and the
    tail admits exactly what SegmentReader.refresh admits."""
    d = str(tmp_path)
    topic = "deltas/t/d0"
    stream = _sanitize(topic)
    writer = DurableLog(d)
    for k in range(3):
        writer.append(topic, _record(1 + 3 * k))
    writer.flush()
    reader = DurableLog(d, readonly=True)
    got = []
    reader.subscribe(topic, lambda m: got.append(m.value["abatch"]
                                                 .base_seq))
    reader.poll()
    reader.drain()
    assert got == [1, 4, 7]

    block, first, last, btype = writer._seg_encode(topic, _record(10))
    if tear == "entry_before_bytes":
        # the index entry lands before the block bytes it points at
        writer.close()
        seg = _segment_files(d, stream)[-1]
        seg_path = os.path.join(d, seg)
        end = os.path.getsize(seg_path)
        with open(os.path.join(d, stream + ".segidx"), "ab") as f:
            f.write(struct.pack("<qqIIII", first, last,
                                int(seg[len(stream) + 4:]), end,
                                len(block), btype))
    else:
        writer._log.seg_tear(stream, first, last, block, btype,
                             mode=0 if tear == "half_block" else 1)
    assert not reader.poll()
    assert reader.drain() == 0
    assert got == [1, 4, 7]
    assert reader.length(topic) == 3
    assert SegmentReader(d, stream).refresh() == 3

    if tear == "entry_before_bytes":
        with open(seg_path, "ab") as f:
            f.write(block[:len(block) // 2])
        assert not reader.poll()
        assert SegmentReader(d, stream).refresh() == 3
        with open(seg_path, "ab") as f:
            f.write(block[len(block) // 2:])
    else:
        writer.append(topic, _record(10))  # cuts the residue, then lands
        writer.flush()
    assert reader.poll()
    reader.drain()
    assert got == [1, 4, 7, 10]
    assert SegmentReader(d, stream).refresh() == reader.length(topic) == 4
    reader.close()
    if tear != "entry_before_bytes":
        writer.close()


def test_reads_cross_segment_rolls(tmp_path):
    """A 256-byte roll puts about every block in a segment of its own;
    one batch read walks them all."""
    d = str(tmp_path)
    farm = _Farm(d, n=4, segment_bytes=256)
    for _ in range(12):
        for i in range(4):
            farm.append(i, n_ops=3)
    farm.writer.flush()
    assert len(_segment_files(d, _sanitize(_topic(0)))) > 6
    before = _storage(farm.reader)
    farm.turn()
    assert all(farm.got[i] == farm.want(i) for i in range(4))
    # one batch read a topic
    assert _delta(before, _storage(farm.reader), "storage.tail.blocks") \
        == 4 * 13
    farm.close()


def test_batch_read_decodes_as_segment_reader(tmp_path):
    d = str(tmp_path)
    topic = "deltas/t/d0"
    writer = DurableLog(d, segment_bytes=512)
    legacy = SequencedDocumentMessage(
        client_id="c9", sequence_number=4, minimum_sequence_number=1,
        client_sequence_number=1, reference_sequence_number=1,
        type=MessageType.OPERATION, contents={"x": 1}, timestamp=5.0)
    records = [_record(1), {"weird": "record"},
               {"tenant_id": "t", "document_id": "d0", "message": legacy},
               _record(5, n=2), _record(7, n=17)]
    for rec in records:
        writer.append(topic, rec)
    writer.flush()
    reader = DurableLog(d, readonly=True)
    tail = [reader.read(topic, i) for i in range(len(records))]
    seg = SegmentReader(d, _sanitize(topic))
    seg.refresh()
    btypes = []
    for i, value in enumerate(tail):
        btype, _, _, payload = seg.block(i)
        btypes.append(btype)
        # the reader's value re-encodes to the very block on disk
        assert writer._seg_encode(topic, value)[0] == payload
        if btype == binwire.SEG_COLS:
            want = writer._seg_decode(topic, payload)["abatch"]
            got = value["abatch"]
            assert got.base_seq == want.base_seq
            assert got.msns.tolist() == want.msns.tolist()
            assert got.messages() == want.messages()
    assert btypes == [binwire.SEG_COLS, binwire.SEG_JSON, binwire.SEG_JSON,
                      binwire.SEG_COLS, binwire.SEG_COLS]
    assert tail[1] == {"weird": "record"}
    assert tail[2]["message"] == legacy
    # random access, backwards, through the same cache
    assert reader.read(topic, 0)["abatch"].base_seq == 1
    assert reader.read(topic, 3)["abatch"].base_seq == 5
    with pytest.raises(IndexError):
        reader.read(topic, len(records))
    seg.close()
    reader.close()
    writer.close()


def test_poll_rewind_redelivers(tmp_path):
    farm = _Farm(str(tmp_path), n=20)
    farm.turn()
    for i in range(20):
        farm.append(i)
    farm.writer.flush()
    farm.turn()
    pending = ["rewind"]
    farm.reader.fault_plane = (
        lambda point, **ctx: pending.pop()
        if point == "log.poll" and pending else None)
    assert farm.turn()  # nothing grew: the rewind alone is the work
    assert all(farm.got[i] == [1, 4, 4] for i in range(20))
    farm.close()


def test_pre_segment_producer_reroutes(tmp_path):
    """A topic subscribed before its producer wrote anything, whose
    producer then writes the record lane (a pre-segment directory)."""
    d = str(tmp_path)
    topic = "deltas/t/old"
    reader = DurableLog(d, readonly=True)
    got = []
    reader.subscribe(topic, lambda m: got.append(m.value["abatch"]
                                                 .base_seq))
    assert not reader.poll()
    producer = DurableLog(d, segmented=False)
    producer.append(topic, _record(1, doc="old"))
    producer.append(topic, _record(4, doc="old"))
    producer.flush()
    assert not any(f.endswith(".segidx") for f in os.listdir(d))
    assert reader.poll()
    reader.drain()
    assert got == [1, 4]
    assert reader._seg_stream(topic) is None
    producer.append(topic, _record(7, doc="old"))
    producer.flush()
    assert reader.poll()
    reader.drain()
    assert got == [1, 4, 7]
    reader.close()
    producer.close()


def test_fd_cap_holds_over_300_topics(tmp_path):
    farm = _Farm(str(tmp_path), fd_cap=64)
    r = farm.reader
    rng = random.Random(11)
    for _ in range(6):
        for i in rng.sample(range(N_TOPICS), 120):
            farm.append(i)
        farm.writer.flush()
        r.poll()
        assert r._log.open_files() <= 64
        r.drain()
        assert r._log.open_files() <= 64
    assert all(farm.got[i] == farm.want(i) for i in range(N_TOPICS))
    farm.close()


def test_stage_tail_builds_no_segment_reader(tmp_path):
    """Twenty poll/drain turns of a read-only stage tail over 300 topics:
    every record delivered, no SegmentReader built, one stream checked
    per subscribed topic per poll."""
    farm = _Farm(str(tmp_path))
    before = _storage(farm.reader)
    rng = random.Random(3)
    appended = 0
    for _ in range(20):
        for i in rng.sample(range(N_TOPICS), 26):
            farm.append(i)
            appended += 1
        farm.writer.flush()
        assert farm.turn()
    after = _storage(farm.reader)
    assert _delta(before, after, "storage.segment.reader_builds") == 0
    assert _delta(before, after, "storage.tail.streams") == 20 * N_TOPICS
    assert _delta(before, after, "storage.tail.blocks") \
        == N_TOPICS + appended
    assert all(farm.got[i] == farm.want(i) for i in range(N_TOPICS))
    farm.close()

