"""The applier's long tier at a small size on the CPU: a farm of S = 16
slots (K = 4, so a doc moves above 8) beside 8 long rows of 256.

- seeded multi-client streams from the benchmark's editor grow some docs
  past S: they move to the tier on the device (no host replay), and
  every doc's device text equals the scalar oracle's (mergetree/) and
  benchmark/refmerge.py's;
- an op past character 32,767 moves its doc, and the farm's wave stays
  int16;
- base, deltas and a restart round-trip with promoted docs, and the
  restarted applier goes on applying on both tiers;
- ``answers.device_texts`` still reads a farm-only checkpoint, and a
  tiered one's farm docs, as before;
- a long row past S_long escalates to the host, and the text holds;
- after ``warm_farm`` and ``warm_long_tier`` a promotion and both
  tiers' waves compile nothing.
"""

import random

import numpy as np
import pytest

from benchmark import answers
from benchmark.editor import Editor
from benchmark.refmerge import reference_text
from fluidframework_tpu.mergetree.client import MergeTreeClient
from fluidframework_tpu.protocol.messages import (MessageType,
                                                  SequencedDocumentMessage)
from fluidframework_tpu.service.tpu_applier import (
    ApplierCheckpointer, TpuDocumentApplier, load_applier_checkpoint,
    save_applier_checkpoint)

SEEDS = [0, 7, 42]
GEOMETRY = dict(max_docs=8, max_slots=16, ops_per_dispatch=4)
TIER = dict(long_docs=8, long_slots=256)
FIELDS = ("length", "text_start", "flags", "ins_seq", "ins_client",
          "rem_seq", "rem_client_a", "rem_client_b", "prop_key", "prop_val",
          "count", "overflow")


def editor_stream(seed, n_clients, n_boxcars, lag=3, max_box=6):
    """Boxcars [(client, [(seq, ref, msn, op)])] of one doc: clients take
    seeded turns, each seeing the others' ops up to ``lag`` boxcars late
    (so ops are concurrent), as the benchmark's socket clients do."""
    rng = random.Random(f"stream/{seed}")
    eds = [Editor(random.Random(f"{seed}/{c}")) for c in range(n_clients)]
    seen = [0] * n_clients
    log, out, seq = [], [], 0  # log: (seq, client, delta)
    for _ in range(n_boxcars):
        c = rng.randrange(n_clients)
        upto = max(seen[c], len(log) - rng.randint(0, lag))
        for s, who, delta in log[seen[c]:upto]:
            if who != c:
                eds[c].observe_delta(delta)
            eds[c].ref_seq = s
        seen[c] = upto
        ops = eds[c].next_wire_ops(rng.randint(1, max_box))
        ref = eds[c].ref_seq
        msn = min(e.ref_seq for e in eds)
        box = []
        for op in ops:
            seq += 1
            delta = (len(op["text"]) if op["type"] == 0 else
                     op["start"] - op["end"] if op["type"] == 1 else 0)
            log.append((seq, c, delta))
            box.append((seq, ref, msn, op))
        out.append((c, box))
    return out


def _msg(client, seq, ref, msn, op):
    return SequencedDocumentMessage(
        client_id=f"c{client}", sequence_number=seq,
        minimum_sequence_number=msn, client_sequence_number=seq,
        reference_sequence_number=ref, type=MessageType.OPERATION,
        contents=op)


def _oracle_text(boxes):
    oracle = MergeTreeClient("oracle")
    for c, box in boxes:
        for seq, ref, msn, op in box:
            oracle.apply_msg(_msg(c, seq, ref, msn, op), local=False)
    return oracle.get_text()


def _reference(boxes):
    return reference_text((seq, ref, f"c{c}", op)
                          for c, box in boxes for seq, ref, _m, op in box)


def _docs(seed):
    """Six docs: three long-running (they outgrow the farm), three short."""
    return {f"d{i}": editor_stream(seed * 10 + i, 3,
                                   40 if i < 3 else 2) for i in range(6)}


def _feed(applier, docs, turns=12, upto=None):
    """Ingest the docs' boxcars in ``turns`` slices, flushing each slice
    and fencing every third (the stage's turn and checkpoint)."""
    applier.set_replay_source(lambda t, d: [
        _msg(c, *x) for c, box in docs[d] for x in box])
    for t in range(turns):
        for d, boxes in docs.items():
            n = len(boxes) if upto is None else upto
            for c, box in boxes[n * t // turns:n * (t + 1) // turns]:
                applier.ingest_batch("t", d, [(_msg(c, *x), x[3])
                                              for x in box])
        applier.flush()
        if t % 3 == 2:
            applier.finalize()
    applier.finalize()


@pytest.mark.parametrize("seed", SEEDS)
def test_docs_past_the_farm_move_to_the_tier(seed):
    docs = _docs(seed)
    a = TpuDocumentApplier(**GEOMETRY, **TIER)
    _feed(a, docs)
    assert a.host_escalations == 0
    on_tier = {a._doc_keys[s][1] for s in a._long_rows}
    assert on_tier >= {"d0", "d1", "d2"} and a.promotions == len(on_tier)
    assert a.long_dispatches > 0
    assert 16 < a.long_slots_peak <= 256
    for d, boxes in docs.items():
        want = _reference(boxes)
        assert want == _oracle_text(boxes), d
        assert a.get_text("t", d) == want, d


def test_without_the_tier_the_same_docs_escalate():
    a = TpuDocumentApplier(**GEOMETRY)
    _feed(a, _docs(0))
    assert a.long_state is None and a.promotions == 0
    assert a.host_escalations >= 1


def test_a_long_edit_past_int16_keeps_the_farm_wave_int16(monkeypatch):
    """A doc appended to past character 32,767 moves to the tier at its
    first such op, whatever its slot count, and the farm's waves, with a
    short doc edited in the same waves, all stay on the int16 lane."""
    a = TpuDocumentApplier(max_docs=8, max_slots=64, ops_per_dispatch=4,
                           **TIER)
    widths = []
    orig = TpuDocumentApplier._execute_wave

    def spy(self, staged):
        widths.append(staged.wide)
        return orig(self, staged)

    monkeypatch.setattr(TpuDocumentApplier, "_execute_wave", spy)
    chunk = "abcdefgh" * 512  # 4,096 characters
    seq, long_ops, short_ops = 0, [], []
    for i in range(12):
        seq += 1
        op = {"type": 0, "pos": i * len(chunk), "text": chunk}
        long_ops.append((seq, op))
        a.ingest("t", "book", _msg(0, seq, seq - 1, seq - 1, op), op)
        seq += 1
        op = {"type": 0, "pos": 0, "text": f"{i}"}
        short_ops.append((seq, op))
        a.ingest("t", "note", _msg(1, seq, seq - 1, seq - 1, op), op)
        a.flush()
    seq += 1
    op = {"type": 1, "start": 40_000, "end": 40_003}
    long_ops.append((seq, op))
    a.ingest("t", "book", _msg(0, seq, seq - 1, seq - 1, op), op)
    a.finalize()
    assert widths and not any(widths)
    assert a.promotions == 1 and a.host_escalations == 0
    assert a._doc_keys[next(iter(a._long_rows))] == ("t", "book")
    book = chunk * 12
    assert a.get_text("t", "book") == book[:40_000] + book[40_003:]
    assert a.get_text("t", "note") == "".join(
        f"{i}" for i in reversed(range(12)))


def _same_tiers(a, b):
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a.state, f)),
                                      np.asarray(getattr(b.state, f)), f)
        np.testing.assert_array_equal(np.asarray(getattr(a.long_state, f)),
                                      np.asarray(getattr(b.long_state, f)),
                                      f)
    for attr in ("_long_rows", "_doc_keys", "_client_ids", "_applied_seq",
                 "_anchored"):
        assert getattr(a, attr) == getattr(b, attr), attr
    assert sorted(a._long_free) == sorted(b._long_free)
    assert [x.text() for x in a.arenas] == [x.text() for x in b.arenas]


@pytest.mark.parametrize("seed", SEEDS)
def test_base_deltas_and_restart_round_trip(tmp_path, seed):
    """A base, a delta a turn while docs move to the tier, a reload: the
    revived applier equals a full save of the live one on both tiers,
    and, fed the rest of the streams, ends where the live one ends."""
    docs = _docs(seed)
    live = TpuDocumentApplier(**GEOMETRY, **TIER)
    path = str(tmp_path / "ck")
    ck = ApplierCheckpointer(live, path)
    ck.limit = 1 << 60  # every delta stays on disk
    assert ck.base().long_bytes == ck.long_raw > 0
    half = {d: boxes[:len(boxes) // 2] for d, boxes in docs.items()}
    long_bytes = 0
    for t in range(4):
        part = {d: b[len(b) * t // 4:len(b) * (t + 1) // 4]
                for d, b in half.items()}
        _feed(live, part, turns=1)
        long_bytes += ck.delta().long_bytes
    assert live.promotions >= 1 and long_bytes > 0
    revived = load_applier_checkpoint(path, ops_per_dispatch=4)
    save_applier_checkpoint(live, str(tmp_path / "full"))
    full = load_applier_checkpoint(str(tmp_path / "full"),
                                   ops_per_dispatch=4)
    _same_tiers(revived, full)
    _same_tiers(revived, live)
    rest = {d: boxes[len(boxes) // 2:] for d, boxes in docs.items()}
    for app in (live, revived):
        _feed(app, rest, turns=3)
    _same_tiers(revived, live)
    assert revived.host_escalations == 0 and live.promotions >= 3
    for d, boxes in docs.items():
        assert revived.get_text("t", d) == _reference(boxes), d


def test_device_texts_reads_farm_docs_as_before(tmp_path):
    """answers.device_texts reads a farm-only checkpoint as it always
    has, and a tiered one's farm docs alone: a promoted doc is off the
    farm's doc_keys."""
    docs = _docs(7)
    farm_only = dict(max_docs=8, max_slots=256, ops_per_dispatch=4)
    for kw, path in ((farm_only, "farm"), ({**GEOMETRY, **TIER}, "tiered")):
        a = TpuDocumentApplier(**kw)
        _feed(a, docs)
        save_applier_checkpoint(a, str(tmp_path / path))
        texts, host = answers.device_texts(str(tmp_path / path))
        assert host == 0
        farm = {("t", d) for d in docs} - {a._doc_keys[s]
                                           for s in a._long_rows}
        assert set(texts) == farm and (path == "farm") == (len(farm) == 6)
        for key in farm:
            assert texts[key] == a.get_text(*key), key


def test_a_long_row_past_its_slots_escalates_to_the_host():
    docs = {"d0": editor_stream(5, 3, 60)}
    a = TpuDocumentApplier(**GEOMETRY, long_docs=8, long_slots=32)
    _feed(a, docs)
    assert a.promotions == 1 and a.host_escalations == 1
    assert not a._long_rows and len(a._long_free) == 8
    assert a.get_text("t", "d0") == _reference(docs["d0"])


def test_nothing_compiles_after_the_warm_up():
    from fluidframework_tpu.utils.chip import CompileClock

    a = TpuDocumentApplier(**GEOMETRY, **TIER)
    a.warm_farm()
    a.warm_long_tier()
    clock = CompileClock()
    _feed(a, _docs(42))
    assert a.promotions >= 3 and a.long_dispatches > 0
    assert clock.compiles == 0
