"""TpuDocumentApplier: the batched device replica must match the scalar
client replicas for every doc — the kernel-vs-full-stack convergence
check (the TPU analog of PartialSequenceLengths verification + the
scribe-replay BASELINE config 5).
"""

import numpy as np
import pytest

from fluidframework_tpu.driver import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Loader
from fluidframework_tpu.service import LocalServer
from fluidframework_tpu.service.tpu_applier import TpuDocumentApplier, channel_stream


@pytest.fixture
def server():
    return LocalServer()


@pytest.fixture
def loader(server):
    return Loader(LocalDocumentServiceFactory(server))


def feed_applier(applier, server, tenant, doc):
    for msg in channel_stream(server, tenant, doc, "default", "text"):
        applier.ingest(tenant, doc, msg, msg.contents)
    applier.finalize()  # flush + overflow fence (escalations observed)


def test_applier_matches_client_replicas(server, loader):
    c1 = loader.resolve("t", "doc")
    c2 = loader.resolve("t", "doc")
    s1 = c1.runtime.create_data_store("default").create_channel("text", "shared-string")
    s2 = c2.runtime.get_data_store("default").get_channel("text")
    s1.insert_text(0, "hello world")
    s2.insert_text(5, ", tpu")
    s1.remove_text(0, 5)
    s2.insert_text(s2.get_text().__len__(), "!")
    assert s1.get_text() == s2.get_text()

    applier = TpuDocumentApplier(max_docs=8, max_slots=64, ops_per_dispatch=4)
    feed_applier(applier, server, "t", "doc")
    assert applier.get_text("t", "doc") == s1.get_text()
    assert applier.host_escalations == 0


def test_applier_many_docs_fuzz(server, loader):
    rng = np.random.default_rng(11)
    docs = [f"doc{i}" for i in range(6)]
    strings = {}
    for d in docs:
        c = loader.resolve("t", d)
        strings[d] = c.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
    for _ in range(120):
        d = docs[rng.integers(0, len(docs))]
        s = strings[d]
        n = len(s.get_text())
        if n > 4 and rng.random() < 0.35:
            a = int(rng.integers(0, n - 1))
            b = int(rng.integers(a + 1, n + 1))
            s.remove_text(a, b)
        else:
            pos = int(rng.integers(0, n + 1))
            s.insert_text(pos, f"[{rng.integers(0, 100)}]")

    applier = TpuDocumentApplier(max_docs=16, max_slots=512, ops_per_dispatch=8)
    for d in docs:
        feed_applier(applier, server, "t", d)
    for d in docs:
        assert applier.get_text("t", d) == strings[d].get_text(), d
    assert applier.host_escalations == 0
    assert applier.dispatches > 0


def test_applier_annotate_stays_on_device(server, loader):
    """Annotate is a first-class device op (round-1 VERDICT #3a): no host
    escalation, and the per-slot LWW prop table matches the client replica."""
    c1 = loader.resolve("t", "doc")
    s1 = c1.runtime.create_data_store("default").create_channel("text", "shared-string")
    s1.insert_text(0, "styled text")
    s1.annotate_range(0, 6, {"bold": True})
    s1.annotate_range(3, 8, {"size": 12})
    s1.annotate_range(0, 2, {"bold": None})  # delete
    s1.insert_text(4, "x")

    applier = TpuDocumentApplier(max_docs=4, max_slots=32, ops_per_dispatch=4)
    feed_applier(applier, server, "t", "doc")
    assert applier.host_escalations == 0
    assert applier.get_text("t", "doc") == s1.get_text()
    replica = c1.runtime.get_data_store("default").get_channel("text").client
    for pos in range(len(s1.get_text())):
        assert applier.get_properties_at("t", "doc", pos) == \
            replica.get_properties_at(pos), pos


def test_applier_zamboni_bounds_slots_under_churn(server, loader):
    """With deli's msn riding every staged op, device zamboni keeps the
    slot count bounded while two clients churn (round-1 VERDICT #3b)."""
    import numpy as np

    rng = np.random.default_rng(5)
    c1 = loader.resolve("t", "doc")
    c2 = loader.resolve("t", "doc")
    s1 = c1.runtime.create_data_store("default").create_channel("text", "shared-string")
    s2 = c2.runtime.get_data_store("default").get_channel("text")

    applier = TpuDocumentApplier(max_docs=4, max_slots=64, ops_per_dispatch=8)
    seen = 0
    max_count = 0
    for i in range(150):
        s = s1 if i % 2 == 0 else s2
        n = len(s.get_text())
        if n > 6 and rng.random() < 0.5:
            a = int(rng.integers(0, n - 3))
            s.remove_text(a, a + 3)
        else:
            s.insert_text(int(rng.integers(0, n + 1)), "ab")
        # feed the applier incrementally (live tail, not one big replay)
        msgs = list(channel_stream(server, "t", "doc", "default", "text"))
        for m in msgs[seen:]:
            applier.ingest("t", "doc", m, m.contents)
        seen = len(msgs)
        if i % 10 == 9:
            applier.flush()
            max_count = max(max_count, applier.slot_count("t", "doc"))
    applier.flush()
    assert applier.host_escalations == 0
    assert applier.get_text("t", "doc") == s1.get_text() == s2.get_text()
    # 150 ops with ~50% removes would need ≳150 slots without zamboni
    assert max(max_count, applier.slot_count("t", "doc")) < 60


def test_applier_escalates_capacity_overflow(server, loader):
    c1 = loader.resolve("t", "doc")
    s1 = c1.runtime.create_data_store("default").create_channel("text", "shared-string")
    for i in range(30):  # far beyond 8 slots after splits
        s1.insert_text(len(s1.get_text()) // 2, f"seg{i}")

    applier = TpuDocumentApplier(max_docs=4, max_slots=8, ops_per_dispatch=4)
    applier.set_replay_source(
        lambda t, d: list(channel_stream(server, t, d, "default", "text")))
    feed_applier(applier, server, "t", "doc")
    assert applier.host_escalations == 1
    assert applier.get_text("t", "doc") == s1.get_text()


def test_applier_on_virtual_mesh(server, loader):
    from fluidframework_tpu.parallel.mesh import make_mesh

    docs = [f"doc{i}" for i in range(4)]
    strings = {}
    for d in docs:
        c = loader.resolve("t", d)
        s = c.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
        s.insert_text(0, f"content of {d}")
        strings[d] = s

    mesh = make_mesh(8, seg_shards=1)
    applier = TpuDocumentApplier(max_docs=8, max_slots=64,
                                 ops_per_dispatch=4, mesh=mesh)
    # mesh mode routes docs through the REAL placement table: one shard
    # per 'docs'-axis device, global row = shard * slots_per_shard + slot
    assert applier.placement.n_shards == 8
    for d in docs:
        feed_applier(applier, server, "t", d)
    shards = {applier.placement.lookup("t", d)[0] for d in docs}
    assert len(shards) > 1, "docs all hashed to one shard"
    for d in docs:
        assert applier.get_text("t", d) == strings[d].get_text()


def test_interval_only_batch_does_not_crash_dispatch():
    """A doc whose batch stages NOTHING on the device (interval metadata
    ops stage zero tuples) must not break the vectorized wave build when
    another doc has real ops in the same flush."""
    from fluidframework_tpu.protocol.messages import (
        MessageType,
        SequencedDocumentMessage,
    )

    applier = TpuDocumentApplier(max_docs=4, max_slots=16, ops_per_dispatch=4)
    applier.set_replay_source(lambda t, d: [])

    def msg(seq):
        return SequencedDocumentMessage(
            client_id="c1", sequence_number=seq, minimum_sequence_number=0,
            client_sequence_number=seq, reference_sequence_number=seq - 1,
            type=MessageType.OPERATION)

    applier.ingest("t", "iv-doc", msg(1), {"type": "interval", "op": "add"})
    applier.ingest("t", "txt-doc", msg(1), {"type": 0, "pos": 0, "text": "hi"})
    applier.flush()
    applier.finalize()
    assert applier.host_escalations == 0
    assert applier.get_text("t", "txt-doc") == "hi"


def test_applier_checkpoint_warm_restart(tmp_path, server, loader):
    """Device-farm checkpointing: save a fenced applier, load it in a
    'new process', and continue ingesting live ops with no replay."""
    from fluidframework_tpu.service.tpu_applier import (
        load_applier_checkpoint,
        save_applier_checkpoint,
    )

    docs = ["a", "b"]
    strings = {}
    applier = TpuDocumentApplier(max_docs=4, max_slots=64,
                                 ops_per_dispatch=4)
    applier.set_replay_source(lambda t, d: [])
    for d in docs:
        c = loader.resolve("t", d)
        s = c.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
        s.insert_text(0, f"checkpointed {d} ")
        s.annotate_range(0, 3, {"bold": True})
        strings[d] = s
        feed_applier(applier, server, "t", d)

    path = str(tmp_path / "farm")
    save_applier_checkpoint(applier, path)

    revived = load_applier_checkpoint(path)
    revived.set_replay_source(lambda t, d: [])
    for d in docs:
        assert revived.get_text("t", d) == strings[d].get_text()
        assert revived.get_properties_at("t", d, 0).get("bold") is True

    # the revived farm keeps ingesting the live stream where it left off
    seen = {d: server.get_deltas("t", d, 0, 10**9)[-1].sequence_number
            for d in docs}
    for d in docs:
        strings[d].insert_text(0, ">> ")
        for m in channel_stream(server, "t", d, "default", "text"):
            if m.sequence_number > seen[d]:
                revived.ingest("t", d, m, m.contents)
    revived.finalize()
    assert revived.host_escalations == 0
    for d in docs:
        assert revived.get_text("t", d) == strings[d].get_text()


# ------------------------------------------------- base + delta checkpoints

CK_SEEDS = (0, 7, 42)
CK_DOCS = [f"doc{i}" for i in range(6)]
CK_ROUNDS = 4
STATE_FIELDS = ("length", "text_start", "flags", "ins_seq", "ins_client",
                "rem_seq", "rem_client_a", "rem_client_b", "prop_key",
                "prop_val", "count", "overflow")


def _ck_soup(seed):
    """Seeded edits over six docs through the client stack; annotate keys
    keep appearing, so the property table grows all along. Returns each
    doc's channel messages and final text."""
    server = LocalServer()
    loader = Loader(LocalDocumentServiceFactory(server))
    rng = np.random.default_rng(seed)
    strings = {}
    for d in CK_DOCS:
        c = loader.resolve("t", d)
        strings[d] = c.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
    for i in range(120):
        s = strings[CK_DOCS[rng.integers(0, len(CK_DOCS))]]
        n = len(s.get_text())
        r = rng.random()
        if n > 4 and r < 0.3:
            a = int(rng.integers(0, n - 1))
            s.remove_text(a, int(rng.integers(a + 1, min(n, a + 5) + 1)))
        elif n > 2 and r < 0.45:
            a = int(rng.integers(0, n - 1))
            s.annotate_range(a, a + 1, {f"k{i // 15}": int(rng.integers(9))})
        else:
            s.insert_text(int(rng.integers(0, n + 1)),
                          f"<{rng.integers(0, 100)}>")
    msgs = {d: list(channel_stream(server, "t", d, "default", "text"))
            for d in CK_DOCS}
    return msgs, {d: strings[d].get_text() for d in CK_DOCS}


@pytest.fixture(scope="module")
def ck_soup():
    return {seed: _ck_soup(seed) for seed in CK_SEEDS}


class _Feed:
    """Feeds an applier the soup in rounds: the first four docs from
    round 0, the last two placed only from round 2; round 1 escalates
    doc1 to the host at its first op there."""

    def __init__(self, msgs):
        self.msgs = msgs
        self.fed = {d: 0 for d in CK_DOCS}
        self.applier = TpuDocumentApplier(max_docs=8, max_slots=64,
                                          ops_per_dispatch=4)
        self.applier.set_replay_source(
            lambda t, d: self.msgs[d][:self.fed[d]])

    def round(self, r):
        a = self.applier
        target = a.slot_of("t", "doc1") if r == 1 else None

        def plane(point, slot=None, **_ctx):
            if point == "applier.ingest" and slot == target \
                    and slot not in a._host_docs:
                return "escalate_host"
            return None

        a.fault_plane = plane
        for i, d in enumerate(CK_DOCS):
            if r < (2 if i >= 4 else 0):
                continue
            upto = len(self.msgs[d]) * (r + 1) // CK_ROUNDS
            while self.fed[d] < upto:
                m = self.msgs[d][self.fed[d]]
                a.ingest("t", d, m, m.contents)
                self.fed[d] += 1
        a.fault_plane = None
        a.flush()


def _assert_same_farm(a, b):
    for f in STATE_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a.state, f)),
                                      np.asarray(getattr(b.state, f)),
                                      err_msg=f)
    assert [x.text() for x in a.arenas] == [x.text() for x in b.arenas]
    assert a.prop_table.snapshot() == b.prop_table.snapshot()
    assert a.placement.snapshot() == b.placement.snapshot()
    for attr in ("_client_ids", "_doc_keys", "_applied_seq", "_first_seq",
                 "_anchored", "_restore_applied"):
        assert getattr(a, attr) == getattr(b, attr), attr
    assert {k: r.snapshot() for k, r in a._host_docs.items()} == \
        {k: r.snapshot() for k, r in b._host_docs.items()}
    for d in a._doc_keys.values():
        assert a.get_text(*d) == b.get_text(*d), d


def _full_save_reloaded(applier, tmp_path):
    from fluidframework_tpu.service.tpu_applier import (
        load_applier_checkpoint, save_applier_checkpoint)

    save_applier_checkpoint(applier, str(tmp_path / "full"))
    return load_applier_checkpoint(str(tmp_path / "full"))


@pytest.mark.parametrize("seed", CK_SEEDS)
def test_base_and_deltas_reload_as_a_full_save(tmp_path, ck_soup, seed):
    """A base, then one delta per round — new docs placed, new property
    keys interned, a doc escalated to the host in between — reloads
    bit-identical to a full save of the same applier: every state row,
    every sidecar table, every doc's text."""
    from fluidframework_tpu.service.tpu_applier import (
        ApplierCheckpointer, _delta_files, load_applier_checkpoint)

    msgs, texts = ck_soup[seed]
    feed = _Feed(msgs)
    path = str(tmp_path / "ck")
    ck = ApplierCheckpointer(feed.applier, path)
    ck.limit = 1 << 60  # no base rewrite: every delta stays on disk
    assert ck.base().bases == 1
    docs = []
    for r in range(CK_ROUNDS):
        feed.round(r)
        w = ck.delta()
        assert w.nbytes > 0 and w.bases == 0
        docs.append(w.docs)
    assert ck.delta().docs == 0  # nothing moved, nothing written
    assert docs[0] == 4 and docs[2] == 6  # only the docs that moved
    assert feed.applier.host_escalations == 1
    assert [first for first, _ in _delta_files(path)] == [1]
    revived = load_applier_checkpoint(path)
    assert revived._marks.seq == CK_ROUNDS
    full = _full_save_reloaded(feed.applier, tmp_path)
    _assert_same_farm(revived, full)
    for d in CK_DOCS:
        assert revived.get_text("t", d) == texts[d], d


@pytest.mark.parametrize("seed", CK_SEEDS)
def test_a_torn_last_delta_loads_as_the_one_before(tmp_path, ck_soup, seed):
    """A crash in the middle of a delta's append leaves a torn record: the
    load stops before it, at the state of the delta before, and the next
    base (the restart's) removes the torn file."""
    import shutil

    from fluidframework_tpu.service.tpu_applier import (
        ApplierCheckpointer, _delta_files, load_applier_checkpoint)

    msgs, _ = ck_soup[seed]
    feed = _Feed(msgs)
    ck_dir = tmp_path / "state"
    ck_dir.mkdir()
    path = str(ck_dir / "applier")
    ck = ApplierCheckpointer(feed.applier, path)
    ck.limit = 1 << 60
    ck.base()
    for r in range(2):
        feed.round(r)
        ck.delta()
    shutil.copytree(ck_dir, tmp_path / "before")
    (_, name), = _delta_files(path)
    size = (ck_dir / name).stat().st_size
    feed.round(2)
    grew = ck.delta().nbytes
    with open(name, "r+b") as f:
        f.truncate(size + grew // 2)
    revived = load_applier_checkpoint(path)
    assert revived._marks.seq == 2
    _assert_same_farm(revived, load_applier_checkpoint(
        str(tmp_path / "before" / "applier")))
    restarted = ApplierCheckpointer(revived, path)
    restarted.base()
    assert _delta_files(path) == []
    _assert_same_farm(load_applier_checkpoint(path), revived)


@pytest.mark.parametrize("seed", CK_SEEDS)
@pytest.mark.parametrize("crash", ["before_base_commit",
                                   "before_delta_cleanup"])
def test_a_crash_around_a_base_rewrite_keeps_every_delta(
        tmp_path, ck_soup, seed, crash, monkeypatch):
    """kill -9 while a due base is written on its thread — after its
    snapshot and before the sidecar's rename, or after the rename and
    before the covered delta files go — loads the applier as it was:
    the older base replays every delta since, or the new one skips those
    it covers."""
    import json
    import shutil
    import threading

    from fluidframework_tpu.service import tpu_applier as ta

    msgs, _ = ck_soup[seed]
    feed = _Feed(msgs)
    ck_dir = tmp_path / "state"
    ck_dir.mkdir()
    path = str(ck_dir / "applier")
    ck = ta.ApplierCheckpointer(feed.applier, path)
    ck.base()
    release = threading.Event()
    if crash == "before_base_commit":
        def write_base(arrays, meta, p):
            with open(p + ".g1.npz", "wb") as f:  # half the arrays
                f.write(b"PK\x03\x04")
            release.wait(60)
            raise OSError("killed before the sidecar's rename")

        monkeypatch.setattr(ta, "_write_base", write_base)
    else:
        remove = ta.ApplierCheckpointer._remove_deltas
        monkeypatch.setattr(
            ta.ApplierCheckpointer, "_remove_deltas",
            lambda self, upto: None if upto is not None
            else remove(self, upto))
    feed.round(0)
    ck.delta()
    ck.limit = 0  # the next delta makes a base due
    feed.round(1)
    ck.delta()
    assert ck._writer is not None
    ck.limit = 1 << 60
    if crash == "before_delta_cleanup":
        ck._writer.join(timeout=60)
        assert not ck._writer.is_alive()
    feed.round(2)
    ck.delta()
    assert [first for first, _ in ta._delta_files(path)] == [1, 3]
    shutil.copytree(ck_dir, tmp_path / "killed")  # what kill -9 leaves
    with open(str(tmp_path / "killed" / "applier.json")) as f:
        assert json.load(f)["delta_seq"] == (
            0 if crash == "before_base_commit" else 2)
    revived = ta.load_applier_checkpoint(str(tmp_path / "killed" /
                                             "applier"))
    assert revived._marks.seq == 3
    release.set()
    if crash == "before_base_commit":
        with pytest.raises(OSError, match="rename"):
            ck.base()  # the stage sees its base writer's failure
        monkeypatch.undo()
    _assert_same_farm(revived, _full_save_reloaded(feed.applier, tmp_path))


@pytest.mark.parametrize("seed", CK_SEEDS)
def test_a_save_elsewhere_leaves_the_delta_chain_intact(tmp_path, ck_soup,
                                                        seed):
    """A full save of the applier to another path between two deltas
    changes nothing of the chain it does not own: the next delta still
    holds every slot changed since the one before, and base + deltas
    reload as a full save."""
    from fluidframework_tpu.service.tpu_applier import (
        ApplierCheckpointer, load_applier_checkpoint, save_applier_checkpoint)

    msgs, texts = ck_soup[seed]
    feed = _Feed(msgs)
    path = str(tmp_path / "ck")
    ck = ApplierCheckpointer(feed.applier, path)
    ck.limit = 1 << 60
    ck.base()
    feed.round(0)
    ck.delta()
    feed.round(1)
    save_applier_checkpoint(feed.applier, str(tmp_path / "side"))
    assert ck.delta().docs == 4  # the four docs round 1 moved
    feed.round(2)
    ck.delta()
    _assert_same_farm(load_applier_checkpoint(path),
                      _full_save_reloaded(feed.applier, tmp_path))


def _annotate_host_doc(applier, msgs, fed, props, after):
    """Sequence a remote annotate of the escalated doc1's first character,
    ``after`` seqs past the last op it was fed."""
    import dataclasses

    last = msgs["doc1"][fed - 1]
    m = dataclasses.replace(
        last, sequence_number=last.sequence_number + after,
        reference_sequence_number=last.sequence_number,
        client_sequence_number=last.client_sequence_number + after,
        contents={"type": 2, "start": 0, "end": 1, "props": props})
    applier.ingest("t", "doc1", m, m.contents)


@pytest.mark.parametrize("seed", CK_SEEDS)
def test_a_base_on_its_thread_holds_the_applier_at_its_snapshot(
        tmp_path, ck_soup, seed, monkeypatch):
    """While a due base is written on the applier-base thread, the stage
    goes on: here it annotates the escalated doc in place. The base on
    disk is still the applier at its snapshot (what a full save taken
    then wrote), and base + deltas reload as a full save at the end."""
    import json
    import threading

    from fluidframework_tpu.service import tpu_applier as ta

    msgs, _ = ck_soup[seed]
    feed = _Feed(msgs)
    a = feed.applier
    path = str(tmp_path / "ck")
    ck = ta.ApplierCheckpointer(a, path)
    ck.limit = 1 << 60
    ck.base()
    for r in range(2):  # round 1 escalates doc1
        feed.round(r)
        ck.delta()
    slot = a.slot_of("t", "doc1")
    assert slot in a._host_docs
    _annotate_host_doc(a, msgs, feed.fed["doc1"], {"late": 1}, after=1)
    parked, release = threading.Event(), threading.Event()
    write_base = ta._write_base

    def parked_write(arrays, meta, p):
        parked.set()
        release.wait(60)
        return write_base(arrays, meta, p)

    monkeypatch.setattr(ta, "_write_base", parked_write)
    ck.limit = 0  # this delta makes a base due
    assert ck.delta().docs == 1
    assert parked.wait(60)
    ck.limit = 1 << 60
    monkeypatch.undo()
    ta.save_applier_checkpoint(a, str(tmp_path / "at_snapshot"))
    _annotate_host_doc(a, msgs, feed.fed["doc1"], {"later": 2}, after=2)
    release.set()
    ck._writer.join(60)
    assert ck.delta().bases == 1

    def meta(p):
        with open(p + ".json") as f:
            m = json.load(f)
        with np.load(f"{p}.g{m.pop('gen')}.npz") as z:
            return m, {k: z[k] for k in z.files}

    (base, base_rows), (then, then_rows) = meta(path), meta(
        str(tmp_path / "at_snapshot"))
    assert base == then
    for f in STATE_FIELDS:
        np.testing.assert_array_equal(base_rows[f], then_rows[f], f)
    assert "later" not in json.dumps(base["host_docs"])
    assert a.get_text("t", "doc1") == feed.applier.get_text("t", "doc1")
    _assert_same_farm(ta.load_applier_checkpoint(path),
                      _full_save_reloaded(a, tmp_path))
