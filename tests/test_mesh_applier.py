"""Mesh lane = fast lane: the doc-sharded applier must match the local
dense lane op-for-op across 1/2/4/8-shard meshes — through compaction
waves, overflow escalation, the chaos force_wide lane, the async worker
with min-wave hold-off, and checkpoint warm restart — while its wave
staging stays proportional to ACTIVE shards (never O(max_docs)) and its
the drained live device buffer count stays flat across waves.

conftest.py forces 8 virtual CPU devices, so every mesh geometry here
runs on real (virtual) multi-device shardings.
"""

import types

import jax
import numpy as np
import pytest

from fluidframework_tpu.driver import LocalDocumentServiceFactory
from fluidframework_tpu.loader import Loader
from fluidframework_tpu.ops.apply import OP_FIELDS
from fluidframework_tpu.parallel.mesh import make_mesh
from fluidframework_tpu.parallel.sharded_apply import doc_sharding
from fluidframework_tpu.service import LocalServer
from fluidframework_tpu.service.tpu_applier import (
    ApplierCheckpointer,
    TpuDocumentApplier,
    channel_stream,
    load_applier_checkpoint,
    save_applier_checkpoint,
)

SEEDS = (0, 7, 42)
DOCS = [f"doc{i}" for i in range(8)]


def _build_soup(seed):
    """Seeded op soup over 8 docs through the real client stack:
    inserts, removes (so zamboni compaction runs), annotates."""
    server = LocalServer()
    loader = Loader(LocalDocumentServiceFactory(server))
    rng = np.random.default_rng(seed)
    strings = {}
    for d in DOCS:
        c = loader.resolve("t", d)
        strings[d] = c.runtime.create_data_store(
            "default").create_channel("text", "shared-string")
    for _ in range(160):
        d = DOCS[rng.integers(0, len(DOCS))]
        s = strings[d]
        n = len(s.get_text())
        r = rng.random()
        if n > 4 and r < 0.30:
            a = int(rng.integers(0, n - 1))
            b = int(rng.integers(a + 1, min(n, a + 6) + 1))
            s.remove_text(a, b)
        elif n > 2 and r < 0.40:
            a = int(rng.integers(0, n - 1))
            s.annotate_range(a, a + 1, {"k": int(rng.integers(0, 5))})
        else:
            s.insert_text(int(rng.integers(0, n + 1)),
                          f"[{rng.integers(0, 100)}]")
    return server, {d: strings[d].get_text() for d in DOCS}


@pytest.fixture(scope="module")
def soup():
    return {seed: _build_soup(seed) for seed in SEEDS}


def _feed(applier, server, doc):
    for msg in channel_stream(server, "t", doc, "default", "text"):
        applier.ingest("t", doc, msg, msg.contents)


def _feed_all(applier, server):
    for d in DOCS:
        _feed(applier, server, d)
    applier.finalize()


@pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
@pytest.mark.parametrize("seed", SEEDS)
def test_mesh_matches_local_fuzz(soup, seed, n_shards):
    server, texts = soup[seed]
    local = TpuDocumentApplier(max_docs=16, max_slots=256,
                               ops_per_dispatch=8)
    meshed = TpuDocumentApplier(max_docs=16, max_slots=256,
                                ops_per_dispatch=8,
                                mesh=make_mesh(n_shards, seg_shards=1))
    for applier in (local, meshed):
        _feed_all(applier, server)
    for d in DOCS:
        assert meshed.get_text("t", d) == texts[d], (seed, n_shards, d)
        assert meshed.get_text("t", d) == local.get_text("t", d)
    assert meshed.host_escalations == 0
    assert local.host_escalations == 0
    # every mesh dispatch rode the per-shard staging lane
    assert meshed.mesh_waves == meshed.dispatches > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_mesh_overflow_escalation_matches(soup, seed):
    """A slot budget far below the soup's live segment count forces the
    overflow → host-escalation flip on the mesh path; escalated docs must
    still converge to the oracle text."""
    server, texts = soup[seed]
    applier = TpuDocumentApplier(max_docs=8, max_slots=8,
                                 ops_per_dispatch=8,
                                 mesh=make_mesh(4, seg_shards=1))
    applier.set_replay_source(
        lambda t, d: channel_stream(server, t, d, "default", "text"))
    _feed_all(applier, server)
    assert applier.host_escalations > 0
    for d in DOCS:
        assert applier.get_text("t", d) == texts[d], (seed, d)


def test_mesh_force_wide_lane_matches(soup):
    """The chaos force_wide seam must route mesh waves down the int32
    wide sharded lane and still converge."""
    server, texts = soup[0]
    applier = TpuDocumentApplier(max_docs=16, max_slots=256,
                                 ops_per_dispatch=8,
                                 mesh=make_mesh(2, seg_shards=1))
    applier.fault_plane = lambda point, **kw: (
        "force_wide" if point == "applier.dispatch" else None)
    _feed_all(applier, server)
    for d in DOCS:
        assert applier.get_text("t", d) == texts[d], d
    assert applier.mesh_waves == applier.dispatches > 0


def test_mesh_staging_bytes_scale_with_active_shards(soup):
    """The tentpole's O(max_docs) → O(active shards) claim, counter-
    asserted: one active doc stages exactly one shard's compact buffers
    per wave, far below the dense global wave."""
    server, _texts = soup[0]
    K = 8
    applier = TpuDocumentApplier(max_docs=64, max_slots=64,
                                 ops_per_dispatch=K,
                                 mesh=make_mesh(8, seg_shards=1))
    _feed(applier, server, DOCS[0])
    applier.finalize()
    sps = applier.placement.slots_per_shard
    per_shard = sps * K * OP_FIELDS * 2 + sps * 2 * 4  # wave16 + bases
    assert applier.mesh_waves > 0
    assert applier.mesh_active_shards == applier.mesh_waves  # 1 per wave
    assert applier.mesh_staged_bytes == applier.mesh_waves * per_shard
    dense_wave = 64 * K * OP_FIELDS * 4  # the pre-refactor global array
    assert per_shard * 8 <= dense_wave  # even all-active stays under int32 dense


def _msg(seq, msn):
    return types.SimpleNamespace(sequence_number=seq,
                                 reference_sequence_number=max(seq - 1, 0),
                                 minimum_sequence_number=msn,
                                 client_id="c0")


def test_mesh_donation_live_buffers_flat():
    """Device-buffer regression (satellite): across 100 mesh waves the
    drained live buffer count must stay flat — a leak in the per-wave
    assembly path grows it monotonically. Counting happens behind a
    fence: the overlap pipeline legitimately keeps in-flight waves (and
    their staged inputs) alive until the device drains, and on
    non-donating backends the superseded state lives until the step
    completes."""
    applier = TpuDocumentApplier(max_docs=8, max_slots=32,
                                 ops_per_dispatch=4,
                                 mesh=make_mesh(4, seg_shards=1))
    seq = 0
    baseline = None
    for wave in range(100):
        for i in range(4):
            doc = f"d{i}"
            seq += 1
            msn = max(seq - 4, 0)
            applier.ingest("t", doc, _msg(seq, msn),
                           {"type": 0, "pos": 0, "text": "x"})
            seq += 1
            applier.ingest("t", doc, _msg(seq, max(seq - 4, 0)),
                           {"type": 1, "start": 0, "end": 1})
        applier.flush()
        if wave == 9:
            # caches are warm by now (jit, zero shards, bases buffers);
            # fence so in-flight waves don't inflate the baseline
            np.asarray(applier.state.count)
            baseline = len(jax.live_arrays())
    np.asarray(applier.state.count)
    assert applier.mesh_waves >= 100
    assert len(jax.live_arrays()) <= baseline + 2
    assert not np.asarray(applier.state.overflow).any()


def test_mesh_checkpoint_restore_resharded(tmp_path, soup):
    """Warm restart of a mesh applier: the restored state must come back
    COMMITTED per P('docs') (the zero-relayout invariant survives the
    checkpoint cycle), and a shard-count mismatch must refuse loudly."""
    server, texts = soup[0]
    mesh = make_mesh(2, seg_shards=1)
    a = TpuDocumentApplier(max_docs=8, max_slots=128, ops_per_dispatch=8,
                           mesh=mesh)
    _feed_all(a, server)
    save_applier_checkpoint(a, str(tmp_path / "ck"))

    b = load_applier_checkpoint(str(tmp_path / "ck"), mesh=mesh)
    assert b.state.length.sharding == doc_sharding(mesh)
    for d in DOCS:
        assert b.get_text("t", d) == texts[d], d

    with pytest.raises(ValueError):
        load_applier_checkpoint(str(tmp_path / "ck"),
                                mesh=make_mesh(4, seg_shards=1))


@pytest.mark.parametrize("seed", SEEDS)
def test_mesh_loads_base_and_deltas_resharded(tmp_path, soup, seed):
    """A mesh applier's base + deltas (each half of every doc's stream a
    delta) replay on the host and re-shard after: the state comes back
    committed per P('docs'), row for row what a full save reloads, and
    every doc's text is the oracle's."""
    server, texts = soup[seed]
    mesh = make_mesh(2, seg_shards=1)
    a = TpuDocumentApplier(max_docs=8, max_slots=128, ops_per_dispatch=8,
                           mesh=mesh)
    ck = ApplierCheckpointer(a, str(tmp_path / "ck"))
    ck.limit = 1 << 60  # every delta stays on disk
    ck.base()
    msgs = {d: list(channel_stream(server, "t", d, "default", "text"))
            for d in DOCS}
    for half in (0, 1):
        for d in DOCS:
            n = len(msgs[d])
            for m in msgs[d][half * n // 2:(half + 1) * n // 2]:
                a.ingest("t", d, m, m.contents)
        assert ck.delta().docs == len(DOCS)
    save_applier_checkpoint(a, str(tmp_path / "full"))

    b = load_applier_checkpoint(str(tmp_path / "ck"), mesh=mesh)
    full = load_applier_checkpoint(str(tmp_path / "full"), mesh=mesh)
    assert b._marks.seq == 2
    assert b.state.length.sharding == doc_sharding(mesh)
    for f in ("length", "text_start", "rem_seq", "prop_key", "count"):
        np.testing.assert_array_equal(np.asarray(getattr(b.state, f)),
                                      np.asarray(getattr(full.state, f)))
    for d in DOCS:
        assert b.get_text("t", d) == texts[d], d
