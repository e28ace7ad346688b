"""The farm's tile step against its full step, at a small size on the CPU.

A wave that occupies at most a tile's rows of the farm is stepped on
those rows alone, padded with rows it does not occupy
(``service/tpu_applier.py`` ``_tile_step_for``). Every comparison here
is exact: an applier that tiles against one that takes the full [D]
step on every wave, fed the same sequenced ops, on both kernel lanes
(the XLA scan, and Pallas in interpret mode):

- waves of 1 row, exactly a tile, one row past it (the next size) and
  past the largest (the full step);
- padding rows interleaved with the occupied ones, and far below them;
- a grid's vector rows in a tile, and ``matrix_cells`` reading the farm
  after the step;
- each turn's delta, byte for byte, and a restore from base + deltas.

The farm is 64 rows of 64 slots, K = 4: tiles of 8 and 16 rows.
"""

import os
import random
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark.editor import Editor
from benchmark.grid_editor import concurrent_stream
from benchmark.refmatrix import reference_grid
from fluidframework_tpu.obs import get_registry, parse_prometheus
from fluidframework_tpu.protocol.messages import (MessageType,
                                                  SequencedDocumentMessage)
from fluidframework_tpu.service.tpu_applier import (
    _STATE_FIELDS, ApplierCheckpointer, TpuDocumentApplier, _tile_rows,
    _tile_sizes, load_applier_checkpoint)

D, S, K = 64, 64, 4
LANES = {"xla": {},
         "pallas": dict(kernel="pallas", pallas_interpret=True)}
TURNS = 6


@pytest.mark.parametrize("docs, sizes", [
    (1000, (64, 256)), (1016, (64, 256)), (1200, (80, 304)),
    (256, (16, 64)), (64, (8, 16)), (16, (8,)), (8, ())])
def test_tile_sizes_follow_the_farm(docs, sizes):
    assert _tile_sizes(docs) == sizes
    for t in sizes:
        assert t % 8 == 0 and 8 <= t <= docs // 2


@pytest.mark.parametrize("slots, T, want", [
    ([0], 8, range(8)),
    ([1, 3, 5], 8, [0, 1, 2, 3, 4, 5, 6, 7]),
    ([60, 63], 8, [0, 1, 2, 3, 4, 5, 60, 63]),
    ([0, 1, 2, 3, 4, 5, 6, 7], 8, range(8)),
    ([2, 9, 11, 14, 15, 20, 30, 41, 50], 16,
     [0, 1, 2, 3, 4, 5, 6, 7, 9, 11, 14, 15, 20, 30, 41, 50])])
def test_tile_rows_pad_with_the_lowest_free_rows(slots, T, want):
    rows = _tile_rows(np.asarray(slots, np.int64), T)
    assert rows.dtype == np.int32 and list(rows) == list(want)
    assert len(set(rows.tolist())) == T and set(slots) <= set(rows.tolist())


def _msg(client, seq, ref, msn, op):
    return SequencedDocumentMessage(
        client_id=f"c{client}", sequence_number=seq,
        minimum_sequence_number=msn, client_sequence_number=seq,
        reference_sequence_number=ref, type=MessageType.OPERATION,
        contents=op)


class _Docs:
    """Seeded text edits a doc: one client, each op at the newest
    perspective, msn two ops behind so that zamboni drops removes."""

    def __init__(self, seed):
        self.seed, self.eds, self.seq = seed, {}, {}

    def box(self, d, n_ops):
        ed = self.eds.setdefault(
            d, Editor(random.Random(f"tile/{self.seed}/{d}")))
        pairs = []
        for op in ed.next_wire_ops(n_ops):
            seq = self.seq[d] = self.seq.get(d, 0) + 1
            pairs.append((_msg(0, seq, seq - 1, max(0, seq - 3), op), op))
        ed.ref_seq = self.seq[d]
        return pairs


def _appliers(lane, **kw):
    """An applier that tiles and one that takes the full step on every
    wave, with doc ``d{i}`` on the same row of both."""
    a, b = (TpuDocumentApplier(**{"max_docs": D, "max_slots": S,
                                  "ops_per_dispatch": K, **LANES[lane],
                                  **kw})
            for _ in range(2))
    b._tiles = ()
    docs = {}
    for i in range(D):
        row = a.slot_of("t", f"d{i}")
        assert b.slot_of("t", f"d{i}") == row
        docs[row] = f"d{i}"
    return a, b, docs


def _same_state(a, b):
    x, y = jax.device_get((a.state, b.state))
    for f in _STATE_FIELDS:
        np.testing.assert_array_equal(getattr(x, f), getattr(y, f),
                                      err_msg=f)


def _wave_counts() -> dict:
    got = parse_prometheus(get_registry().scrape())
    return {k: sum(got.get(f"fluid_applier_wave_{k}", {}).values())
            for k in ("tiled", "full", "rows", "rows_padded")}


def _pick(rng, n, where, turn):
    """``n`` rows: every other row from 0 (padding between them), the
    top n rows (padding far below), or at random on odd turns."""
    if turn % 2:
        return rng.sample(range(D), n)
    if where == "interleaved":
        return list(range(0, 2 * n, 2))
    return list(range(D - n, D))


@pytest.mark.parametrize("where", ["interleaved", "top"])
@pytest.mark.parametrize("n, tile", [(1, 8), (8, 8), (9, 16), (17, 0)])
@pytest.mark.parametrize("lane", sorted(LANES))
def test_the_tile_step_equals_the_full_step(lane, n, tile, where):
    a, b, docs = _appliers(lane)
    rng = random.Random(f"{lane}/{n}/{where}")
    src = _Docs(f"{n}/{where}")
    before = _wave_counts()
    for turn in range(TURNS):
        for row in _pick(rng, n, where, turn):
            pairs = src.box(row, rng.randint(1, K))
            for app in (a, b):
                app.ingest_batch("t", docs[row], pairs)
        for app in (a, b):
            app.flush()
        _same_state(a, b)
    for app in (a, b):
        app.finalize()
    assert a.host_escalations == b.host_escalations == 0
    _same_state(a, b)
    for row in range(D):
        assert a.get_text("t", docs[row]) == b.get_text("t", docs[row])
    # one wave a turn: every doc took at most K ops
    assert (a.waves_tiled, a.waves_full) == \
        ((TURNS, 0) if tile else (0, TURNS))
    assert (a.wave_rows, a.wave_rows_padded) == \
        (TURNS * n, TURNS * (tile or D))
    assert (b.waves_tiled, b.waves_full) == (0, TURNS)
    after = _wave_counts()
    assert {k: after[k] - before[k] for k in after} == {
        "tiled": a.waves_tiled, "full": a.waves_full + b.waves_full,
        "rows": a.wave_rows + b.wave_rows,
        "rows_padded": a.wave_rows_padded + b.wave_rows_padded}


def test_the_tile_sizes_compile_before_any_wave():
    """``warm_farm`` compiles the full step and both tile steps, so no
    wave of any width compiles after it (K = 5: shapes no other test
    here compiles)."""
    from fluidframework_tpu.utils.chip import CompileClock

    a, _b, docs = _appliers("xla", ops_per_dispatch=5)
    a.warm_farm()
    clock = CompileClock()
    src = _Docs("warm")
    for n in (1, 9, 17, 3):
        for row in range(n):
            a.ingest_batch("t", docs[row], src.box(row, 2))
        a.flush()
    a.finalize()
    assert (a.waves_tiled, a.waves_full) == (3, 1)
    assert clock.compiles == 0


@pytest.mark.parametrize("lane", sorted(LANES))
def test_warming_leaves_the_farm_as_it_was(lane):
    """``warm_farm`` runs all-NOOP waves on the farm itself: a farm
    with edits in it comes out bit-identical, and steps on as before."""
    a, b, docs = _appliers(lane)
    src = _Docs(f"warm/{lane}")
    for n in (5, 20, 3):
        if n == 20:
            a.warm_farm()
            _same_state(a, b)
        for row in range(n):
            pairs = src.box(row, K)
            for app in (a, b):
                app.ingest_batch("t", docs[row], pairs)
        for app in (a, b):
            app.flush()
    for app in (a, b):
        app.finalize()
    _same_state(a, b)


def _grid_boxes(seed, g):
    mix = ({"setCell": 0.8, "insertRows": 0.05, "insertCols": 0.05,
            "removeRows": 0.05, "removeCols": 0.05} if g % 2 else
           {"setCell": 0.92, "insertRows": 0.02, "insertCols": 0.02,
            "removeRows": 0.02, "removeCols": 0.02})
    return concurrent_stream(f"tile/{seed}/{g}", mix)


@pytest.mark.parametrize("lane", sorted(LANES))
def test_grid_vector_rows_in_a_tile(lane):
    """8 grids' 16 vector rows on a 16-row farm (one tile of 8): the
    waves that carry a few grids' vector ops are tiled, the farm and
    the cells match the full step's after every flush, and the cells
    read back match the reference."""
    grids = [_grid_boxes(lane, g) for g in range(8)]
    kw = dict(max_docs=16, max_slots=128, ops_per_dispatch=4, grids=8,
              grid_handles=64, **LANES[lane])
    a, b = TpuDocumentApplier(**kw), TpuDocumentApplier(**kw)
    b._tiles = ()
    for app in (a, b):
        app.set_grid_replay_source(lambda t, d: iter(()))
        for g in range(8):
            app.attach_grid("t", f"g{g}")
    at = [0] * 8
    rng = random.Random(lane)
    while any(i < len(bx) for i, bx in zip(at, grids)):
        # a few grids a turn, so that a wave occupies few vector rows
        for g in rng.sample(range(8), rng.randint(1, 5)):
            for c, box in grids[g][at[g]:at[g] + 4]:
                pairs = [(_msg(c, *row), row[3]) for row in box]
                for app in (a, b):
                    app.ingest_grid_batch("t", f"g{g}", pairs)
            at[g] += 4
        for app in (a, b):
            app.flush()
        _same_state(a, b)
    for app in (a, b):
        app.finalize()
    _same_state(a, b)
    np.testing.assert_array_equal(np.asarray(a.grid_cells),
                                  np.asarray(b.grid_cells))
    assert a.waves_tiled > 0 and b.waves_tiled == 0
    assert a.grid_escalations == 0 and a.grid_dispatches > 0
    for g, boxes in enumerate(grids):
        got = a.get_grid("t", f"g{g}")
        assert got == b.get_grid("t", f"g{g}"), g
        assert got == reference_grid(
            (seq, ref, f"c{c}", op)
            for c, box in boxes for seq, ref, _m, op in box), g


@pytest.mark.parametrize("lane", sorted(LANES))
def test_deltas_and_restore_equal_the_full_steps(tmp_path, lane):
    """Each turn's delta is byte for byte the full step's, and a restore
    from base + deltas gives the same farm and texts."""
    a, b, docs = _appliers(lane)
    paths = {app: str(tmp_path / name / "applier")
             for app, name in ((a, "tiled"), (b, "full"))}
    cks = {}
    for app, path in paths.items():
        os.makedirs(os.path.dirname(path))
        cks[app] = ApplierCheckpointer(app, path)
        cks[app].limit = 1 << 40  # deltas alone after the first base
        cks[app].base()
    rng = random.Random(lane)
    src = _Docs(f"ck/{lane}")
    for turn in range(TURNS):
        for row in rng.sample(range(D), (1, 5, 12, 20, 9, 30)[turn]):
            pairs = src.box(row, rng.randint(1, 2 * K))
            for app in (a, b):
                app.ingest_batch("t", docs[row], pairs)
        for app in (a, b):
            app.flush()
            cks[app].delta()
    assert a.waves_tiled > 0 and a.waves_full > 0
    deltas = {}
    for app, path in paths.items():
        d = os.path.dirname(path)
        deltas[app] = {f: Path(d, f).read_bytes()
                       for f in sorted(os.listdir(d)) if ".delta." in f}
    assert deltas[a] and deltas[a] == deltas[b]
    ra, rb = (load_applier_checkpoint(paths[app]) for app in (a, b))
    _same_state(ra, a)
    _same_state(rb, b)
    _same_state(ra, rb)
    for row in range(D):
        assert ra.get_text("t", docs[row]) == a.get_text("t", docs[row])
