"""SharedMatrix grids on the device applier, at a small size on the CPU:
8 grids of 16 x 16 (H = 64 handles a vector), 4 clients a grid, a farm
of S = 128 slots and K = 4.

Every comparison is exact: the applier's cells (``get_grid``) against
the host SharedMatrix (``dds/matrix.py``) fed the same sequenced ops, and
against ``benchmark/refmatrix.py``:

- seeded concurrent streams (the benchmark's grid editor) on 8 grids;
- two setCells of one cell in one wave: the higher seq wins;
- a setCell after a vector insert of lower seq in the same wave;
- a setCell into a row removed concurrently: a dead write, counted;
- inserts at one position by two clients;
- handles past H escalate the grid, and it converges on the host;
- base + deltas + restore equals an uninterrupted run;
- with no grids configured the farm's wave arrays are the same;
- a doc that holds text beside its grid keeps both, attached before or
  after its text, in the applier and through the applier stage (which
  leaves the matrix channel alone where no grids are configured).
"""

import random
from dataclasses import replace

import numpy as np
import pytest

from benchmark.editor import Editor
from benchmark.grid_editor import concurrent_stream
from benchmark.refmatrix import reference_grid
from fluidframework_tpu.dds.matrix import SharedMatrix
from fluidframework_tpu.protocol.messages import (MessageType,
                                                  SequencedDocumentMessage)
from fluidframework_tpu.service.tpu_applier import (
    ApplierCheckpointer, TpuDocumentApplier, load_applier_checkpoint)

SEEDS = [0, 7, 42]
GEOMETRY = dict(max_slots=128, ops_per_dispatch=4, grids=8, grid_handles=64)
CELL_MIX = {"setCell": 0.92, "insertRows": 0.02, "insertCols": 0.02,
            "removeRows": 0.02, "removeCols": 0.02}
# more vector ops than the cell's, so that waves split often
VECTOR_MIX = {"setCell": 0.8, "insertRows": 0.05, "insertCols": 0.05,
              "removeRows": 0.05, "removeCols": 0.05}


def grid_stream(seed, mix=CELL_MIX, **kw):
    return concurrent_stream(seed, mix, **kw)


def _msg(client, seq, ref, msn, op):
    return SequencedDocumentMessage(
        client_id=f"c{client}", sequence_number=seq,
        minimum_sequence_number=msn, client_sequence_number=seq,
        reference_sequence_number=ref, type=MessageType.OPERATION,
        contents=op)


def _messages(boxes):
    return [_msg(c, *row) for c, box in boxes for row in box]


def _host(boxes) -> dict:
    grid = SharedMatrix("matrix")
    for m in _messages(boxes):
        grid.process(m, local=False)
    return {(r, c): v for r, vals in enumerate(grid.to_lists())
            for c, v in enumerate(vals) if v is not None}


def _ref(boxes) -> dict:
    return reference_grid((seq, ref, f"c{c}", op)
                          for c, box in boxes for seq, ref, _m, op in box)


def _applier(**kw):
    a = TpuDocumentApplier(**{"max_docs": 16, **GEOMETRY, **kw})
    logs = {}
    a.set_grid_replay_source(lambda t, d: iter(logs.get(d, ())))
    a.logs = logs
    return a


def _feed(applier, grids, turns=10, upto=None):
    """Each turn ingests a share of every grid's boxcars, then flushes;
    the replay source holds what was ingested."""
    for gi, boxes in enumerate(grids):
        applier.attach_grid("t", f"g{gi}")
    n = max(len(b) for b in grids) if upto is None else upto
    per = -(-n // turns)
    for lo in range(0, n, per):
        for gi, boxes in enumerate(grids):
            for c, box in boxes[lo:lo + per]:
                pairs = [(_msg(c, *row), row[3]) for row in box]
                applier.logs.setdefault(f"g{gi}", []).extend(
                    m for m, _ in pairs)
                applier.ingest_grid_batch("t", f"g{gi}", pairs)
        applier.flush()


@pytest.mark.parametrize("seed", SEEDS)
def test_concurrent_streams_match_the_host_and_the_reference(seed):
    grids = [grid_stream(f"{seed}/{g}", VECTOR_MIX if g % 2 else CELL_MIX)
             for g in range(8)]
    a = _applier()
    _feed(a, grids)
    assert a.grid_escalations == 0 and a.grid_dispatches > 0
    for gi, boxes in enumerate(grids):
        want = _host(boxes)
        assert want == _ref(boxes)
        assert a.get_grid("t", f"g{gi}") == want, gi
    assert a.grid_cells_set > 0


def _one_wave(ops, **kw):
    """Ingest (client, ref, op) rows of one grid as one batch and flush
    once: every op rides one flush, in as few waves as the split rule
    allows."""
    a = _applier(**kw)
    a.attach_grid("t", "g0")
    setup = [{"op": "insertRows", "pos": 0, "count": 4},
             {"op": "insertCols", "pos": 0, "count": 4}]
    boxes = [(0, [(1, 0, 0, setup[0]), (2, 0, 0, setup[1])])]
    boxes += [(c, [(3 + i, ref, 0, op)]) for i, (c, ref, op) in
              enumerate(ops)]
    msgs = _messages(boxes)
    a.logs["g0"] = msgs
    a.ingest_grid_batch("t", "g0", [(m, m.contents) for m in msgs])
    a.flush()
    return a, boxes


def test_the_higher_seq_wins_a_cell_in_one_wave():
    ops = [(1, 2, {"op": "setCell", "row": 1, "col": 2, "value": 5}),
           (2, 2, {"op": "setCell", "row": 1, "col": 2, "value": 6}),
           (3, 2, {"op": "setCell", "row": 1, "col": 2, "value": 7})]
    a, boxes = _one_wave(ops)
    assert a.grid_dispatches == 1  # all three in one wave
    assert a.get_grid("t", "g0") == {(1, 2): 7} == _host(boxes) \
        == _ref(boxes)


def test_a_set_cell_sees_a_vector_insert_of_lower_seq_in_its_wave():
    ops = [(1, 2, {"op": "insertRows", "pos": 0, "count": 2}),
           (1, 3, {"op": "setCell", "row": 0, "col": 0, "value": 9}),
           (2, 2, {"op": "setCell", "row": 0, "col": 1, "value": 8})]
    a, boxes = _one_wave(ops)
    assert a.dispatches == 1 and a.grid_dispatches == 1
    got = a.get_grid("t", "g0")
    # client 1 wrote into a new row; client 2 did not see it: its row 0
    # is now row 2
    assert got == {(0, 0): 9, (2, 1): 8} == _host(boxes) == _ref(boxes)


def test_set_cells_either_side_of_a_vector_op_share_its_wave():
    """setCell, insertRows, setCell, insertCols: one farm wave, the
    first setCell written before its step and the second after; the
    second insert starts the next wave."""
    a, boxes = _one_wave([])
    ops = [(1, 2, {"op": "setCell", "row": 0, "col": 0, "value": 1}),
           (2, 2, {"op": "insertRows", "pos": 0, "count": 1}),
           (1, 4, {"op": "setCell", "row": 0, "col": 0, "value": 2}),
           (2, 4, {"op": "insertCols", "pos": 0, "count": 1})]
    more = [(c, [(3 + i, ref, 0, op)]) for i, (c, ref, op) in
            enumerate(ops)]
    msgs = _messages(more)
    a.logs["g0"] += msgs
    waves, cells = a.dispatches, a.grid_dispatches
    a.ingest_grid_batch("t", "g0", [(m, m.contents) for m in msgs])
    a.flush()
    assert (a.dispatches - waves, a.grid_dispatches - cells) == (2, 2)
    boxes += more
    # the first write went to what is now row 1, the second to the new
    # row 0; a column was inserted before both
    assert a.get_grid("t", "g0") == {(1, 1): 1, (0, 1): 2} \
        == _host(boxes) == _ref(boxes)


def test_a_set_cell_into_a_removed_row_is_a_dead_write():
    ops = [(1, 2, {"op": "setCell", "row": 3, "col": 3, "value": 1}),
           (1, 3, {"op": "removeRows", "pos": 2, "count": 2}),
           (2, 3, {"op": "setCell", "row": 2, "col": 0, "value": 4}),
           (2, 3, {"op": "setCell", "row": 1, "col": 0, "value": 2})]
    a, boxes = _one_wave(ops)
    got = a.get_grid("t", "g0")
    assert got == {(1, 0): 2} == _host(boxes) == _ref(boxes)
    assert a.grid_dead_writes == 1 and a.grid_dispatches == 2


def test_inserts_at_one_position_by_two_clients():
    ops = [(1, 2, {"op": "insertCols", "pos": 1, "count": 2}),
           (2, 2, {"op": "insertCols", "pos": 1, "count": 3}),
           (1, 4, {"op": "setCell", "row": 0, "col": 1, "value": 11}),
           (2, 4, {"op": "setCell", "row": 0, "col": 1, "value": 12}),
           (3, 5, {"op": "setCell", "row": 0, "col": 6, "value": 13})]
    a, boxes = _one_wave(ops)
    got = a.get_grid("t", "g0")
    # the later insert lands first: both clients' column 1 is its first
    # column, and client 2's write, the later, wins
    assert got == {(0, 1): 12, (0, 6): 13} == _host(boxes) == _ref(boxes)


def test_handles_past_h_escalate_and_the_grid_converges_on_the_host():
    boxes = grid_stream(3, VECTOR_MIX, max_count=8, n_ops=400)
    a = _applier(grid_handles=40)
    _feed(a, [boxes])
    assert a.grid_escalations == 1 and 0 in a._host_grids
    assert a.get_grid("t", "g0") == _host(boxes) == _ref(boxes)


def _same_grids(a, b, n):
    for gi in range(n):
        assert a.get_grid("t", f"g{gi}") == b.get_grid("t", f"g{gi}"), gi


@pytest.mark.parametrize("seed", SEEDS)
def test_base_deltas_and_restore_equal_an_uninterrupted_run(tmp_path, seed):
    grids = [grid_stream(f"r{seed}/{g}", VECTOR_MIX if g % 2 else CELL_MIX)
             for g in range(8)]
    whole = _applier()
    _feed(whole, grids)
    a = _applier()
    path = str(tmp_path / "applier")
    ck = ApplierCheckpointer(a, path)
    ck.limit = 1 << 40  # no base from the deltas' growth: deltas alone
    ck.base()
    n = max(len(b) for b in grids)
    half = [b[:n // 2] for b in grids]
    for turn in range(4):  # deltas, one a turn
        _feed(a, [b[turn * len(b) // 4:(turn + 1) * len(b) // 4]
                  for b in half], turns=1)
        set_before = a.grid_cells_set
        written = ck.delta()
        # a delta's grid share follows the cells written, not G x H x H
        assert 0 < written.grid_bytes <= 12 * (a.grid_cells_set
                                               - set_before)
    b = load_applier_checkpoint(path)
    b.set_grid_replay_source(lambda t, d: iter(()))
    b.logs = {}
    assert b.grid_cells_set == 0
    assert np.array_equal(np.asarray(b.grid_written),
                          np.asarray(a.grid_written))
    _same_grids(a, b, 8)
    rest = [bx[n // 2:] for bx in grids]
    for app in (a, b):
        _feed(app, rest)
    _same_grids(whole, b, 8)
    _same_grids(whole, a, 8)


def test_a_base_alone_restores_the_grids(tmp_path):
    grids = [grid_stream(f"b/{g}") for g in range(3)]
    a = _applier()
    _feed(a, grids)
    path = str(tmp_path / "applier")
    ApplierCheckpointer(a, path).base()
    b = load_applier_checkpoint(path)
    _same_grids(a, b, 3)
    assert b.grid_handles_peak == a.grid_handles_peak


def _text_boxes(seed, n_docs=4):
    out = []
    for d in range(n_docs):
        ed = Editor(random.Random(f"text/{seed}/{d}"))
        seq, boxes = 0, []
        for _ in range(30):
            ops = ed.next_wire_ops(3)
            rows = []
            for op in ops:
                seq += 1
                rows.append((seq, seq - 1, 0, op))
            ed.ref_seq = seq
            boxes.append((0, rows))
        out.append(boxes)
    return out


def test_without_grids_the_farm_stages_the_same_waves(monkeypatch):
    """Text docs alone: an applier with no grids and one with grids
    configured stage the same farm wave arrays, and neither runs the
    cells step."""
    waves = {}

    def run(grids):
        a = TpuDocumentApplier(max_docs=16, max_slots=256,
                               ops_per_dispatch=4, grids=grids,
                               grid_handles=64)
        seen = waves.setdefault(grids, [])
        orig = a._execute_wave

        def capture(staged):
            seen.append([np.asarray(x).copy() for x in staged.arrays])
            return orig(staged)

        monkeypatch.setattr(a, "_execute_wave", capture)
        for d, boxes in enumerate(_text_boxes(1)):
            for c, box in boxes:
                a.ingest_batch("t", f"d{d}", [
                    (_msg(c, *row), row[3]) for row in box])
            a.flush()
        a.finalize()
        assert a.grid_dispatches == 0
        assert (a.grid_cells is None) == (grids == 0)
        return a

    plain, with_grids = run(0), run(8)
    assert len(waves[0]) == len(waves[8]) > 0
    for w0, w8 in zip(waves[0], waves[8]):
        assert all(np.array_equal(x, y) for x, y in zip(w0, w8))
    for d in range(4):
        assert plain.get_text("t", f"d{d}") == \
            with_grids.get_text("t", f"d{d}")


def _mixed_doc(seed, grid_first):
    """One doc's stream of text and grid boxcars, renumbered into one
    seq space: [(channel, [message])], the matrix channel's attach
    (channel "attach") before the grid's first boxcar. The text comes
    from one client that sees everything; the grid's from
    ``concurrent_stream``, its refs mapped to the new seqs. Also the
    grid's boxcars in their own numbering, for the host."""
    grid = grid_stream(f"mixed/{seed}", n_ops=120)
    ed = Editor(random.Random(f"mixed-text/{seed}"))
    text = [ed.next_wire_ops(3) for _ in range(12)]
    order = ["grid"] * len(grid) + ["text"] * len(text)
    random.Random(seed).shuffle(order)
    first = "grid" if grid_first else "text"
    order.remove(first)
    order.insert(0, first)
    new_of, seq, out = {0: 0}, 0, []
    gi = ti = 0
    for kind in order:
        if kind == "grid":
            if gi == 0:
                seq += 1
                out.append(("attach", [_msg(9, seq, seq - 1, 0, {
                    "attach": {"type": "shared-matrix"}})]))
            c, box = grid[gi]
            gi += 1
            msgs = []
            for gseq, ref, _msn, op in box:
                seq += 1
                new_of[gseq] = seq
                msgs.append(_msg(c, seq, new_of[ref], 0, {"contents": op}))
        else:
            ops = text[ti]
            ti += 1
            ed.ref_seq = seq
            msgs = []
            for op in ops:
                seq += 1
                msgs.append(_msg(8, seq, seq - 1, 0, {"contents": op}))
        out.append((kind, msgs))
    return out, grid


def _text_of(stream) -> str:
    from fluidframework_tpu.mergetree.client import MergeTreeClient

    oracle = MergeTreeClient("oracle")
    for kind, msgs in stream:
        if kind == "text":
            for m in msgs:
                oracle.apply_msg(replace(m, contents=m.contents["contents"]),
                                 local=False)
    return oracle.get_text()


@pytest.mark.parametrize("grid_first", [True, False],
                         ids=["grid_first", "text_first"])
def test_a_doc_holds_text_beside_its_grid(grid_first):
    """Text and a grid in one doc: three farm rows (its text, the two
    vectors), the text and the cells each the host's, the doc's applied
    seq the last of either channel."""
    stream, grid = _mixed_doc(1, grid_first)
    a = _applier()
    for kind, msgs in stream:
        pairs = [(m, m.contents.get("contents")) for m in msgs]
        if kind == "attach":
            a.attach_grid("t", "d")
        elif kind == "grid":
            a.logs.setdefault("d", []).extend(
                replace(m, contents=op) for m, op in pairs)
            a.ingest_grid_batch("t", "d", pairs)
        else:
            a.ingest_batch("t", "d", pairs)
        a.flush()
    assert a.get_text("t", "d") == _text_of(stream)
    assert a.get_grid("t", "d") == _host(grid) == _ref(grid)
    assert sorted(a.placement.snapshot()["map"]) == [
        "t/d", "t/d\x00cols", "t/d\x00rows"]
    assert a.applied_seq("t", "d") == stream[-1][1][-1].sequence_number
    assert a.grid_escalations == a.host_escalations == 0


@pytest.mark.parametrize("grids", [0, 4])
@pytest.mark.parametrize("grid_first", [True, False],
                         ids=["grid_first", "text_first"])
def test_the_stage_applies_text_beside_a_grid(tmp_path, monkeypatch,
                                              grid_first, grids):
    """Through the applier stage: with grids configured it routes the
    doc's matrix channel to the device beside its text; with none it
    reads the text alone, as a stage without grids always has."""
    from fluidframework_tpu import config
    from fluidframework_tpu.service.durable_log import DurableLog
    from fluidframework_tpu.service.stage_runner import ApplierStage

    monkeypatch.setattr(config, "DEFAULT", replace(
        config.DEFAULT, applier_max_docs=16, applier_max_slots=128,
        applier_grids=grids, applier_grid_handles=64))
    stream, grid = _mixed_doc(2, grid_first)
    log = DurableLog(str(tmp_path / "log"))
    for kind, msgs in stream:
        channel = "text" if kind == "text" else "matrix"
        log.append("deltas/t/d", {"boxcar": [replace(m, contents={
            "kind": "chanop", "address": "default",
            "contents": {"address": channel, **m.contents}})
            for m in msgs]})
    log.flush()
    stage = ApplierStage(str(tmp_path / "log"), str(tmp_path / "state"))
    while stage.run_once():
        pass
    a = stage.applier
    assert a.get_text("t", "d") == _text_of(stream)
    rows = sorted(a.placement.snapshot()["map"])
    if grids:
        assert a.get_grid("t", "d") == _host(grid)
        assert rows == ["t/d", "t/d\x00cols", "t/d\x00rows"]
        assert a.applied_seq("t", "d") == \
            stream[-1][1][-1].sequence_number
    else:
        assert rows == ["t/d"] and a.grid_cells is None
        assert not a._grid_slots and a.grid_escalations == 0
    assert a.host_escalations == 0


def test_a_value_that_is_not_int32_escalates():
    a, boxes = _one_wave([(1, 2, {"op": "setCell", "row": 0, "col": 0,
                                  "value": 5}),
                          (1, 3, {"op": "setCell", "row": 1, "col": 0,
                                  "value": "text"})])
    assert a.grid_escalations == 1
    assert a.get_grid("t", "g0") == {(0, 0): 5, (1, 0): "text"}


def test_replay_of_a_message_with_a_lower_seq_is_skipped():
    a, boxes = _one_wave([(1, 2, {"op": "setCell", "row": 0, "col": 0,
                                  "value": 5})])
    a._escalate_grid(0)
    m = replace(_messages(boxes)[-1], contents={"op": "setCell", "row": 0,
                                                "col": 0, "value": 1})
    a.ingest_grid_batch("t", "g0", [(m, m.contents)])
    assert a.get_grid("t", "g0") == {(0, 0): 5}
