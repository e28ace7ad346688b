"""The grid cell's reference and its reader of the stage's answer.

- ``refmatrix.reference_grid`` equals the host SharedMatrix
  (``dds/matrix.py``) on seeded concurrent streams: the cell's op mix,
  one heavy in row and column edits, and blocks of two runs so that
  every op crosses block boundaries;
- ``answers_grid.device_grids`` reads a small saved checkpoint: each
  device grid's cells equal the reference's, and a grid escalated to the
  host is named as such;
- ``read_grid_log`` reads a grid doc's ops from the durable log.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import answers_grid, refmerge_runs  # noqa: E402
from benchmark.grid_editor import concurrent_stream  # noqa: E402
from benchmark.refmatrix import reference_grid  # noqa: E402

CELL_MIX = {"setCell": 0.92, "insertRows": 0.02, "insertCols": 0.02,
            "removeRows": 0.02, "removeCols": 0.02}
VECTOR_MIX = {"setCell": 0.5, "insertRows": 0.125, "insertCols": 0.125,
              "removeRows": 0.125, "removeCols": 0.125}


def _ops(boxes):
    return [(seq, ref, f"c{c}", op) for c, box in boxes
            for seq, ref, _m, op in box]


def _host(boxes) -> dict:
    from fluidframework_tpu.dds.matrix import SharedMatrix
    from fluidframework_tpu.protocol.messages import (
        MessageType, SequencedDocumentMessage)

    grid = SharedMatrix("matrix")
    for c, box in boxes:
        for seq, ref, msn, op in box:
            grid.process(SequencedDocumentMessage(
                client_id=f"c{c}", sequence_number=seq,
                minimum_sequence_number=msn, client_sequence_number=seq,
                reference_sequence_number=ref, type=MessageType.OPERATION,
                contents=op), local=False)
    return {(r, c): v for r, vals in enumerate(grid.to_lists())
            for c, v in enumerate(vals) if v is not None}


@pytest.mark.parametrize("seed", [0, 7, 42, 1234])
@pytest.mark.parametrize("mix", [CELL_MIX, VECTOR_MIX],
                         ids=["cell_mix", "vector_mix"])
def test_reference_matches_the_host_matrix(seed, mix):
    boxes = concurrent_stream(seed, mix, n_clients=6, n_ops=500, lag=8,
                              max_count=4)
    ops = _ops(boxes)
    assert any(op["op"].startswith("remove") for *_x, op in ops)
    assert any(ref < seq - 1 for seq, ref, _c, _o in ops)  # concurrent
    want = _host(boxes)
    assert reference_grid(ops) == want
    assert want


def test_reference_matches_on_small_blocks(monkeypatch):
    monkeypatch.setattr(refmerge_runs, "BLOCK", 2)
    boxes = concurrent_stream(3, VECTOR_MIX, n_ops=300, max_count=4)
    assert reference_grid(_ops(boxes)) == _host(boxes)


def test_reference_refuses_ops_out_of_order():
    ops = [(2, 0, "a", {"op": "insertRows", "pos": 0, "count": 1}),
           (1, 0, "a", {"op": "insertCols", "pos": 0, "count": 1})]
    with pytest.raises(ValueError, match="not in order"):
        reference_grid(ops)


def test_device_grids_reads_a_saved_checkpoint(tmp_path):
    from fluidframework_tpu.protocol.messages import (
        MessageType, SequencedDocumentMessage)
    from fluidframework_tpu.service.tpu_applier import (
        ApplierCheckpointer, TpuDocumentApplier)

    a = TpuDocumentApplier(max_docs=16, max_slots=128, ops_per_dispatch=4,
                           grids=3, grid_handles=64)
    logs = {}
    a.set_grid_replay_source(lambda t, d: iter(logs.get(d, ())))
    streams = {f"g{i}": concurrent_stream(f"ckpt/{i}", CELL_MIX)
               for i in range(4)}  # the fourth is past the store's 3
    for doc, boxes in streams.items():
        a.attach_grid("t", doc)
        for c, box in boxes:
            pairs = [(SequencedDocumentMessage(
                client_id=f"c{c}", sequence_number=seq,
                minimum_sequence_number=msn, client_sequence_number=seq,
                reference_sequence_number=ref, type=MessageType.OPERATION,
                contents=op), op) for seq, ref, msn, op in box]
            logs.setdefault(doc, []).extend(m for m, _ in pairs)
            a.ingest_grid_batch("t", doc, pairs)
        a.flush()
    path = str(tmp_path / "applier")
    ApplierCheckpointer(a, path).base()
    cells, host = answers_grid.device_grids(path)
    assert host == {("t", "g3")}
    assert set(cells) == {("t", f"g{i}") for i in range(3)}
    for i in range(3):
        want = reference_grid(_ops(streams[f"g{i}"]))
        assert cells[("t", f"g{i}")] == want and want


def test_a_checkpoint_without_grids_reads_as_none(tmp_path):
    from fluidframework_tpu.service.tpu_applier import (
        TpuDocumentApplier, save_applier_checkpoint)

    path = str(tmp_path / "applier")
    save_applier_checkpoint(TpuDocumentApplier(max_docs=8, max_slots=16),
                            path)
    assert answers_grid.device_grids(path) == ({}, set())
